"""Activation checkpointing: remat policies, module API, RNG tracker.

Mirrors reference tests/unit/runtime/activation_checkpointing coverage:
checkpointed forward/backward must equal the un-checkpointed ones for every
policy, and the module-level configure API must behave like the reference's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.transformer_lm import GPT, GPTConfig
from deepspeed_tpu.runtime import activation_checkpointing as ac


@pytest.fixture(autouse=True)
def _clean():
    ac.reset()
    yield
    ac.reset()


def _mlp(w1, w2, x):
    return jnp.sum(jnp.tanh(jnp.tanh(x @ w1) @ w2) ** 2)


def _params():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    return (jax.random.normal(k1, (16, 32)),
            jax.random.normal(k2, (32, 16)),
            jax.random.normal(k3, (4, 16)))


@pytest.mark.parametrize("remat", ["none", "full", "selective"])
def test_checkpoint_matches_plain(remat):
    ac.configure(remat=remat)
    assert ac.is_configured()
    w1, w2, x = _params()

    plain_val = _mlp(w1, w2, x)
    plain_grad = jax.grad(_mlp)(w1, w2, x)

    val = ac.checkpoint(_mlp, w1, w2, x)
    grad = jax.grad(lambda w: ac.checkpoint(_mlp, w, w2, x))(w1)

    np.testing.assert_allclose(np.asarray(val), np.asarray(plain_val),
                               rtol=1e-6)
    # atol floor for near-zero grads: the checkpointed and plain programs
    # compile to different fusion orders, so elements at the 1e-5 scale
    # differ in the last ulps — rtol alone flags them as 4e-3 "errors"
    np.testing.assert_allclose(np.asarray(grad), np.asarray(plain_grad),
                               rtol=1e-5, atol=1e-6)


def test_checkpoint_wrapper_under_jit():
    ac.configure(remat="full")
    w1, w2, x = _params()
    f = ac.checkpoint_wrapper(_mlp)
    g = jax.jit(jax.grad(f))(w1, w2, x)
    np.testing.assert_allclose(np.asarray(g),
                               np.asarray(jax.grad(_mlp)(w1, w2, x)),
                               rtol=1e-4, atol=1e-5)


def test_configure_from_engine_config():
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    cfg = DeepSpeedConfig({
        "train_batch_size": 8,
        "activation_checkpointing": {
            "partition_activations": True,
            "number_checkpoints": 2,
        },
    }, dp_world_size=1)
    state = ac.configure(cfg, remat="selective")
    assert state.config.partition_activations
    assert state.number_checkpoints == 2


def test_policy_mapping():
    cp = jax.checkpoint_policies
    assert ac.policy_from_config(None, "none") is cp.everything_saveable
    assert ac.policy_from_config(None, "full") is cp.nothing_saveable
    assert (ac.policy_from_config(None, "selective")
            is cp.dots_with_no_batch_dims_saveable)
    with pytest.raises(ValueError):
        ac.policy_from_config(None, "bogus")


def test_rng_tracker_deterministic_fork():
    ac.model_parallel_reconfigure(seed=1234, tp_rank=0)
    t = ac.get_rng_tracker()
    a0 = t.fork()
    a1 = t.fork()
    assert not np.array_equal(np.asarray(a0), np.asarray(a1))

    # same seed reproduces the same stream
    ac.model_parallel_reconfigure(seed=1234, tp_rank=0)
    b0 = ac.get_rng_tracker().fork()
    assert np.array_equal(np.asarray(a0), np.asarray(b0))

    # different tp rank decorrelates
    ac.model_parallel_reconfigure(seed=1234, tp_rank=1)
    c0 = ac.get_rng_tracker().fork()
    assert not np.array_equal(np.asarray(a0), np.asarray(c0))


def test_rng_tracker_state_roundtrip():
    ac.model_parallel_reconfigure(seed=7)
    t = ac.get_rng_tracker()
    saved = t.get_states()
    x = t.fork()
    t.set_states(saved)
    y = t.fork()
    assert np.array_equal(np.asarray(x), np.asarray(y))
    with pytest.raises(KeyError):
        t.fork("never-added")


def _gpt(policy, flash, seq):
    cfg = GPTConfig(
        vocab_size=256, n_positions=seq, n_embd=64, n_layer=2, n_head=4,
        dtype=jnp.float32, scan_layers=True, remat=True,
        remat_policy=policy, use_flash_attention=flash)
    return GPT(cfg)


def _loss_and_grads(model, seq):
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, 256, (2, seq)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids, deterministic=True)

    def loss_fn(p):
        return model.apply(p, ids, labels=ids, deterministic=True)

    return jax.value_and_grad(loss_fn)(params)


class TestRematPolicyParity:
    """Remat changes what is recomputed, never what is computed: every
    policy must reproduce ``full``'s loss and gradients exactly."""

    @pytest.mark.parametrize("policy",
                             ["save_dots", "save_nothing_but_flash"])
    @pytest.mark.slow
    def test_einsum_path_parity(self, policy):
        ref_l, ref_g = _loss_and_grads(_gpt("full", False, 64), 64)
        got_l, got_g = _loss_and_grads(_gpt(policy, False, 64), 64)
        np.testing.assert_allclose(got_l, ref_l, rtol=1e-6)
        for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(ref_g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)

    @pytest.mark.parametrize("policy",
                             ["save_dots", "save_nothing_but_flash"])
    @pytest.mark.slow
    def test_flash_path_parity(self, policy):
        # T=128 takes the (interpreted) flash kernel, where the
        # checkpoint_name-tagged attn_out/attn_lse residuals exist
        ref_l, ref_g = _loss_and_grads(_gpt("full", True, 128), 128)
        got_l, got_g = _loss_and_grads(_gpt(policy, True, 128), 128)
        np.testing.assert_allclose(got_l, ref_l, rtol=1e-6)
        for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(ref_g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
