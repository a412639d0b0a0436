"""sha256 of the lowered text of the programs the benchmark's GPT and MoE
cells run, at a small size on the CPU: ``jit_prefill`` at two prompt
buckets, ``jit_decode_k``, the scheduler's ``splice``, and the train step of
a dense and of a dropless-MoE decoder (PR 30's method). Run as a script it
prints them as JSON; ``tests/unit/data/gpt_program_hashes.json`` holds what
it printed on the parent of the PR that brought the hybrid block
(534fd1d), and ``test_falcon_h1.py`` holds this tree to it.

PR 34 moved decode attention of one query token over dense storage into a
Pallas kernel: ``jit_decode_k`` ALONE was recorded again, on that PR's
tree, and the other five entries stand as recorded on 534fd1d. The
``fallback_hashes`` entries (the decode calls that kernel does not take)
were recorded on PR 34's parent, d382f5d. The hybrid, retention, latent and
speculative entries say in their functions' docstrings where each was
recorded. The splice, copy and rewind programs are reached through the
scheduler's ``lane_cache`` (inference/lane_cache.py) since PR 46, which
moved them there; what they lower to is what it was.

PR 62 traces the admission prefill once for all prompt buckets where the
model allows it (``InferenceEngine.plan_prefill``): ``jit_prefill[64]`` and
``jit_prefill[128]`` were recorded again on that PR's tree (the module is
now a call into the exported one) and nothing else was: the rule
(``GPTConfig.prefill_bucket_dependence``) refuses every other family, whose
engines keep ``jax.jit(prefill)``."""
import hashlib
import json
import os
import sys

if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
        " --xla_force_host_platform_device_count=8"
    # run from a checkout's root (``python tests/unit/gpt_program_hashes.py``)
    # to hash THAT checkout
    sys.path.insert(0, os.getcwd())
    sys.path.insert(0, os.path.join(os.getcwd(), "tests", "perfbench"))

import jax
import jax.numpy as jnp
import numpy as np


def _sha(text):
    return hashlib.sha256(text.encode()).hexdigest()


def serve_hashes():
    """A GPT-2-shaped decoder (LayerNorm, learned positions, tied head,
    full heads) served in bf16 on four lanes."""
    import deepspeed_tpu
    from deepspeed_tpu import serving
    from deepspeed_tpu.models.transformer_lm import GPT, GPTConfig
    from deepspeed_tpu.parallel.mesh import reset_default_topology

    reset_default_topology()
    cfg = GPTConfig(vocab_size=256, n_positions=256, n_embd=64, n_layer=3,
                    n_head=2, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                    scan_layers=True, use_flash_attention="auto")
    eng = deepspeed_tpu.init_inference(GPT(cfg), dtype="bf16", seed=5)
    sched = serving.build_serving(eng, {"slots": 4, "prompt_bucket": 64})
    sched._ensure_compiled()
    out = {}
    subs = {}
    for bucket in (64, 128):
        ids = jnp.zeros((1, bucket), jnp.int32)
        mask = jnp.ones((1, bucket), jnp.bool_)
        out["jit_prefill[%d]" % bucket] = _sha(
            eng._prefill_fn.fn.lower(eng.params, ids, mask).as_text())
        subs[bucket] = jax.eval_shape(eng._prefill_fn.fn, eng.params, ids,
                                      mask)[1]
    cache = sched.lane_cache.shapes
    out["jit_decode_k"] = _sha(eng._decode_k_fn.fn.lower(
        eng.params, jnp.zeros((4,), jnp.int32), cache,
        jax.random.PRNGKey(0), jnp.float32(0.0), 1).as_text())
    full = sched._empty_cache()
    sub = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), subs[64])
    sched._splice(full, sub, 1)
    out["jit_splice"] = _sha(sched.lane_cache._splice_fn.fn.lower(
        sched.lane_cache.shapes, subs[64], jnp.int32(1)).as_text())
    return out


def train_hashes():
    """The train step of a dense GPT-2-shaped decoder and of a dropless MoE
    decoder (OLMoE's shape in small), pure bf16 under ZeRO-1 on one
    device, as the benchmark's one-chip training cells run."""
    import deepspeed_tpu
    from deepspeed_tpu.models.transformer_lm import GPT, GPTConfig
    from deepspeed_tpu.parallel.mesh import (
        MeshTopology,
        reset_default_topology,
        set_default_topology,
    )
    from deepspeed_tpu.runtime import engine as engine_mod

    models = {
        "dense": GPTConfig(
            vocab_size=256, n_positions=64, n_embd=64, n_layer=3, n_head=2,
            dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, scan_layers=True,
            remat=True, remat_policy="full", use_flash_attention="auto"),
        "moe": GPTConfig(
            vocab_size=256, n_positions=64, n_embd=64, n_layer=2, n_head=4,
            intermediate_size=32, norm="rmsnorm", activation="silu",
            use_bias=False, rotary=True, learned_positions=False,
            tie_word_embeddings=False, qk_norm=True, dtype=jnp.bfloat16,
            param_dtype=jnp.bfloat16, remat=True, scan_layers=True,
            use_flash_attention=False, moe_num_experts=8, moe_top_k=3,
            moe_drop_tokens=False, moe_gated_experts=True,
            moe_aux_loss_coef=0.01, moe_z_loss_coef=0.001)}
    out = {}
    for name, cfg in models.items():
        reset_default_topology()
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=GPT(cfg), topology=MeshTopology(devices=jax.devices()[:1]),
            seed=3, config={
                "train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 1,
                "bf16": {"enabled": True}, "gradient_clipping": 1.0,
                "optimizer": {"type": "FusedAdam", "params": {
                    "lr": 2e-4, "betas": [0.9, 0.95], "weight_decay": 0.1}},
                "zero_optimization": {"stage": 1},
                "steps_per_print": 10 ** 9})
        ids = np.random.RandomState(0).randint(0, 256, (2, 64)).astype(
            np.int32)
        batch = {"input_ids": ids, "labels": ids}
        set_default_topology(engine.topology)
        engine._init_state(dict(batch))
        engine._put_batch(dict(batch))
        avals = engine_mod._avals_like
        out["jit_train_step[%s]" % name] = _sha(
            engine._build_train_step().lower(
                avals(engine._params), avals(engine._opt_state),
                avals(engine._ls_state), engine._last_batch_aval,
                avals(engine._rng), engine.micro_steps,
                jnp.float32(1.0)).as_text())
    return out


def fallback_hashes():
    """The decode calls that the block-skipping attention kernel (PR 34,
    ops/pallas/decode_attention.py) leaves on the einsums: more than one
    query token over a cache that exists (speculative verification, a
    chunked prefill's continuation), the ring cache of a window layout,
    int8 KV storage and ALiBi. Recorded on PR 34's parent (d382f5d)."""
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models.transformer_lm import GPT, GPTConfig
    from deepspeed_tpu.ops.sparse_attention.sparse_attention_utils import \
        apply_sparse_attention
    from deepspeed_tpu.parallel.mesh import reset_default_topology

    base = dict(vocab_size=128, n_positions=256, n_embd=32, n_layer=2,
                n_head=4, dtype=jnp.float32, scan_layers=True)
    models = {
        "dense": GPT(GPTConfig(**base)),
        "ring": apply_sparse_attention(
            GPT(GPTConfig(rotary=True, learned_positions=False, **base)),
            {"mode": "local_sliding_window", "block": 16,
             "num_sliding_window_blocks": 3}),
        "int8": GPT(GPTConfig(kv_cache_dtype="int8", **base)),
        "alibi": GPT(GPTConfig(alibi=True, learned_positions=False,
                               **base)),
    }
    out = {}
    for name, model in models.items():
        reset_default_topology()
        eng = InferenceEngine(model, {"dtype": "fp32"}, seed=0)
        eng._materialize(jnp.zeros((1, 64), jnp.int32))
        eng._build_decode_fns()
        ids = jnp.zeros((3, 16), jnp.int32)
        cache = jax.eval_shape(eng._prefill_fn.fn, eng.params, ids,
                               jnp.ones((3, 16), jnp.bool_))[1]
        if name == "dense":
            out["jit_verify_greedy[T=3]"] = _sha(
                eng._verify_greedy_fn.fn.lower(
                    eng.params, jnp.zeros((3, 3), jnp.int32),
                    cache).as_text())
            out["jit_prefill_more[16]"] = _sha(
                eng._prefill_more_fn.fn.lower(
                    eng.params, ids, jnp.ones((3, 16), jnp.bool_),
                    cache).as_text())
            continue
        out["jit_decode_k[%s]" % name] = _sha(eng._decode_k_fn.fn.lower(
            eng.params, jnp.zeros((3,), jnp.int32), cache,
            jax.random.PRNGKey(0), jnp.float32(0.0), 1).as_text())
    return out


def hybrid_hashes():
    """The tiny hybrid configuration (a Mamba-2 mixer beside attention,
    ``tests/perfbench/falcon_h1_tiny.py``) served in bf16 on three lanes:
    ``jit_prefill``, ``jit_decode_k`` and the scheduler's ``splice``.
    Recorded on d382f5d + PR 34 (2b32c9c), the parent of the PR that made
    a model declare its recurrent leaves; ``hybrid_jit_decode_k`` ALONE
    was recorded again on PR 36's tree (on e55e290), which moved the
    one-token recurrence of the mixer into the Pallas kernel ``ssm_step``
    (ops/pallas/ssd_step.py)."""
    import deepspeed_tpu
    from deepspeed_tpu import serving
    from deepspeed_tpu.models.transformer_lm import GPT
    from deepspeed_tpu.parallel.mesh import reset_default_topology
    from falcon_h1_tiny import TINY_FALCON_H1
    from perfbench.builders import falcon_h1_serve

    reset_default_topology()
    section = dict(TINY_FALCON_H1["serve"], param_dtype="bfloat16",
                   compute_dtype="bfloat16")
    eng = deepspeed_tpu.init_inference(
        GPT(falcon_h1_serve.model_config(TINY_FALCON_H1, section)),
        dtype="bf16", seed=5)
    sched = serving.build_serving(eng, {"slots": 3, "prompt_bucket": 16})
    sched._ensure_compiled()
    ids = jnp.zeros((1, 32), jnp.int32)
    mask = jnp.ones((1, 32), jnp.bool_)
    out = {"hybrid_jit_prefill[32]": _sha(
        eng._prefill_fn.fn.lower(eng.params, ids, mask).as_text())}
    sub = jax.eval_shape(eng._prefill_fn.fn, eng.params, ids, mask)[1]
    cache = sched.lane_cache.shapes
    out["hybrid_jit_decode_k"] = _sha(eng._decode_k_fn.fn.lower(
        eng.params, jnp.zeros((3,), jnp.int32), cache,
        jax.random.PRNGKey(0), jnp.float32(0.0), 1).as_text())
    sched._splice(sched._empty_cache(),
                  jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), sub), 1)
    out["hybrid_jit_splice"] = _sha(sched.lane_cache._splice_fn.fn.lower(
        cache, sub, jnp.int32(1)).as_text())
    return out


def retention_hashes():
    """The tiny attention-free configuration (power retention in place of
    attention, ``tests/perfbench/brumby_tiny.py``) served in bf16 on three
    lanes: ``jit_prefill`` and ``jit_decode_k``. Recorded on e55e290, the
    parent of the PR that gave the hybrid decode step its kernel and moved
    the two mixers' ``step_kernel`` predicate to one place."""
    import deepspeed_tpu
    from brumby_tiny import TINY_BRUMBY
    from deepspeed_tpu import serving
    from deepspeed_tpu.models.transformer_lm import GPT
    from deepspeed_tpu.parallel.mesh import reset_default_topology
    from perfbench.builders import brumby_serve

    reset_default_topology()
    section = dict(TINY_BRUMBY["serve"], param_dtype="bfloat16",
                   compute_dtype="bfloat16")
    eng = deepspeed_tpu.init_inference(
        GPT(brumby_serve.model_config(TINY_BRUMBY, section)),
        dtype="bf16", seed=5)
    sched = serving.build_serving(eng, {"slots": 3, "prompt_bucket": 16})
    sched._ensure_compiled()
    ids = jnp.zeros((1, 32), jnp.int32)
    mask = jnp.ones((1, 32), jnp.bool_)
    return {
        "retention_jit_prefill[32]": _sha(
            eng._prefill_fn.fn.lower(eng.params, ids, mask).as_text()),
        "retention_jit_decode_k": _sha(eng._decode_k_fn.fn.lower(
            eng.params, jnp.zeros((3,), jnp.int32), sched.lane_cache.shapes,
            jax.random.PRNGKey(0), jnp.float32(0.0), 1).as_text())}


def latent_hashes():
    """The tiny latent-attention configuration (DeepSeek-V2's block,
    ``tests/perfbench/deepseek_v2_tiny.py``) served in bf16 on three
    lanes: ``jit_prefill`` (the per-head form), the scheduler's
    ``splice``, ``jit_prefill_more`` (a continuation of sixteen tokens
    over a cache that exists: the absorbed einsums) and ``jit_decode_k``.
    The first three were recorded on 0ed240d, the parent of the PR that
    gave the absorbed decode step its kernel ``mla_decode_attn``
    (ops/pallas/latent_decode_attention.py): the pass that makes a lane's
    cache plans no kernel work, the einsum route is the parent's byte for
    byte, and both lower as they did. ``latent_jit_decode_k`` was recorded
    on that PR's tree, with the kernel in it. PR 55 (parent 63e2c31) holds
    the second query projection's 2-D product as a value before the
    per-head view is taken (an ``optimization_barrier`` in every form of
    the layer): ``latent_jit_prefill[32]``, ``latent_jit_prefill_more[16]``
    and ``latent_jit_decode_k`` were recorded again on that PR's tree;
    ``latent_jit_splice`` and every other family's entries stand as they
    were, which is the proof that no other program moved."""
    import deepspeed_tpu
    from deepseek_v2_tiny import TINY_DEEPSEEK
    from deepspeed_tpu import serving
    from deepspeed_tpu.models.transformer_lm import GPT
    from deepspeed_tpu.parallel.mesh import reset_default_topology
    from perfbench.builders import deepseek_v2_serve

    reset_default_topology()
    section = dict(TINY_DEEPSEEK["serve"], param_dtype="bfloat16",
                   compute_dtype="bfloat16")
    eng = deepspeed_tpu.init_inference(
        GPT(deepseek_v2_serve.model_config(TINY_DEEPSEEK, section)),
        dtype="bf16", seed=5)
    sched = serving.build_serving(eng, {"slots": 3, "prompt_bucket": 16})
    sched._ensure_compiled()
    ids = jnp.zeros((1, 32), jnp.int32)
    mask = jnp.ones((1, 32), jnp.bool_)
    out = {"latent_jit_prefill[32]": _sha(
        eng._prefill_fn.fn.lower(eng.params, ids, mask).as_text())}
    sub = jax.eval_shape(eng._prefill_fn.fn, eng.params, ids, mask)[1]
    cache = sched.lane_cache.shapes
    out["latent_jit_decode_k"] = _sha(eng._decode_k_fn.fn.lower(
        eng.params, jnp.zeros((3,), jnp.int32), cache,
        jax.random.PRNGKey(0), jnp.float32(0.0), 1).as_text())
    sched._splice(sched._empty_cache(),
                  jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), sub), 1)
    out["latent_jit_splice"] = _sha(sched.lane_cache._splice_fn.fn.lower(
        cache, sub, jnp.int32(1)).as_text())
    out["latent_jit_prefill_more[16]"] = _sha(eng._prefill_more_fn.fn.lower(
        eng.params, jnp.zeros((1, 16), jnp.int32),
        jnp.ones((1, 16), jnp.bool_), sub).as_text())
    return out


def speculative_hashes():
    """The programs that only a speculative scheduler dispatches,
    ``jit_copy_tree`` and ``jit_rewind``, over a dense and over a ring lane
    cache (``fallback_hashes``' models, the ring one with the slack block
    speculation needs; each is its own draft), and the plain loop's
    ``jit_set_token``. Recorded on 7a4dead, the parent of the PR that moved
    the first two behind inference/lane_cache.py."""
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.scheduler import ContinuousBatchingScheduler
    from deepspeed_tpu.models.transformer_lm import GPT, GPTConfig
    from deepspeed_tpu.ops.sparse_attention.sparse_attention_utils import \
        apply_sparse_attention
    from deepspeed_tpu.parallel.mesh import reset_default_topology

    base = dict(vocab_size=128, n_positions=256, n_embd=32, n_layer=2,
                n_head=4, dtype=jnp.float32, scan_layers=True)
    models = {
        "dense": GPT(GPTConfig(**base)),
        "ring": apply_sparse_attention(
            GPT(GPTConfig(rotary=True, learned_positions=False,
                          kv_cache_slack_blocks=1, **base)),
            {"mode": "local_sliding_window", "block": 16,
             "num_sliding_window_blocks": 3}),
    }
    out = {}
    delta = jnp.zeros((3,), jnp.int32)
    for name, model in models.items():
        reset_default_topology()
        eng = InferenceEngine(model, {"dtype": "fp32"}, seed=0)
        sched = ContinuousBatchingScheduler(
            eng, slots=3, prompt_bucket=16, draft_engine=eng, spec_k=2)
        sched._ensure_compiled()
        lanes = sched.lane_cache
        cache = sched._empty_cache()
        sched._rewind(sched._copy_tree(cache), cache, delta)
        out["jit_copy_tree[%s]" % name] = _sha(
            lanes._copy_fn.fn.lower(lanes.shapes).as_text())
        out["jit_rewind[%s]" % name] = _sha(lanes._rewind_fn.fn.lower(
            lanes.shapes, lanes.shapes, delta).as_text())
    sched = ContinuousBatchingScheduler(eng, slots=3, prompt_bucket=16)
    sched._set_token(delta, 1, jnp.zeros((1,), jnp.int32))
    out["jit_set_token"] = _sha(sched._set_token_fn.fn.lower(
        delta, np.int32(1), jnp.zeros((1,), jnp.int32)).as_text())
    return out


def all_hashes():
    return dict(serve_hashes(), **train_hashes(), **fallback_hashes(),
                **hybrid_hashes(), **retention_hashes(), **latent_hashes(),
                **speculative_hashes())


if __name__ == "__main__":
    print(json.dumps(all_hashes(), indent=1, sort_keys=True))
