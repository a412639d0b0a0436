"""A stack of window and full attention layers TRAINED (no cache), with a
router that reads the block's input and ReLU-gated experts of which a share
is held: the program's ``GPT`` as ``perfbench/builders/smallthinker_train.py``
builds it, at a tiny size in float32 on the CPU, against the plain
reference ``perfbench/reference/smallthinker.py``: logits, loss, every
parameter group's gradient; each assumed item switched off in the
reference; the window's edge to the bit; the four shares of a layer against
the uncut layer, forward and backward; the cut's parameter count without
allocating; and the same model served through its caches."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import serving
from deepspeed_tpu.models.transformer_lm import GPT
from deepspeed_tpu.moe.layer import MoE
from deepspeed_tpu.telemetry.bus import KIND_FLASH_PLAN, telemetry_bus
from perfbench.builders import smallthinker_train
from perfbench.reference import smallthinker as reference
from smallthinker_tiny import CONFIG, TINY_SMALLTHINKER

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# two periods, so that the second full layer reads what window layers wrote
TINY = dict(TINY_SMALLTHINKER, num_hidden_layers=8)
WINDOW = TINY["sliding_window_size"]
VOCAB = TINY["vocab_size"]
T = 48                                      # six windows long


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def model_config(config=TINY, **changes):
    return dataclasses.replace(smallthinker_train.model_config(
        config, config["train"], config["max_position_embeddings"]),
        **changes)


def reference_kw(cfg, **changes):
    return dict(dict(
        layer_types=cfg.layer_types, window=cfg.sliding_window,
        n_head=cfg.n_head, n_kv_head=cfg.kv_heads, head_dim=cfg.head_dim,
        top_k=cfg.moe_top_k, first=cfg.moe_experts_held[0],
        eps=cfg.layer_norm_epsilon, theta=cfg.rope_theta), **changes)


@pytest.fixture(scope="module")
def trained():
    """Model, seeded parameters and ids, the system's logits, loss and
    gradients."""
    cfg = model_config()
    model = GPT(cfg)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, VOCAB, (2, T)),
                      jnp.int32)
    with jax.default_matmul_precision("highest"):
        params = model.init(jax.random.PRNGKey(0), ids)["params"]
        loss, grads = jax.value_and_grad(lambda p: model.apply(
            {"params": p}, ids, labels=ids))(params)
        logits = model.apply({"params": params}, ids)
    return cfg, model, params, ids, logits, loss, grads


def test_the_declaration(trained):
    cfg = trained[0]
    assert cfg.layer_types == ("attention", "window", "window", "window") * 2
    assert cfg.rotary_kinds == ("window",) and cfg.sliding_window == WINDOW
    assert (cfg.moe_router_input, cfg.moe_expert_activation) == (
        "block", "relu")
    assert cfg.moe_experts_held == (2, 4) and cfg.moe_num_experts == 8
    assert cfg.moe_norm_topk_prob and cfg.moe_aux_loss_coef == 0.0
    with pytest.raises(ValueError, match="moe_router_input"):
        model_config(moe_router_input="attention")
    with pytest.raises(ValueError, match="moe_expert_activation"):
        model_config(moe_expert_activation="swish")


def test_logits_loss_and_every_gradient_are_the_references(trained):
    cfg, _, params, ids, logits, loss, grads = trained
    want_loss, _, want = reference.loss_and_grads(params, ids,
                                                  **reference_kw(cfg))
    _, want_logits, _ = reference.forward(params, ids, **reference_kw(cfg))
    np.testing.assert_allclose(logits, want_logits, atol=2e-5)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(flat) == 19      # two kinds' eight leaves, head, norm, table
    for (path, got), ref in zip(flat, jax.tree.leaves(want)):
        assert float(jnp.linalg.norm(got - ref)) < 1e-4 * float(
            jnp.linalg.norm(ref)), jax.tree_util.keystr(path)


@pytest.mark.parametrize("assumed", [
    "router_reads_the_normed_stream", "router_reads_the_attended_stream",
    "silu_for_relu", "rotary_on_a_full_layer", "a_window_one_wider",
    "no_window"])
def test_each_assumed_item_switched_off_is_another_model(trained, assumed):
    cfg, _, params, ids, logits, loss, _ = trained
    other = {"router_reads_the_normed_stream": dict(router_reads="mlp"),
             "router_reads_the_attended_stream":
                 dict(router_reads="attended"),
             "silu_for_relu": dict(activation="silu"),
             "rotary_on_a_full_layer": dict(rotate_full=True),
             "a_window_one_wider": dict(window=WINDOW + 1),
             "no_window": dict(window_layers=False)}[assumed]
    ce, their_logits, _ = reference.forward(params, ids,
                                            **reference_kw(cfg, **other))
    assert float(jnp.abs(their_logits - logits).max()) > 1e-2
    assert abs(float(ce) - float(loss)) > 1e-4


def test_the_program_with_an_item_switched_off_leaves_the_reference(trained):
    """The same from the program's side: each declared field, changed,
    is the reference's variant and not the published model."""
    cfg, _, params, ids, logits, _, _ = trained
    for changed, other in ((dict(moe_router_input="mlp"),
                            dict(router_reads="mlp")),
                           (dict(moe_expert_activation="silu"),
                            dict(activation="silu")),
                           (dict(rotary_kinds=None),
                            dict(rotate_full=True))):
        theirs = GPT(model_config(**changed)).apply({"params": params}, ids)
        assert float(jnp.abs(theirs - logits).max()) > 1e-2
        _, want, _ = reference.forward(params, ids,
                                       **reference_kw(cfg, **other))
        np.testing.assert_allclose(theirs, want, atol=2e-5)


def test_the_windows_edge_to_the_bit():
    """One window layer: another token at position j changes the outputs
    at j .. j + window - 1 and leaves position j + window as it was, bit
    for bit."""
    config = dict(TINY, num_hidden_layers=1, rope_layout=[1],
                  sliding_window_layout=[1])
    model = GPT(model_config(config))
    ids = np.random.default_rng(2).integers(0, VOCAB, (1, T))
    params = model.init(jax.random.PRNGKey(1), jnp.asarray(ids))["params"]
    j = 11
    other = ids.copy()
    other[0, j] = (ids[0, j] + 1) % VOCAB
    a, b = (np.asarray(model.apply({"params": params}, jnp.asarray(x))[0])
            for x in (ids, other))
    changed = np.abs(a - b).max(-1) > 0
    assert changed[j:j + WINDOW].all()
    assert not changed[:j].any() and not changed[j + WINDOW:].any()


def test_the_flash_kernels_under_the_kinds_are_the_einsum_form():
    """At lengths the kernels take (whole lane tiles of positions and of
    the window) the call without a cache runs them (interpreted here),
    grouped queries and window included, and gives the einsum form's
    logits and gradients."""
    config = dict(TINY, num_hidden_layers=4, sliding_window_size=128,
                  max_position_embeddings=256)
    ids = jnp.asarray(np.random.default_rng(3).integers(0, VOCAB, (1, 256)),
                      jnp.int32)
    einsum, flash = (GPT(model_config(config, use_flash_attention=on))
                     for on in (False, True))
    params = einsum.init(jax.random.PRNGKey(2), ids[:, :8])["params"]
    plans = []

    def on(event):
        if event["kind"] == KIND_FLASH_PLAN:
            plans.append(event.get("window"))

    telemetry_bus.subscribe(on)
    try:
        jax.jit(lambda p: einsum.apply({"params": p}, ids)).lower(params)
        assert plans == []
        jax.jit(lambda p: flash.apply({"params": p}, ids)).lower(params)
    finally:
        telemetry_bus.unsubscribe(on)
    # the full layer's launch, then the window layers' (one scan body)
    assert plans == [None, 128]
    want, got = (jax.value_and_grad(lambda p, m=m: m.apply(
        {"params": p}, ids, labels=ids))(params) for m in (einsum, flash))
    assert abs(float(want[0]) - float(got[0])) < 1e-5
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        assert float(jnp.linalg.norm(a - b)) <= 1e-4 * float(
            jnp.linalg.norm(b))


def test_the_four_shares_are_the_uncut_layer_forward_and_backward():
    """Four chips hold 4 of 16 experts each: with one cotangent, the
    shares' outputs, input gradients and router gradients add up to the
    uncut layer's, and each share's held matrices get the uncut layer's
    gradient for those experts."""
    def layer(held):
        return MoE(d_model=32, d_hidden=16, num_experts=16, k=6,
                   drop_tokens=False, gated_experts=True,
                   expert_activation=jax.nn.relu, norm_topk_prob=True,
                   experts_held=held, dtype=jnp.float32,
                   param_dtype=jnp.float32)

    x, scored, cot = (jax.random.normal(jax.random.PRNGKey(n), (1, 24, 32))
                      for n in (4, 5, 6))
    params = layer(None).init(jax.random.PRNGKey(7), x)["params"]

    def out_and_grads(held, params):
        def f(params, x, scored):
            return layer(held).apply({"params": params}, x,
                                     router_input=scored)[0]
        y, vjp = jax.vjp(f, params, x, scored)
        return (y,) + vjp(cot)

    whole = out_and_grads(None, params)
    y = dx = dscored = dgate = 0.0
    for first in range(0, 16, 4):
        mine = dict(params, experts=jax.tree.map(
            lambda a: a[first:first + 4], params["experts"]))
        yi, dp, dxi, dsi = out_and_grads((first, 4), mine)
        y, dx, dscored = y + yi, dx + dxi, dscored + dsi
        dgate = dgate + dp["gate"]["kernel"]
        for name, got in dp["experts"].items():
            np.testing.assert_allclose(
                got, whole[1]["experts"][name][first:first + 4], atol=1e-5,
                err_msg=f"{name} of the share from {first}")
    for got, want in ((y, whole[0]), (dx, whole[2]), (dscored, whole[3]),
                      (dgate, whole[1]["gate"]["kernel"])):
        np.testing.assert_allclose(got, want, atol=1e-5)
    # the router read ``scored`` and the experts ``x``: neither gradient
    # is the other's
    assert float(jnp.abs(whole[2] - whole[3]).max()) > 1e-3


def test_the_cuts_parameter_count_without_allocating():
    with open(os.path.join(REPO, "perfbench", "configs", CONFIG + ".json"),
              encoding="utf-8") as f:
        config = json.load(f)
    cfg = model_config(config)
    shapes = jax.eval_shape(lambda: GPT(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    counted = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    c, f, v = config["hidden_size"], config["moe_ffn_hidden_size"], \
        config["model"]["vocab_size"]
    attention = c * 128 * (2 * 28 + 2 * 4)
    layer = attention + c * 64 + 16 * 3 * c * f + 2 * c
    assert (attention, 16 * 3 * c * f) == (20_971_520, 94_371_840)
    assert counted == config["num_hidden_layers"] * layer + 2 * v * c + c \
        == 656_529_920
    whole = attention + c * 64 + 64 * 3 * c * f + 2 * c
    assert round(whole / 1e6, 1) == 398.6


def test_served_through_its_caches_the_model_is_the_references():
    """``init_inference`` -> ``build_serving``: prompts prefilled in passes
    and decoded through ``KindCache`` give the reference's greedy token
    after every prefix of prompt + tokens so far: the block is shared, so a served model
    routes on the block's input too."""
    config = dict(TINY, num_hidden_layers=4)
    cfg = model_config(config, window_slack=4, remat=False)
    eng = deepspeed_tpu.init_inference(GPT(cfg), dtype="fp32", seed=3)
    sched = serving.build_serving(eng, {"slots": 2, "prompt_bucket": 8})
    prompts = [np.random.default_rng([5, n]).integers(
        0, VOCAB, size=n).tolist() for n in (5, 19)]
    got = {}
    rids = [sched.submit(p, max_new_tokens=6,
                         stream_callback=lambda r, t, d: got.setdefault(
                             r, []).append(int(t))) for p in prompts]
    sched.run()
    kw = reference_kw(cfg)
    for rid, prompt in zip(rids, prompts):
        # causal: one pass over prompt + served tokens gives the reference's
        # next token after every prefix
        seq = list(prompt) + got[rid]
        _, logits, _ = reference.forward(
            eng.params, jnp.asarray([seq], jnp.int32), **kw)
        want = np.asarray(logits[0, len(prompt) - 1:-1].argmax(-1))
        assert len(got[rid]) == 6 and got[rid] == want.tolist()
