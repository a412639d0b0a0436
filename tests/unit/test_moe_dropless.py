"""The dropless MoE path (sort, gather, grouped matmul, weighted sum back)
against the plain reference ``perfbench/reference/olmoe.py`` on seeded
weights, at a small size on the CPU.

Tolerances: both sides compute in float32 here, so they differ only by the
order of the float32 sums (the grouped matmul sums one expert's rows, the
reference a dense masked product; attention and norms are the same
formulas). That is ~1e-6 relative on a value and grows with the depth of
the backward pass; 2e-4 of a group's norm covers it with a margin of ten
and is a thousand times below what a dropped token, a renormalised weight
or a bfloat16 matmul changes (1e-2 and more, see the cases below).
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.transformer_lm import GPT, GPTConfig
from deepspeed_tpu.moe import experts as experts_mod
from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.moe.experts import StackedExperts
from deepspeed_tpu.moe.layer import MoE
from deepspeed_tpu.moe.utils import publish_expert_load, routing_stats
from deepspeed_tpu.parallel.mesh import MeshTopology, reset_default_topology
from perfbench.reference import olmoe

TOL = 2e-4
SIZES = [(8, 3), (64, 8)]       # (experts, experts per token)
# at widths of 128 the grouped matmuls are the Pallas kernel
# (ops/pallas/grouped_matmul.py; moe/experts.py grouped_matmul_tiles); the
# narrower models above run jax.lax.ragged_dot
WIDE = dict(n_embd=128, intermediate_size=128)
GROUPS = {"experts": ("mlp/experts/",), "router": ("mlp/gate/",),
          "attention": ("attn/",), "norms": ("ln_1/", "ln_2/"),
          "embedding": ("wte/",), "head": ("lm_head", "ln_f/")}


def config(experts, top_k, **kw):
    base = dict(
        vocab_size=256, n_positions=32, n_embd=64, n_layer=2, n_head=4,
        intermediate_size=32, norm="rmsnorm", activation="silu",
        use_bias=False, rotary=True, learned_positions=False,
        tie_word_embeddings=False, qk_norm=True, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=True, scan_layers=True,
        use_flash_attention=False, moe_num_experts=experts, moe_top_k=top_k,
        moe_drop_tokens=False, moe_gated_experts=True,
        moe_aux_loss_coef=0.01, moe_z_loss_coef=0.001)
    base.update(kw)
    return GPTConfig(**base)


def seeded(cfg, seed=0, batch=2):
    ids = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, size=(batch, 32), dtype=np.int32)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(seed), ids)["params"]
    # seeded weights as a trained router has them: far enough from uniform
    # that the k-th and (k+1)-th probabilities are not within rounding
    params["h"]["block"]["mlp"]["gate"]["kernel"] *= 8.0
    return model, params, ids


def ref_kw(cfg):
    return dict(n_head=cfg.n_head, top_k=cfg.moe_top_k,
                eps=cfg.layer_norm_epsilon, theta=cfg.rope_theta,
                renormalize=cfg.moe_norm_topk_prob)


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def by_group(tree):
    flat = {"/".join(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}
    out = {}
    for group, marks in GROUPS.items():
        leaves = [v.reshape(-1) for k, v in sorted(flat.items())
                  if any(m in k for m in marks)]
        assert leaves, group
        out[group] = jnp.concatenate(leaves)
    assert sum(v.size for v in out.values()) \
        == sum(v.size for v in flat.values())      # every leaf in one group
    return out


@pytest.fixture(scope="module", params=SIZES + [(8, 3, WIDE)],
                ids=lambda s: f"e{s[0]}k{s[1]}" + "-kernel" * (len(s) > 2))
def both(request):
    """System and reference on one seeded model: logits, loss, routing and
    gradients of each."""
    cfg = config(*request.param[:2], **dict(*request.param[2:]))
    assert (experts_mod.grouped_matmul_tiles(
        2 * 32 * cfg.moe_top_k, cfg.n_embd, cfg.intermediate_size,
        cfg.moe_num_experts, cfg.dtype) is not None) == (
            len(request.param) > 2)
    model, params, ids = seeded(cfg)
    # each one compiled program, as the engine's step is, and not the
    # model's operations dispatched (and compiled) one by one
    sys_logits = jax.jit(model.apply)({"params": params}, ids)
    sys_loss, sys_grads = jax.jit(jax.value_and_grad(
        lambda p: model.apply({"params": p}, ids, labels=ids)))(params)
    _, ref_logits, ref_chosen, _, _ = olmoe.forward(params, ids,
                                                    **ref_kw(cfg))
    ref_loss, (_, _), ref_grads = olmoe.loss_and_grads(
        params, ids, balance_coef=cfg.moe_aux_loss_coef,
        z_coef=cfg.moe_z_loss_coef, **ref_kw(cfg))
    stats = routing_stats(model, params, {"input_ids": ids})
    return dict(cfg=cfg, model=model, params=params, ids=ids,
                sys=(sys_logits, sys_loss, sys_grads),
                ref=(ref_logits, ref_loss, ref_grads, ref_chosen),
                stats=stats)


def test_logits_match_the_reference(both):
    assert rel(both["sys"][0], both["ref"][0]) < TOL


def test_loss_with_both_auxiliary_losses_matches_the_reference(both):
    assert abs(float(both["sys"][1]) - float(both["ref"][1])) \
        < TOL * float(both["ref"][1])


@pytest.mark.parametrize("group", sorted(GROUPS))
def test_gradients_match_the_reference(both, group):
    got, want = by_group(both["sys"][2]), by_group(both["ref"][2])
    assert float(jnp.linalg.norm(want[group])) > 0
    assert rel(got[group], want[group]) < TOL


def test_every_token_keeps_the_experts_the_reference_chose(both):
    cfg, chosen = both["cfg"], np.asarray(both["ref"][3])
    experts = both["stats"]["chosen"]              # [L, tokens, k]
    mine = np.zeros(chosen.shape, bool)
    layer, token = np.indices(experts.shape[:2])
    mine[layer[..., None], token[..., None], experts] = True
    assert (mine == chosen).all()
    assert (both["stats"]["computed"] == chosen.sum(1)).all()
    assert both["stats"]["routed"].tolist() \
        == [both["ids"].size * cfg.moe_top_k] * cfg.n_layer


def test_the_engine_trains_it_and_starts_at_the_reference_loss(both):
    cfg, ids = both["cfg"], both["ids"]
    reset_default_topology()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT(cfg), model_parameters=both["params"], config={
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 1, "gradient_clipping": 1.0,
            "optimizer": {"type": "FusedAdam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1}, "steps_per_print": 10 ** 9},
        topology=MeshTopology(devices=jax.devices()[:1]))
    batches = itertools.repeat({"input_ids": ids, "labels": ids})
    losses = [float(engine.train_batch(batches)) for _ in range(6)]
    assert abs(losses[0] - float(both["ref"][1])) < TOL * losses[0]
    assert losses[-1] < losses[0] - 0.1
    load = publish_expert_load(engine.module, engine.params,
                               {"input_ids": ids})
    assert load["kind"] == "moe.load" and load["tokens_dropped"] == 0
    assert load["max_over_mean"] >= 1.0
    # which grouped matmul the layers traced, and how well its row tiles
    # fit this load (a tile that two experts share is computed twice)
    if cfg.n_embd == WIDE["n_embd"]:
        rows, tm = ids.size * cfg.moe_top_k, 96
        assert load["grouped_matmul"] == "pallas"
        assert load["grouped_matmul_tiles"] == [tm, cfg.n_embd, 128]
        assert 1.0 <= load["row_tile_visits_over_least"] \
            <= (rows // tm + cfg.moe_num_experts) / (rows // tm)
        # ... read in the stacked parameters, as the steps above read them
        assert load["expert_matrices"] == "in_place"
        assert both["stats"]["in_place"].tolist() == [1] * cfg.n_layer
    else:
        assert load["grouped_matmul"] == "xla"
        assert load["grouped_matmul_tiles"] is None
        assert load["row_tile_visits_over_least"] is None
        assert load["expert_matrices"] is None


def layer_on(experts, top_k, gate, **kw):
    """One MoE layer with seeded experts and the router kernel ``gate``."""
    moe = MoE(d_model=16, d_hidden=8, num_experts=experts, k=top_k,
              drop_tokens=False, gated_experts=True, dtype=jnp.float32,
              **kw)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 12, 16))
    params = moe.init(jax.random.PRNGKey(2), x)["params"]
    params["gate"]["kernel"] = gate(params["gate"]["kernel"])
    return moe, params, x


@pytest.mark.parametrize("experts,top_k", SIZES)
def test_nothing_is_dropped_when_every_token_picks_the_same_experts(
        experts, top_k):
    """A router that says the same for every token (a zero kernel: all
    probabilities equal, top-k takes the first k) puts all 48 tokens on
    the same k experts, 6-8 times any capacity a balanced load would set.
    Forward and backward still equal the dense reference."""
    moe, params, x = layer_on(experts, top_k, jnp.zeros_like)
    (y, _, _, counts), stats = jax.jit(lambda p, x: moe.apply(
        {"params": p}, x, mutable=["moe_stats"]))(params, x)
    assert counts.tolist() == [48] * top_k + [0] * (experts - top_k)
    # counted from the experts' output, not from the routing
    assert stats["moe_stats"]["computed"][0].tolist() == counts.tolist()
    assert int(stats["moe_stats"]["routed"][0]) == 48 * top_k
    flat = x.reshape(48, 16)
    w, _, _, _ = olmoe.route(flat, params["gate"]["kernel"], top_k, False)

    def ref(p, x):
        return olmoe.experts(x, w, p)

    want = ref(params["experts"], flat)
    assert rel(y.reshape(48, 16), want) < TOL
    cot = jax.random.normal(jax.random.PRNGKey(3), want.shape)
    got_g = jax.jit(jax.grad(lambda p, x: jnp.sum(
        moe.apply({"params": p}, x)[0].reshape(48, 16) * cot),
        argnums=(0, 1)))(params, x)
    want_g = jax.grad(lambda p, x: jnp.sum(ref(p, x) * cot),
                      argnums=(0, 1))(params["experts"], flat)
    assert rel(got_g[1].reshape(48, 16), want_g[1]) < TOL
    for name in ("wg", "wi", "wo"):
        assert rel(got_g[0]["experts"][name], want_g[0][name]) < TOL


@pytest.mark.parametrize("experts,top_k", SIZES)
def test_renormalised_weights_are_another_model(experts, top_k):
    """``norm_topk_prob`` true divides by the sum of the k weights, which
    with a freshly initialised router is little more than k / experts: the
    outputs differ severalfold, far outside ``TOL``."""
    scale = lambda k: k
    moe, params, x = layer_on(experts, top_k, scale)
    plain = moe.apply({"params": params}, x)[0]
    renorm = layer_on(experts, top_k, scale, norm_topk_prob=True)[0].apply(
        {"params": params}, x)[0]
    assert rel(renorm, plain) > 0.05
    r = sharded_moe.topk_routing(
        x.reshape(48, 16) @ params["gate"]["kernel"], top_k)
    assert float(r.weights.sum(-1).max()) < 1.0
    r = sharded_moe.topk_routing(
        x.reshape(48, 16) @ params["gate"]["kernel"], top_k, True)
    np.testing.assert_allclose(r.weights.sum(-1), 1.0, rtol=1e-6)


@pytest.mark.parametrize("top_k", [1, 2])
def test_the_new_path_equals_the_old_one_with_capacity_for_all(top_k):
    """k <= 2 through sort, gather and grouped matmul equals the one-hot
    dispatch when its capacity holds every token (top-2 renormalises, so
    the new path is asked to)."""
    experts, tokens = 4, 24
    x = jax.random.normal(jax.random.PRNGKey(4), (tokens, 16))
    logits = jax.random.normal(jax.random.PRNGKey(5), (tokens, experts)) * 3
    ffn = StackedExperts(num_experts=experts, d_model=16, d_hidden=8,
                         dtype=jnp.float32)
    params = ffn.init(jax.random.PRNGKey(6), jnp.zeros((experts, 2, 16)))
    params = jax.tree.map(     # the biases too: zeros would hide them
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(7),
                                              p.shape), params)

    def old(x, logits):
        g = sharded_moe.top1_gating(logits, drop_tokens=False) \
            if top_k == 1 else sharded_moe.top2_gating(
                logits, capacity_factor=float(experts))
        assert g.dispatch_mask.shape[2] == tokens
        out = ffn.apply(params, sharded_moe.dispatch_tokens(
            g.dispatch_mask, x))
        return sharded_moe.combine_tokens(g.combine_weights, out), g.l_aux

    def new(x, logits):
        r = sharded_moe.topk_routing(logits, top_k, renormalize=top_k == 2)
        order, inverse = sharded_moe.sort_by_expert(r.experts)
        rows = ffn.apply(params, sharded_moe.dispatch_rows(
            x, order, inverse, top_k), r.exp_counts)
        return sharded_moe.combine_rows(rows, r.weights, order,
                                        inverse), r.l_aux

    (y_old, aux_old), (y_new, aux_new) = old(x, logits), new(x, logits)
    assert rel(y_new, y_old) < TOL
    if top_k == 1:      # top-2's balance loss counts first choices only
        assert abs(float(aux_new - aux_old)) < TOL
    cot = jax.random.normal(jax.random.PRNGKey(8), y_old.shape)
    for arg in (0, 1):
        g_old = jax.grad(lambda *a: jnp.sum(old(*a)[0] * cot), arg)(x, logits)
        g_new = jax.grad(lambda *a: jnp.sum(new(*a)[0] * cot), arg)(x, logits)
        assert rel(g_new, g_old) < TOL


def test_capacity_gating_still_refuses_more_than_two_and_points_here():
    with pytest.raises(ValueError, match="dropless"):
        sharded_moe.topk_gating(jnp.zeros((4, 8)), k=3)
    with pytest.raises(ValueError, match="deterministically"):
        MoE(d_model=16, d_hidden=8, num_experts=4, k=3,
            noisy_gate_policy="RSample").init(
                jax.random.PRNGKey(0), jnp.zeros((1, 4, 16)))


def test_rows_the_grouped_matmul_skips_are_counted_as_dropped(monkeypatch):
    """``tokens_dropped`` is counted from the experts' output: group sizes
    that leave rows out (here the last expert's are halved) show up in it,
    though the routing asked for every pair."""
    from deepspeed_tpu.moe import layer

    def short(logits, k, *rest):
        route = sharded_moe.topk_routing(logits, k, *rest)
        counts = route.exp_counts
        return route._replace(exp_counts=counts.at[-1].set(counts[-1] // 2))

    cfg = config(8, 3)
    model, params, ids = seeded(cfg)
    whole = publish_expert_load(model, params, {"input_ids": ids})
    assert whole["tokens_dropped"] == 0
    monkeypatch.setattr(layer, "topk_routing", short)
    load = publish_expert_load(model, params, {"input_ids": ids})
    last = np.array(whole["tokens_per_expert"])[:, -1]
    assert (last > 1).all()
    assert load["tokens_dropped"] == int((last - last // 2).sum())


def test_the_z_loss_has_a_coefficient_of_its_own():
    """With the balance coefficient at zero the z-loss still counts, and
    with both at zero the loss is the cross-entropy's."""
    ids = seeded(config(8, 3))[2]

    def loss(**coefs):
        model, params, _ = seeded(config(8, 3, **coefs))
        return float(model.apply({"params": params}, ids, labels=ids))

    plain = loss(moe_aux_loss_coef=0.0, moe_z_loss_coef=0.0)
    z_only = loss(moe_aux_loss_coef=0.0, moe_z_loss_coef=0.001)
    both_on = loss(moe_aux_loss_coef=0.01, moe_z_loss_coef=0.001)
    assert plain < z_only < both_on


def test_the_compilers_ragged_dot_calls_are_the_experts():
    """On the TPU a ragged dot becomes Mosaic calls that keep only their
    own name; the scope table gives them ``moe_experts``."""
    from deepspeed_tpu.telemetry import scopes

    text = """HloModule jit_train_step

ENTRY %main (p: bf16[8,4]) -> bf16[8,4] {
  %p = bf16[8,4]{1,0} parameter(0)
  %ragged-dot-metadata.1 = s32[9]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %ragged-dot-none.3 = bf16[8,4]{1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  ROOT %add.1 = bf16[8,4]{1,0} add(%ragged-dot-none.3, %p), metadata={op_name="jit(train_step)/jvp(GPT)/moe_combine/add"}
}
"""
    module, table = scopes.instruction_scopes(text)
    assert module == "jit_train_step"
    for name in ("ragged-dot-none.3", "ragged-dot-metadata.1"):
        assert scopes.has_scope(table[name], scopes.SCOPE_MOE_EXPERTS)
    assert scopes.has_scope(table["add.1"], scopes.SCOPE_MOE_COMBINE)
    assert not scopes.has_scope(table["add.1"], scopes.SCOPE_MOE_EXPERTS)


# --- a share moves the rows routed here alone (ops/pallas/row_fetch.py) ------
def share_layer(dtype=jnp.float32):
    """One layer that holds experts 4-7 of 16 at widths the kernels take,
    its input and a cotangent."""
    width = 128 if dtype == jnp.float32 else 256
    moe = MoE(d_model=width, d_hidden=128, num_experts=16, k=4,
              drop_tokens=False, gated_experts=True, dtype=dtype,
              experts_held=(4, 4))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, width)).astype(dtype)
    params = moe.init(jax.random.PRNGKey(2), x)["params"]
    params["gate"]["kernel"] = params["gate"]["kernel"] * 8.0
    return moe, params, x, jax.random.normal(jax.random.PRNGKey(3), x.shape)


def value_and_grads(moe, params, x, cot):
    def loss(p, x):
        y, l_aux, _, _ = moe.apply({"params": p}, x)
        return jnp.sum(y.astype(jnp.float32) * cot) + l_aux, y

    (_, y), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True))(params, x)
    return [y] + jax.tree_util.tree_leaves(grads)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_a_share_under_the_row_fetch_kernels_is_the_share_under_xla(
        dtype, monkeypatch):
    """The layer's output and every gradient with the live rows moved by
    the kernels against the same layer under XLA's gathers of all pairs
    (the crossover out of reach): the same rows, the same sums."""
    moe, params, x, cot = share_layer(dtype)
    want = value_and_grads(moe, params, x, cot)
    monkeypatch.setattr(sharded_moe, "ROW_FETCH_MIN_PAIRS", 0)
    got = value_and_grads(moe, params, x, cot)
    for g, w in zip(got, want):
        assert np.isfinite(np.asarray(g, np.float32)).all()
        assert rel(g.astype(jnp.float32), w.astype(jnp.float32)) < 1e-6


@pytest.mark.parametrize("routing", ["seeded", "edge"])
def test_nothing_reads_a_row_the_fetch_did_not_write(routing, monkeypatch):
    """Between dispatch and experts every row the fetch left unwritten
    (those past the consumer's tile that holds row ``n_live``: the rows
    from ``n_live`` to that tile's end are the fetch's zeros, which the
    matrices' gradient multiplies by) is replaced by NaN, and so is the
    rows' cotangent on its way back: output and gradients stay finite and
    are the unpoisoned ones. ``edge``: the live rows end on a tile's edge
    and the last two held experts get none, so their one visit each (the
    matrices' gradient stores its zeros there) lies in a tile of no live
    row: the first chip run of PR 64 trained into NaN there."""
    from deepspeed_tpu.moe import layer
    from deepspeed_tpu.moe.experts import grouped_matmul_tiles

    moe, params, x, cot = share_layer()
    monkeypatch.setattr(sharded_moe, "ROW_FETCH_MIN_PAIRS", 0)
    tm = grouped_matmul_tiles(512, 128, 128, 4, jnp.float32)[0]
    if routing == "edge":
        def by_hand(logits, k, *rest, **kw):
            route = sharded_moe.topk_routing(logits, k, *rest, **kw)
            # the first tm / 2 tokens send two pairs here (experts 4 and 5
            # of the held 4..7), every other pair goes elsewhere
            here = (jnp.arange(128) < tm // 2)[:, None]
            experts = jnp.where(here, jnp.array([4, 5, 0, 1]),
                                jnp.array([0, 1, 2, 3])).astype(jnp.int32)
            return route._replace(
                experts=experts,
                exp_counts=jnp.bincount(experts.reshape(-1), length=16)
                .astype(jnp.int32))

        monkeypatch.setattr(layer, "topk_routing", by_hand)
    want = value_and_grads(moe, params, x, cot)
    seen = []

    @jax.custom_vjp
    def poison(rows, unwritten):
        return jnp.where(unwritten, jnp.nan, rows)

    poison.defvjp(lambda rows, unwritten: (poison(rows, unwritten),
                                           unwritten),
                  lambda unwritten, g: (jnp.where(unwritten, jnp.nan, g),
                                        None))

    def dispatch(tokens, order, inverse, k, n_live, zero_to):
        rows = sharded_moe.dispatch_rows(tokens, order, inverse, k, n_live,
                                         zero_to)
        written = (n_live // zero_to + 1) * zero_to
        seen.append(zero_to)
        return poison(rows, jnp.arange(rows.shape[0])[:, None] >= written)

    monkeypatch.setattr(layer, "dispatch_rows", dispatch)
    got = value_and_grads(moe, params, x, cot)
    assert seen == [tm] * len(seen) and tm < 512
    for g, w in zip(got, want):
        assert np.isfinite(np.asarray(g)).all()
        assert np.array_equal(np.asarray(g), np.asarray(w))
    if routing == "edge":
        stats = jax.jit(lambda p, x: moe.apply(
            {"params": p}, x, mutable=["moe_stats"]))(params, x)[1]
        assert int(stats["moe_stats"]["rows_moved"][0]) == tm


@pytest.mark.parametrize("held,kernels", [((0, 2), True), ((0, 2), False),
                                          (None, True)])
def test_the_load_event_says_how_many_rows_the_dispatch_moved(
        held, kernels, monkeypatch):
    """``rows_moved`` is ``routed_here`` where the kernels move a share's
    rows and ``routed`` where XLA's gather moves every pair: a share under
    the crossover, and a layer that holds all its experts."""
    if kernels:
        monkeypatch.setattr(sharded_moe, "ROW_FETCH_MIN_PAIRS", 0)
    cfg = config(8, 3, moe_experts_held=held, **WIDE)
    model, params, ids = seeded(cfg)
    load = publish_expert_load(model, params, {"input_ids": ids})
    assert load["tokens_dropped"] == 0
    if held and kernels:
        assert load["rows_moved"] == load["routed_here"] < load["routed"]
    else:
        assert load["rows_moved"] == load["routed"] == 2 * 64 * 3


@pytest.mark.parametrize("call", ["serving", "training", "counters"])
def test_a_serving_call_keeps_the_gather_it_was_lowered_with(
        call, monkeypatch):
    """With the crossover out of the way a share's training step and its
    counter pass move the live rows through the kernels; a serving call of
    the same model (``decode=True``: the scan's owner says so to the
    experts) traces none of them, so no serving program changes however
    many pairs a prompt's pass sorts."""
    from deepspeed_tpu.ops.pallas import row_fetch

    monkeypatch.setattr(sharded_moe, "ROW_FETCH_MIN_PAIRS", 0)
    fetched = []
    real = row_fetch.fetch_rows
    monkeypatch.setattr(row_fetch, "fetch_rows", lambda *a, **kw: (
        fetched.append(1), real(*a, **kw))[1])
    cfg = config(8, 3, moe_experts_held=(0, 2), **WIDE)
    model, params, ids = seeded(cfg)
    if call == "serving":
        jax.jit(lambda p: model.apply({"params": p}, ids, decode=True,
                                      mutable=["cache"]))(params)
        assert not fetched
    elif call == "training":
        jax.jit(jax.grad(lambda p: model.apply(
            {"params": p}, ids, labels=ids)))(params)
        assert fetched
    else:
        routing_stats(model, params, {"input_ids": ids})
        assert fetched
