"""Keye-VL-2.0's language model (grouped-query attention over the positions
a lightning indexer chooses, per-head q/k norm, three-section rotary, an
expert layer of which the program holds a share) on the normal serving
path, at a small size on the CPU with seeded weights, against the plain
reference the benchmark's cell uses (``perfbench/reference/keye_vl.py``).
``topk`` is 8 and the contexts run to 60, so every form is exercised both
under ``topk`` (every position chosen) and several times over it.

Tolerances. Program and reference are float32 with every matmul at
``highest`` (the fixture below), so they differ by the ORDER of float32
sums alone (the tiled pass's online softmax, the gather's rows in score
order). Logits of these tiny models are ~0.4 in size and came out 1e-7..3e-7
apart; 5e-6 leaves the sums an order of magnitude. The chosen sets are
compared exactly: a score that differed by one rounding at the boundary of
a set would show there first."""
import dataclasses
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import serving
from deepspeed_tpu.inference import lane_cache, scheduler
from deepspeed_tpu.inference.lane_cache import LaneLayout
from deepspeed_tpu.models import indexer, transformer_lm
from deepspeed_tpu.models.transformer_lm import (
    GPT,
    GPTConfig,
    IndexerConfig,
    IndexKeyError,
)
from deepspeed_tpu.moe.layer import MOE_STATS, MoE
from deepspeed_tpu.ops import indexed_attention as ia
from deepspeed_tpu.ops import rotary
from deepspeed_tpu.telemetry import scopes
from keye_vl_tiny import TINY_KEYE
from perfbench.builders import keye_vl_serve
from perfbench.reference import keye_vl as reference

SIZES = reference.sizes(TINY_KEYE)
VOCAB = TINY_KEYE["vocab_size"]
TOPK = TINY_KEYE["sa_config"]["topk"]
BUCKET = 16
ATOL = 5e-6


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def model_config(dtype="float32", **changes):
    section = dict(TINY_KEYE["serve"], param_dtype=dtype,
                   compute_dtype=dtype)
    return dataclasses.replace(
        keye_vl_serve.model_config(TINY_KEYE, section), **changes)


def tokens(n, seed=0):
    return np.random.default_rng([seed, n]).integers(0, VOCAB, size=n)


def init(cfg, seed=0):
    """A model with every norm weight and bias moved off its initial 1 / 0,
    so that a norm left out or misplaced shows."""
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(flat))
    moved = [leaf + 0.1 * jax.random.normal(k, leaf.shape)
             if any(n in jax.tree_util.keystr(path)
                    for n in ("norm", "ln_")) else leaf
             for (path, leaf), k in zip(flat, keys)]
    return model, jax.tree_util.tree_unflatten(tree, moved)


def as_scanned(params, cfg):
    """An unrolled model's parameters in the tree the reference reads."""
    blocks = [params[f"h_{i}"] for i in range(cfg.n_layer)]
    return dict({n: v for n, v in params.items() if not n.startswith("h_")},
                h={"block": jax.tree.map(lambda *a: jnp.stack(a), *blocks)})


def served(slots=3, seed=3, **changes):
    eng = deepspeed_tpu.init_inference(GPT(model_config(**changes)),
                                       dtype="fp32", seed=seed)
    sched = serving.build_serving(eng, {"slots": slots,
                                        "prompt_bucket": BUCKET})
    sched._ensure_compiled()
    return eng, sched


@pytest.fixture(scope="module")
def fp32():
    return served()


def one_token_step(model, params, also=()):
    """``step(cache, ids [B, 1]) -> (logits, variables)``, traced once."""
    return jax.jit(lambda cache, ids: model.apply(
        {"params": params, "cache": cache}, ids, decode=True,
        mutable=["cache", *also]))


# ---------------------------------------------------------------------------
# every form of the layer and the reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("scan", [True, False], ids=["scanned", "unrolled"])
@pytest.mark.parametrize("form", ["no_cache", "prefill", "steps", "chunk"])
@pytest.mark.parametrize("prompt", [5, 24], ids=["under_topk", "over_topk"])
def test_each_form_gives_the_references_logits(scan, form, prompt):
    cfg = model_config(scan_layers=scan, num_logits_to_keep=None)
    model, params = init(cfg)
    n = prompt + 24                       # to 29 and 48: 3.6 and 6 x topk
    ids = tokens(n)
    tree = params if scan else as_scanned(params, cfg)
    want = reference.logits(tree, ids, SIZES)
    batch = jnp.asarray(ids)[None]
    # each pass one compiled program, as test_dots3.py's, and not the
    # model's operations dispatched (and compiled) one by one
    if form == "no_cache":
        got = jax.jit(model.apply)({"params": params}, batch)[0]
        np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
        return
    got, var = jax.jit(lambda ids: model.apply(
        {"params": params}, ids, decode=True, mutable=["cache"]))(
            batch[:, :prompt])
    np.testing.assert_allclose(got[0], want[:prompt], atol=ATOL, rtol=0)
    if form == "steps":
        step = one_token_step(model, params)
        for t in range(prompt, n):
            got, var = step(var["cache"], batch[:, t:t + 1])
            np.testing.assert_allclose(got[0, 0], want[t], atol=ATOL, rtol=0)
    elif form == "chunk":           # a continuation of many query tokens
        got, var = jax.jit(lambda cache, ids: model.apply(
            {"params": params, "cache": cache}, ids,
            decode=True, mutable=["cache"]))(var["cache"], batch[:, prompt:])
        np.testing.assert_allclose(got[0], want[prompt:], atol=ATOL, rtol=0)


@pytest.mark.parametrize("form", ["no_cache", "prefill_then_steps", "chunk"])
def test_the_chosen_sets_are_the_references(form):
    """Each query's set ``S_t``, layer by layer, as the program's three
    forms choose it (sown where a test asks) and as the reference's dense
    ``top_k`` a row does; 40 positions, five times ``topk``."""
    cfg = model_config(scan_layers=False, num_logits_to_keep=None)
    model, params = init(cfg)
    n, prompt = 40, 19
    ids = tokens(n, seed=1)
    _, _, want, _ = reference.hidden_and_states(
        as_scanned(params, cfg), ids, SIZES, with_chosen=True)
    want = np.asarray(want)                               # [layers, T, T]
    assert (want.sum(-1)[:, TOPK:] == TOPK).all()         # a choice is made
    batch = jnp.asarray(ids)[None]

    def sets(state, rows):
        got = np.stack([np.asarray(
            state["intermediates"][f"h_{i}"]["attn"]["chosen"][0][0])
            for i in range(cfg.n_layer)])
        return got[:, :, :n] if got.shape[-1] > n else np.pad(
            got, ((0, 0), (0, 0), (0, n - got.shape[-1])))[:, -rows:]

    if form == "no_cache":
        _, state = model.apply({"params": params}, batch,
                               mutable=["intermediates"])
        np.testing.assert_array_equal(sets(state, n), want)
        return
    _, state = model.apply({"params": params}, batch[:, :prompt],
                           decode=True, mutable=["cache", "intermediates"])
    np.testing.assert_array_equal(sets(state, prompt), want[:, :prompt])
    if form == "chunk":
        _, state = model.apply(
            {"params": params, "cache": state["cache"]}, batch[:, prompt:],
            decode=True, mutable=["cache", "intermediates"])
        np.testing.assert_array_equal(sets(state, n - prompt),
                                      want[:, prompt:])
        return
    step = one_token_step(model, params, also=("intermediates",))
    for t in range(prompt, n):
        _, state = step(state["cache"], batch[:, t:t + 1])
        np.testing.assert_array_equal(sets(state, 1)[:, 0], want[:, t])


def test_the_mask_without_a_scatter_is_top_ks_set_ties_included():
    """``chosen_mask`` (a tile of a pass of many queries) against
    ``lax.top_k``'s own rows, on scores full of exact ties, zeros among
    them as a relu's sum gives, and rows that see fewer than ``topk``
    positions; a decode step's ``chosen_set`` is the same set."""
    rng = np.random.default_rng(0)
    scores = rng.integers(-2, 3, size=(2, 24, 40)).astype(np.float32)
    visible = (np.arange(40)[None, None] <= np.arange(3, 27)[None, :, None]) \
        & (rng.random((2, 1, 40)) > 0.2)
    vals, rows = jax.lax.top_k(
        jnp.where(jnp.asarray(visible), jnp.asarray(scores), -jnp.inf), 8)
    ok = vals > -jnp.inf
    want = np.zeros(scores.shape, bool)
    b, t = np.indices(rows.shape[:2])
    np.logical_or.at(want, (b[..., None], t[..., None], np.asarray(rows)),
                     np.asarray(ok))
    got = ia.chosen_mask(jnp.asarray(scores), jnp.asarray(visible), 8)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(ia.chosen_set(
        jnp.asarray(scores), jnp.asarray(visible), 8)), want)
    assert (want.sum(-1) == np.minimum(visible.sum(-1), 8)).all()


def test_rotary_takes_each_frequency_from_its_sections_stream():
    """Three position streams that differ, through the model and through
    ``ops/rotary.py`` alone; equal streams are the plain rotary."""
    cfg = model_config(num_logits_to_keep=None)
    model, params = init(cfg)
    n = 30
    ids = tokens(n, seed=2)
    pos = np.stack([np.arange(n), 50 - np.arange(n), np.arange(n) * 7 % 11])
    want = reference.logits(params, ids, SIZES, rotary_at=pos)
    got = model.apply({"params": params}, jnp.asarray(ids)[None],
                      positions=jnp.asarray(pos)[:, None])
    np.testing.assert_allclose(got[0], want, atol=ATOL, rtol=0)
    text = reference.logits(params, ids, SIZES)
    assert np.abs(want - text).max() > 20 * ATOL        # the streams matter

    x = jax.random.normal(jax.random.PRNGKey(0), (1, n, 2, 16))
    p = jnp.asarray(pos)[:, None]
    out = rotary.apply_rotary_pos_emb(x, p, base=1e7, sections=(4, 2, 2))
    for stream, (lo, hi) in enumerate([(0, 4), (4, 6), (6, 8)]):
        alone = rotary.apply_rotary_pos_emb(x, p[stream], base=1e7)
        for half in (0, 8):
            np.testing.assert_array_equal(
                out[..., half + lo:half + hi], alone[..., half + lo:half + hi])
    np.testing.assert_array_equal(
        rotary.apply_rotary_pos_emb(x, jnp.stack([p[0]] * 3), base=1e7,
                                    sections=(4, 2, 2)),
        rotary.apply_rotary_pos_emb(x, p[0], base=1e7))
    with pytest.raises(ValueError, match="do not add up"):
        rotary.section_streams((4, 2, 1), 16)
    assert reference.indexer_sections((16, 24, 24), 128, 64) == (8, 12, 12)


def test_q_and_k_are_normalised_head_by_head():
    cfg = model_config()
    _, params = init(cfg)
    attn = params["h"]["block"]["attn"]
    assert attn["q_norm"]["scale"].shape == (cfg.n_layer, cfg.head_dim)
    assert attn["k_norm"]["scale"].shape == (cfg.n_layer, cfg.head_dim)
    with pytest.raises(ValueError, match="qk_norm"):
        model_config(qk_norm="rows")


# ---------------------------------------------------------------------------
# the expert layer: a share of a renormalised top-k, no shared expert
# ---------------------------------------------------------------------------
def moe_layer(held):
    return MoE(d_model=32, d_hidden=16, num_experts=16, k=3,
               drop_tokens=False, gated_experts=True, norm_topk_prob=True,
               experts_held=held, dtype=jnp.float32,
               param_dtype=jnp.float32)


@pytest.mark.parametrize("held", [2, 4], ids=["eight_shares", "four_shares"])
def test_the_shares_sum_to_the_uncut_layer(held):
    """The uncut layer (all 16 experts) and its shares of ``held``
    consecutive experts, each with ITS slice of the uncut layer's
    matrices: nothing is computed alike on every chip (no shared expert),
    so the shares' outputs sum to the uncut layer's, and each is what the
    reference gives for that share. Float32 sums in another order."""
    x = jax.random.normal(jax.random.PRNGKey(2), (48, 32))
    whole = moe_layer(None)
    params = whole.init(jax.random.PRNGKey(1), x)["params"]
    y_whole = whole.apply({"params": params}, x)[0]
    total, routed = 0.0, 0
    for first in range(0, 16, held):
        part = dict(params, experts=jax.tree.map(
            lambda a: a[first:first + held], params["experts"]))
        (y, *_), st = moe_layer((first, held)).apply(
            {"params": part}, x, mutable=[MOE_STATS])
        want = reference.moe(x, part, dict(SIZES, held=(first, held)))
        np.testing.assert_allclose(y, want, atol=ATOL, rtol=0)
        total = total + y
        routed += int(st[MOE_STATS]["routed_here"][0])
    np.testing.assert_allclose(total, y_whole, atol=1e-5, rtol=0)
    assert float(jnp.abs(y_whole).max()) > 0.01          # not vacuous
    assert routed == 48 * 3       # every pair is computed on one share


# ---------------------------------------------------------------------------
# the third leaf: declared by the model, laid out by LaneLayout alone
# ---------------------------------------------------------------------------
def test_the_index_key_reaches_the_lane_layout_through_cache_leaves_alone(
        fp32):
    eng, sched = fp32
    cfg = eng.module.config
    names = [leaf.name for leaf in cfg.cache_leaves]
    assert names == ["cached_key", "cached_value", indexer.CACHED_INDEX_KEY,
                     indexer.CHOSEN_ROWS, indexer.CHOICE_QUERY,
                     indexer.CHOICE_WEIGHTS]
    assert [(step.kind, step.rank, step.counted_as, step.dtype, step.unset)
            for step in cfg.cache_leaves[3:]] == [
        ("step", 2, (), jnp.int32, -1), ("step", 3, (), None, 0),
        ("step", 2, (), jnp.float32, 0)]
    leaf = cfg.cache_leaves[2]
    assert (leaf.kind, leaf.rank, leaf.counted_as, leaf.carry_tag) == (
        "position", 3, ("index",), scopes.SCOPE_KV_CACHE_CARRY)
    assert cfg.position_leaves[2] == (indexer.CACHED_INDEX_KEY, 3)
    lanes = sched.lane_cache
    assert lanes.leaves == cfg.cache_leaves
    shapes = {str(path[-1].key): sd for path, sd in
              jax.tree_util.tree_flatten_with_path(lanes.shapes)[0]}
    ix = cfg.indexer
    assert shapes[indexer.CACHED_INDEX_KEY].shape == (
        cfg.n_layer, 3, cfg.n_positions, ix.head_dim)
    geo = sched.kv_cache_stats()
    per_lane = cfg.n_layer * cfg.n_positions * ix.head_dim * 4
    assert geo["index_key_bytes_per_lane"] == per_lane
    assert geo["kv_bytes_per_lane"] == geo["bytes_per_lane"] \
        == per_lane * (1 + 2 * cfg.kv_heads * cfg.head_dim // ix.head_dim) \
        + cfg.n_layer * (cfg.n_positions + 4 + ix.topk * 4
                         + ix.n_heads * (ix.head_dim + 1) * 4)
    assert shapes[indexer.CHOSEN_ROWS].shape == (cfg.n_layer, 3, ix.topk)
    assert shapes[indexer.CHOICE_QUERY].shape == (
        cfg.n_layer, 3, ix.n_heads, ix.head_dim)
    # an empty cache: nothing written, and no step has chosen anything
    assert all(np.all(np.asarray(a) == (
        -1 if str(path[-1].key) == indexer.CHOSEN_ROWS else 0))
        for path, a in jax.tree_util.tree_flatten_with_path(
            lanes.empty())[0])
    # a bare module sizes the same cache
    assert jax.tree.map(lambda s: (s.shape, s.dtype),
                        LaneLayout(GPT(cfg), 3).shapes) \
        == jax.tree.map(lambda s: (s.shape, s.dtype), lanes.shapes)
    # and the scheduler knows nothing of the leaf, the indexer or its
    # module: the declaration is all that crossed
    text = inspect.getsource(scheduler)
    for word in ("index_key", "indexer", "Indexer", "selected"):
        assert word not in text, word
    assert indexer.CACHED_INDEX_KEY not in inspect.getsource(lane_cache)


def test_a_model_without_an_indexer_declares_and_sizes_what_it_did():
    plain = dataclasses.replace(model_config(), indexer=None)
    assert [leaf.name for leaf in plain.cache_leaves] == [
        "cached_key", "cached_value"]
    assert "index_key_bytes_per_lane" not in LaneLayout(
        GPT(plain), 2).geometry()


def reference_greedy(params, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(reference.logits(
            params, np.asarray(seq), SIZES, positions=[len(seq) - 1]
        )[0].argmax()))
    return seq[len(prompt):]


def test_the_scheduler_serves_the_references_tokens_and_keeps_its_rows(fp32):
    """Prompts under and over ``topk``, left-padded into buckets, more
    requests than lanes; every served token is the reference's greedy one
    (teacher-forced: the margin to the reference's largest logit is 0),
    and the rows a live lane keeps, the index key among them, are the
    reference's for every token it has taken in."""
    eng, sched = fp32
    sched.retain_lanes = True
    prompts = [tokens(n, seed=5).tolist() for n in (5, 20, 30, 17)]
    wants = (30, 25, 20, 12)
    out = {}
    rids = [sched.submit(p, max_new_tokens=w, stream_callback=lambda r, t, d:
                         out.setdefault(r, []).append(int(t)))
            for p, w in zip(prompts, wants)]

    def stop_late():        # end the run with lanes in flight
        if len(out.get(rids[0], ())) >= 24:
            raise KeyboardInterrupt

    with pytest.raises(KeyboardInterrupt):
        sched.run(poll_fn=stop_late)
    for rid, prompt in zip(rids, prompts):
        served_tokens = out[rid]
        # (padded on the right to one length: a causal model's rows never
        # read the padding, and every request then shares one compile)
        n = len(prompt) + len(served_tokens) - 1
        seq = np.zeros((64,), np.int64)
        seq[:n] = prompt + served_tokens[:-1]
        logits = reference.logits(
            eng.params, seq, SIZES,
            positions=list(range(len(prompt) - 1, n)))
        assert (logits.argmax(-1) == np.asarray(served_tokens)).all()
    kept = sched.lanes_at_exit
    assert kept.live
    for lane_no, comp in kept.live.items():
        prompt = prompts[rids.index(comp.request_id)]
        rows = kept.positions(lane_no)
        taken = prompt + [int(t) for t in comp.tokens]
        first = -(-len(prompt) // BUCKET) * BUCKET - len(prompt)
        valid = np.asarray(rows["valid"][0])
        assert valid.sum() == len(taken) == valid[first:first
                                                  + len(taken)].sum()
        last = len(taken) - 1
        _, (k, v, k_i), sets, (q_i, w) = reference.hidden_and_states(
            eng.params, np.asarray(taken), SIZES, offset=first,
            with_chosen=True, queries_at=(last,))
        for got, want in ((rows["cached_key"], k), (rows["cached_value"], v),
                          (rows[indexer.CACHED_INDEX_KEY], k_i)):
            np.testing.assert_allclose(
                got[:, first:first + len(taken)], want, atol=ATOL, rtol=0)
        # the rows the lane's last decode step attended over, as the step
        # left them: the set of the last token it took in, by the
        # reference's dense top_k and by its one-query form on the host
        left = np.asarray(kept.last_step(lane_no)[indexer.CHOSEN_ROWS])
        assert left.shape == (len(k), TOPK)
        for layer, got in enumerate(left):
            want = np.flatnonzero(np.asarray(sets[layer, last]))
            assert len(want) == min(TOPK, len(taken))
            np.testing.assert_array_equal(np.sort(got[got >= 0]) - first,
                                          want)
            np.testing.assert_array_equal(reference.choose(
                q_i[layer, 0], w[layer, 0], k_i[layer], TOPK), want)
        # and the query it left beside them is the one that chose them
        mine = kept.last_step(lane_no)
        np.testing.assert_allclose(mine[indexer.CHOICE_QUERY], q_i[:, 0],
                                   atol=ATOL, rtol=0)
        np.testing.assert_allclose(mine[indexer.CHOICE_WEIGHTS], w[:, 0],
                                   atol=ATOL, rtol=0)
    sched.retain_lanes = False


def test_only_a_decode_step_says_which_rows_it_chose():
    """``chosen_rows`` is what ONE query token over a cache leaves: a
    prefill leaves it unset, a step fills it (``topk`` rows, or the rows
    there are), and a pass of several tokens over the cache leaves it as
    it was."""
    cfg = model_config(n_layer=1, scan_layers=False)
    model, params = init(cfg)
    batch = jnp.asarray(tokens(30, seed=2))[None]

    def chosen(var):
        return np.asarray(var["cache"]["h_0"]["attn"][indexer.CHOSEN_ROWS])

    # (each pass one compiled program: outside ``jax.jit`` every operation
    # of the model is compiled by itself)
    _, var = jax.jit(lambda ids: model.apply(
        {"params": params}, ids, decode=True, mutable=["cache"]))(
            batch[:, :5])
    assert (chosen(var) == -1).all()
    step = one_token_step(model, params)
    _, var = step(var["cache"], batch[:, 5:6])
    assert sorted(chosen(var)[0]) == [-1, -1, 0, 1, 2, 3, 4, 5]
    before = chosen(var)
    _, var = step(var["cache"], batch[:, 6:29])     # a pass of 23 tokens
    np.testing.assert_array_equal(chosen(var), before)
    _, var = step(var["cache"], batch[:, 29:30])
    assert (chosen(var) >= 0).all() and len(set(chosen(var)[0])) == TOPK


@pytest.mark.parametrize("feature", ["int8_kv", "prefix_cache", "tp"])
def test_the_index_key_refuses_what_assumes_keys_and_values_alone(feature):
    from deepspeed_tpu.parallel.mesh import reset_default_topology

    with pytest.raises(IndexKeyError) as err:
        if feature == "tp":
            try:
                reset_default_topology()
                eng = deepspeed_tpu.init_inference(
                    GPT(model_config()), dtype="fp32", mp_size=2)
                serving.ContinuousBatchingScheduler(eng, slots=2)
            finally:
                reset_default_topology()
        else:
            cfg = model_config(kv_cache_dtype="int8") \
                if feature == "int8_kv" else model_config()
            eng = deepspeed_tpu.init_inference(GPT(cfg), dtype="fp32",
                                               seed=0)
            serving.build_serving(
                eng, {"slots": 2, "prompt_bucket": BUCKET,
                      **({"prefix_cache": True}
                         if feature == "prefix_cache" else {})})
    assert err.value.feature == {"int8_kv": "kv_cache_dtype='int8'",
                                 "prefix_cache": "prefix_cache",
                                 "tp": "tp > 1"}[feature]
    assert "cached_index_key" in str(err.value)


def test_rewind_steps_the_index_key_back_with_keys_and_values(fp32):
    """What speculation needs of the leaf, and why no refusal names a draft
    engine: a verification pass of 4 tokens of which 3 are rejected,
    stepped back by ``LaneLayout.rewind``, leaves every leaf, the index
    key among them, as one sequential token does; and the next step's
    logits are the reference's."""
    eng, sched = fp32
    lanes = sched.lane_cache
    model, params = eng.module, eng.params
    ids = tokens(30, seed=7)
    batch = jnp.asarray(ids)[None]
    # (each pass one compiled program: outside ``jax.jit`` every operation
    # of the model is compiled by itself)
    _, var = jax.jit(lambda ids: model.apply(
        {"params": params}, ids, decode=True, mutable=["cache"]))(
            batch[:, :20])
    more = one_token_step(model, params)    # traced again for four tokens
    snapshot = lanes.copy(var["cache"])
    _, one = more(lanes.copy(snapshot), batch[:, 20:21])
    wrong = jnp.asarray([[ids[20], 1, 2, 3]], jnp.int32)
    _, four = more(var["cache"], wrong)
    after_pass = jax.tree.map(np.asarray, four["cache"])   # rewind donates
    back = lanes.rewind(snapshot, four["cache"], jnp.asarray([3], jnp.int32))
    for (path, got), want, passed in zip(
            jax.tree_util.tree_flatten_with_path(back)[0],
            jax.tree.leaves(one["cache"]), jax.tree.leaves(after_pass)):
        if path[-1].key in (indexer.CHOSEN_ROWS, indexer.CHOICE_QUERY,
                            indexer.CHOICE_WEIGHTS):
            # no slots to step back: as the pass left it, which chose
            # nothing a decode step would say (the prefill's -1 and 0)
            want = passed
            assert (np.asarray(want) == (
                -1 if path[-1].key == indexer.CHOSEN_ROWS else 0)).all()
        np.testing.assert_allclose(got, want, atol=ATOL, rtol=0,
                                   err_msg=jax.tree_util.keystr(path))
    got, _ = more(back, batch[:, 21:22])
    want = reference.logits(params, ids[:22], SIZES, positions=[21])
    np.testing.assert_allclose(got[0, -1], want[0], atol=ATOL, rtol=0)


def test_the_route_is_told_from_the_cache_and_by_no_option():
    """A cache of at most ``topk`` positions chooses every position
    whatever the scores, so the model takes plain attention's route
    (``decode_attn``'s block rule answers); a longer one reads chosen
    rows, not blocks."""
    long, short = model_config(), model_config(n_positions=TOPK)
    assert long.indexer.engaged(long) and not short.indexer.engaged(short)
    assert transformer_lm.decode_attention_block(long) is None
    assert transformer_lm.decode_attention_block(short) \
        == transformer_lm.decode_attention_block(
            dataclasses.replace(short, indexer=None))
    model, params = init(dataclasses.replace(short, num_logits_to_keep=None))
    ids = tokens(TOPK, seed=3)
    want = reference.logits(params, ids, SIZES)
    got, var = model.apply({"params": params}, jnp.asarray(ids)[None, :5],
                           decode=True, mutable=["cache"])
    np.testing.assert_allclose(got[0], want[:5], atol=ATOL, rtol=0)
    step = one_token_step(model, params)
    for t in range(5, TOPK):
        got, var = step(var["cache"], jnp.asarray(ids)[None, t:t + 1])
        np.testing.assert_allclose(got[0, 0], want[t], atol=ATOL, rtol=0)


def test_an_indexer_sits_beside_causal_rotary_attention_alone():
    for changes in (dict(rotary=False, learned_positions=True),
                    dict(alibi=True), dict(causal=False),
                    dict(rotary_interleaved=True)):
        with pytest.raises(ValueError):
            model_config(**changes)
    with pytest.raises(ValueError, match="no indexer"):
        IndexerConfig(n_heads=2, head_dim=7, topk=4)


def test_the_serving_programs_carry_the_new_scopes(fp32):
    """The decode program's compiled text has the four scopes of the
    selection, the index key's write under ``kv_cache_write``, and no
    whole index-key leaf under the carry tag."""
    eng, sched = fp32
    sched.submit(tokens(20, seed=9).tolist(), max_new_tokens=3)
    sched.run()
    table = sched.program_scopes()
    decode = next(v for k, v in table.items() if "decode_k" in k)
    paths = [p for p in decode.values() if p]
    for scope in (scopes.SCOPE_DSA_INDEX_PROJ, scopes.SCOPE_DSA_INDEX_SCORES,
                  scopes.SCOPE_DSA_SELECT, scopes.SCOPE_DSA_ATTN,
                  scopes.SCOPE_KV_CACHE_WRITE):
        assert any(scopes.has_scope(p, scope) for p in paths), scope
    assert not any(scopes.has_scope(p, scopes.SCOPE_ATTN_CORE)
                   for p in paths)


def test_chip_smokes_check_of_the_selection_at_a_tiny_size():
    """``chip_smoke.py``'s check of one decode step over chosen rows
    against its plain form, as the chip runs it at the cell's shape: at
    ``topk`` the step reads blocks, at a quarter of it it gathers, and
    each form alone gives the plain form's sums."""
    import chip_smoke

    out = chip_smoke._check_selected_attention(
        2, 3, 96, 2, 4, 16, 3, 8, 16, jnp.float32, strict=False)
    assert [(c["topk"], c["reads_blocks"]) for c in out["cases"]] \
        == [(16, True), (4, False)]
    assert all(c["rel_l2"] < 1e-5 and c["sets_agree"] == 1.0
               for c in out["cases"])
    assert max(out["alone_rel_l2"].values()) < 1e-5
    assert out["live"][1] == 96 and out["mosaic_calls"] == 0
    assert out["cost"] == "not measured (no chip)"
    assert chip_smoke.SELECTED_SHAPE[1:4] == (32, 24576, 4)


def test_chip_smokes_check_of_the_selection_alone_at_a_tiny_size():
    """``chip_smoke.py``'s check of the set and its rows against
    ``lax.top_k`` and a scatter on the host, as the chip runs it at the
    cell's shape and at 131,072 positions (there with the stages' times)."""
    import chip_smoke

    out = chip_smoke._check_selection(5, 300, 16, strict=False)
    assert [(c["positions"], c["scores"]) for c in out["cases"]] \
        == [(300, "random"), (300, "ties_and_both_zeros")]
    assert all(c["set_is_top_ks"] and c["rows_are_the_sets"]
               for c in out["cases"])
    assert out["cost"] == "not measured (no chip)"
