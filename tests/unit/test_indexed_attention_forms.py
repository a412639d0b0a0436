"""The two forms of a decode step's attention over the rows an indexer chose
(``ops/indexed_attention.py``): the chosen rows gathered, and the lanes' live
blocks under the chosen mask through the dense path's kernel ``decode_attn``
(Pallas interpreter mode here); the selection that stands in for ``top_k``
and a scatter of its rows (the set by a threshold, its positions by a
running count, nothing sorted); the rule that tells a step which form to
take; and that a model without an indexer never enters the module."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import indexed_attention as ia
from deepspeed_tpu.ops.pallas import decode_attention as da

B, S, TOPK = 4, 40, 8


def scatter(rows, ok, positions):
    want = np.zeros((rows.shape[0], positions), bool)
    np.logical_or.at(want, (np.arange(rows.shape[0])[:, None],
                            np.asarray(rows)), np.asarray(ok))
    return want


def top_k_rows(scores, visible, topk):
    """What the selection has to equal: ``lax.top_k`` of the masked scores,
    ``ok`` where a row is one the lane sees."""
    masked = jnp.where(visible, scores, -jnp.inf)
    vals, rows = jax.lax.top_k(masked, min(topk, scores.shape[-1]))
    return rows, vals > -jnp.inf


MASK_CASES = {
    "ties_at_the_kth": lambda at: np.broadcast_to(at <= 33, (B, S)),
    "fewer_than_topk": lambda at: at < np.array([3, TOPK - 1, 1, 5])[:, None],
    "exactly_topk": lambda at: np.broadcast_to((at >= 7) & (at < 7 + TOPK),
                                               (B, S)),
    "left_padding": lambda at: (at >= np.array([5, 11, 0, 30])[:, None])
    & (at <= 37),
    "nothing_visible": lambda at: (at <= 30)
    & np.array([True, False, True, False])[:, None],
}


@pytest.mark.parametrize("case", MASK_CASES)
def test_the_mask_is_the_scatter_of_top_ks_rows(case):
    """Small integer scores, so that many positions share the ``topk``-th
    value, zeros of both signs among them (a relu's weighted sum gives both,
    and ``top_k`` puts -0.0 below 0.0): the lower position wins a tie."""
    rng = np.random.default_rng(3)
    scores = rng.integers(-1, 3, size=(B, S)).astype(np.float32)
    scores[rng.random((B, S)) < 0.3] = -0.0
    scores[rng.random((B, S)) < 0.2] = 0.0
    visible = jnp.asarray(MASK_CASES[case](np.arange(S)[None, :]))
    rows, ok = top_k_rows(jnp.asarray(scores), visible, TOPK)
    got = np.asarray(jax.jit(ia.chosen_set, static_argnums=2)(
        jnp.asarray(scores), visible, TOPK))
    np.testing.assert_array_equal(got, scatter(rows, ok, S))
    seen = np.asarray(visible).sum(1)
    assert (got.sum(1) == np.minimum(seen, TOPK)).all()
    # where a lane sees no more than ``topk`` rows the mask is ``visible``
    few = seen <= TOPK
    np.testing.assert_array_equal(got[few], np.asarray(visible)[few])


def test_a_tie_of_both_zeros_goes_to_the_positive_one_whatever_its_row():
    """Two chosen of [-0.0, 0.0, -0.0, 0.0]: ``top_k`` takes rows 1 and 3
    (the floats' total order), not rows 0 and 1 (a float comparison, under
    which all four tie)."""
    scores = jnp.asarray([[-0.0, 0.0, -0.0, 0.0]], jnp.float32)
    visible = jnp.ones((1, 4), bool)
    rows, ok = top_k_rows(scores, visible, 2)
    assert sorted(np.asarray(rows)[0].tolist()) == [1, 3]
    np.testing.assert_array_equal(
        np.asarray(ia.chosen_set(scores, visible, 2)),
        [[False, True, False, True]])
    mine, counts = ia.choose(scores, visible, 2)
    assert np.asarray(mine).tolist() == [[1, 3]] and np.asarray(counts).all()


def test_the_mask_follows_the_rows_that_count():
    """Of ``top_k``'s rows a prefix may count (``ok``): the set of that
    many is the prefix's scatter (``top_k`` hands its rows over best first,
    ties to the lower row), not the whole row's."""
    rng = np.random.default_rng(4)
    scores = jnp.asarray(rng.integers(0, 3, size=(B, S)), jnp.float32)
    visible = jnp.ones((B, S), bool)
    rows, ok = top_k_rows(scores, visible, TOPK)
    ok = ok & (jnp.arange(TOPK) < TOPK // 2)
    np.testing.assert_array_equal(
        np.asarray(ia.chosen_set(scores, visible, TOPK // 2)),
        scatter(rows, ok, S))


def drawn_scores(kind, lanes, positions, seed=5):
    """Scores that try the selection: the floats' total order where a float
    comparison ties, many ties at the threshold, none at all, and
    magnitudes at both ends of the format."""
    rng = np.random.default_rng(seed)
    shape = (lanes, positions)
    if kind == "random":
        return rng.standard_normal(shape).astype(np.float32)
    if kind == "quantised":
        scores = rng.integers(-2, 3, shape).astype(np.float32)
        scores[rng.random(shape) < 0.3] = -0.0
        return scores
    if kind == "all_equal":
        return np.full(shape, 1.5, np.float32)
    if kind == "zeros_of_both_signs":
        return np.where(rng.random(shape) < 0.5, 0.0, -0.0).astype(np.float32)
    assert kind == "denormals_and_large_negatives"
    scores = (rng.integers(-3, 4, shape) * np.float32(1e-42)).astype(
        np.float32)
    scores[rng.random(shape) < 0.2] = -3e38
    scores[rng.random(shape) < 0.1] = 3e38
    return scores


SCORE_KINDS = ["random", "quantised", "all_equal", "zeros_of_both_signs",
               "denormals_and_large_negatives"]
# positions and ``topk``: more positions than chosen, with a ragged last
# chunk of ``rows_of`` and with none; no more positions than ``topk``, where
# nothing is searched
SELECT_SHAPES = {"300_of_37": (300, 37), "256_of_128": (256, 128),
                 "64_of_100": (64, 100)}


def lanes_that_see(positions, topk):
    """``visible``: lanes that see nothing, one row, one under ``topk``,
    exactly ``topk``, one over it, every row, and ``topk + 5`` behind left
    padding."""
    seen = np.minimum([0, 1, topk - 1, topk, topk + 1, positions, topk + 5],
                      positions)
    at = np.arange(positions)[None, :]
    first = np.zeros(len(seen), int)
    first[-1] = positions - seen[-1]
    return (at >= first[:, None]) & (at < (first + seen)[:, None])


@pytest.mark.parametrize("shape", SELECT_SHAPES)
@pytest.mark.parametrize("kind", SCORE_KINDS)
def test_the_set_is_top_ks_to_the_row(kind, shape):
    positions, topk = SELECT_SHAPES[shape]
    visible = lanes_that_see(positions, topk)
    scores = jnp.asarray(drawn_scores(kind, len(visible), positions))
    rows, ok = top_k_rows(scores, jnp.asarray(visible), topk)
    got = np.asarray(jax.jit(ia.chosen_set, static_argnums=2)(
        scores, jnp.asarray(visible), topk))
    np.testing.assert_array_equal(got, scatter(rows, ok, positions))
    assert (got.sum(1) == np.minimum(visible.sum(1), topk)).all()
    assert not (got & ~visible).any()


@pytest.mark.parametrize("shape", SELECT_SHAPES)
@pytest.mark.parametrize("kind", SCORE_KINDS)
def test_the_rows_are_the_sets_positions_each_once(kind, shape):
    """``choose``: ascending, ``ok`` on the first ``min(K, visible)`` and on
    no other; what the caller leaves in ``chosen_rows`` pads with -1."""
    positions, topk = SELECT_SHAPES[shape]
    visible = lanes_that_see(positions, topk)
    scores = jnp.asarray(drawn_scores(kind, len(visible), positions, seed=6))
    chosen = np.asarray(ia.chosen_set(scores, jnp.asarray(visible), topk))
    rows, ok = jax.jit(ia.choose, static_argnums=2)(
        scores, jnp.asarray(visible), topk)
    assert rows.shape == ok.shape == (len(visible), min(topk, positions))
    assert rows.dtype == jnp.int32
    leaf = np.asarray(jnp.where(ok, rows, -1))
    for lane, want in zip(leaf, chosen):
        n = want.sum()
        assert lane[:n].tolist() == np.flatnonzero(want).tolist()
        assert (lane[n:] == -1).all()
    assert (np.asarray(ok).sum(1) == np.minimum(visible.sum(1), topk)).all()


@pytest.mark.parametrize("count", [1, 7, 128, 200])
def test_rows_of_counts_through_every_chunk(count):
    """The positions of a mask, whichever chunks hold them: all in the
    first, all in the last (a ragged one), one a chunk, a full chunk."""
    positions = 3 * ia._CHUNK + 17
    masks = np.zeros((4, positions), bool)
    masks[0, :count] = True
    masks[1, positions - count:] = True
    masks[2, np.arange(min(count, 4)) * ia._CHUNK + 5] = True
    masks[3, ia._CHUNK:ia._CHUNK + min(count, ia._CHUNK)] = True
    rows, ok = ia.rows_of(jnp.asarray(masks), count)
    for lane, counts, want in zip(np.asarray(rows), np.asarray(ok), masks):
        assert lane[counts].tolist() == np.flatnonzero(want).tolist()
        assert counts.sum() == want.sum() and not lane[~counts].any()


# lanes: every row of a full cache; left padding and a clock just past a
# block boundary; two visible rows (fewer than ``topk``); a clock on a
# block's last row; nothing visible
FIRST = np.array([0, 37, 130, 5, 200])
CLOCK = np.array([255, 128, 131, 127, 100])
POSITIONS, BLOCK, D = 256, 128, 128


def step_inputs(dtype, heads, kv_heads, layers=3, seed=0):
    rng = np.random.default_rng(seed)
    lanes = len(FIRST)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    at = np.arange(POSITIONS)[None, :]
    visible = jnp.asarray((at >= FIRST[:, None]) & (at <= CLOCK[:, None]))
    return dict(q=draw(lanes, heads, D), q_idx=draw(lanes, 3, 16),
                w=jnp.asarray(rng.standard_normal((lanes, 3)), jnp.float32),
                keys=draw(layers, lanes, POSITIONS, kv_heads, D),
                values=draw(layers, lanes, POSITIONS, kv_heads, D),
                index_keys=draw(layers, lanes, POSITIONS, 16),
                visible=visible, clock=jnp.asarray(CLOCK, jnp.int32))


def dense_masked_softmax(q, keys, values, chosen):
    """The plain form on the float32 upcast: a softmax over every position
    under the chosen set as a mask."""
    lanes, heads, d = q.shape
    kv = keys.shape[2]
    qg = np.asarray(q, np.float64).reshape(lanes, kv, heads // kv, d)
    k, v = (np.asarray(t, np.float64) for t in (keys, values))
    att = np.einsum("bhgd,bkhd->bhgk", qg, k) / np.sqrt(d)
    att = np.where(chosen[:, None, None, :], att, -np.inf)
    with np.errstate(invalid="ignore"):     # a lane with nothing chosen
        att = np.exp(att - att.max(-1, keepdims=True))
    att /= att.sum(-1, keepdims=True)
    return np.einsum("bhgk,bkhd->bhgd", att, v).reshape(lanes, heads, d)


def rel_l2(got, want):
    got, want = (np.asarray(t, np.float64) for t in (got, want))
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize("stacked", [True, False], ids=["stacked", "a_layer"])
@pytest.mark.parametrize("heads,kv_heads", [(4, 4), (16, 2)],
                         ids=["per_head", "groups_of_8"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-6),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_blocks_under_the_mask_give_what_the_gathered_rows_give(
        stacked, heads, kv_heads, dtype, tol):
    """Both forms on the same rows, against each other and against the
    dense masked softmax: the stacked leaves under a traced layer and one
    layer's own, full and grouped heads, blocks of 128 so that lanes read
    one block or both. bfloat16 within the tolerance ``chip_smoke.py``
    gives the step (2e-2, relative L2); float32 differs by the order of
    its sums. The lane with nothing visible gets finite numbers."""
    x = step_inputs(dtype, heads, kv_heads)
    topk, layer = 24, 2
    scores = ia.index_scores(x["q_idx"][:, None], x["index_keys"][layer],
                             x["w"][:, None])[:, 0]
    rows, ok = ia.choose(scores, x["visible"], topk)
    chosen = ia.chosen_set(scores, x["visible"], topk)
    assert np.asarray(chosen).sum(1).tolist() == [24, 24, 2, 24, 0]
    np.testing.assert_array_equal(np.asarray(chosen),
                                  scatter(rows, ok, POSITIONS))
    keys, values, at = x["keys"], x["values"], jnp.int32(layer)
    if not stacked:
        keys, values, at = keys[layer], values[layer], None

    @jax.jit
    def both(keys, values, at):
        with jax.default_matmul_precision("highest"):
            return (ia.attend_chosen_blocks(x["q"], keys, values, at, chosen,
                                            x["clock"], BLOCK, dtype),
                    ia.attend_chosen_rows(x["q"], keys, values, at, rows, ok,
                                          1.0 / np.sqrt(D), dtype))

    blocks, gathered = both(keys, values, at)
    assert blocks.dtype == gathered.dtype == dtype
    assert np.isfinite(np.asarray(blocks, np.float32)).all()
    want = dense_masked_softmax(x["q"], x["keys"][layer], x["values"][layer],
                                np.asarray(chosen))
    live = slice(0, 4)          # the last lane sees nothing
    assert rel_l2(blocks[live], gathered[live]) < tol
    assert rel_l2(blocks[live], want[live]) < tol
    assert rel_l2(gathered[live], want[live]) < tol


def lanes_holding(blocks_held, block):
    """``(first, clock)`` of two lanes that hold ``blocks_held`` blocks
    between them: one lane with left padding, one of a single block."""
    first = np.array([block + 2, 0])
    clock = np.array([block + 2 + (blocks_held - 2) * block, 5])
    lo, hi = da.live_blocks(first, clock, block)
    assert (hi - lo + 1).sum() == blocks_held
    return jnp.asarray(first, jnp.int32), jnp.asarray(clock, jnp.int32)


def test_the_rule_compares_the_two_costs_and_is_set_by_nothing_else():
    """Blocks while the positions in the lanes' live blocks cost less than
    the chosen rows' gathers, by the two measured constants: the last
    block count under the crossover reads blocks, the next gathers."""
    block, chosen, lanes = 128, 64, 2
    ratio = ia._NS_A_CHOSEN_ROW / ia._NS_A_BLOCK_POSITION
    assert 6 < ratio < 12       # measured on the chip: PERF.md, PR 49
    under = int(np.ceil(ratio * lanes * chosen / block)) - 1
    for held, want in ((under, True), (under + 1, False)):
        first, clock = lanes_holding(held, block)
        assert bool(jax.jit(lambda f, c: ia.reads_blocks(
            f, c, block, chosen))(first, clock)) is want
    # the form is the call's to read: no field, option or variable sets it
    import inspect

    from deepspeed_tpu.models.transformer_lm import IndexerConfig
    assert set(inspect.signature(ia.decode_step).parameters) == {
        "q", "q_idx", "w", "keys", "values", "index_keys", "layer",
        "visible", "clock", "topk", "dtype"}
    assert set(IndexerConfig.__dataclass_fields__) == {
        "n_heads", "head_dim", "topk", "q_chunk", "kv_chunk", "rope_dim"}
    assert "environ" not in inspect.getsource(ia)


def decode_step_under(monkeypatch, x, topk, dtype, spy=False):
    """``decode_step`` jitted afresh (so that it reads the module as
    patched). With ``spy`` the forms are stand-ins that say who ran."""
    if spy:
        shape = x["q"].shape
        monkeypatch.setattr(ia, "attend_chosen_blocks",
                            lambda *a: jnp.full(shape, 1.0, dtype))
        monkeypatch.setattr(ia, "attend_chosen_rows",
                            lambda *a: jnp.full(shape, 2.0, dtype))
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda *a: ia.decode_step(*a, topk, dtype))(
            x["q"], x["q_idx"], x["w"], x["keys"], x["values"],
            x["index_keys"], jnp.int32(1), x["visible"], x["clock"])


@pytest.mark.parametrize("side", ["under_the_crossover", "over_it"])
def test_a_step_takes_the_form_the_rule_names(monkeypatch, side):
    """The same lanes and a ``topk`` on each side of the crossover: five
    lanes hold 6 blocks of 128 = 768 positions, worth ~90 gathered rows, so
    24 chosen a lane (120) read blocks and 12 (60) gather."""
    x = step_inputs(jnp.float32, 4, 4)
    monkeypatch.setattr(da, "_BLOCK_BYTES", BLOCK * 4 * D * 4)
    topk = {"under_the_crossover": 24, "over_it": 12}[side]
    first = jnp.asarray(np.argmax(np.asarray(x["visible"]), 1), jnp.int32)
    want = bool(ia.reads_blocks(first, x["clock"], BLOCK, topk))
    assert want is (side == "under_the_crossover")
    y, _, _ = decode_step_under(monkeypatch, x, topk, jnp.float32, spy=True)
    assert float(y[0, 0, 0]) == (1.0 if want else 2.0)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-6),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_a_step_gives_the_same_under_each_form(monkeypatch, dtype, tol):
    """The step with the gathers priced at nothing (rows) and at the
    measured cost (these lanes read blocks): the same rows and ``ok``, ``y``
    within the forms' own difference, and not bit for bit (another order of
    sums: the blocks form did run)."""
    x = step_inputs(dtype, 16, 2)
    monkeypatch.setattr(da, "_BLOCK_BYTES", BLOCK * 2 * D * x["q"].itemsize)
    by_blocks = decode_step_under(monkeypatch, x, 24, dtype)
    monkeypatch.setattr(ia, "_NS_A_CHOSEN_ROW", 0.0)
    by_rows = decode_step_under(monkeypatch, x, 24, dtype)
    np.testing.assert_array_equal(by_blocks[1], by_rows[1])
    np.testing.assert_array_equal(by_blocks[2], by_rows[2])
    live = slice(0, 4)
    assert 0 < rel_l2(by_blocks[0][live], by_rows[0][live]) < tol


def tiny_served():
    """The tiny selected-attention configuration (``tests/perfbench/
    keye_vl_tiny.py``: a scanned model whose cache is longer than its
    ``topk``) in float32 behind a scheduler of three lanes, built afresh:
    its decode program is traced under the module as it is patched now."""
    import deepspeed_tpu
    from deepspeed_tpu import serving
    from deepspeed_tpu.models.transformer_lm import GPT
    from keye_vl_tiny import TINY_KEYE
    from perfbench.builders import keye_vl_serve

    section = dict(TINY_KEYE["serve"], param_dtype="float32",
                   compute_dtype="float32")
    jax.clear_caches()
    eng = deepspeed_tpu.init_inference(
        GPT(keye_vl_serve.model_config(TINY_KEYE, section)), dtype="fp32",
        seed=3)
    sched = serving.build_serving(eng, {"slots": 3, "prompt_bucket": 16})
    sched.retain_lanes = True
    return sched


def serve(prompts, until):
    """The tokens streamed and, of each lane, its last decode step's own
    account of its choice (``chosen_rows``, ``choice_query``,
    ``choice_weights``, every layer), the run stopped with the lanes in
    flight once the first request has ``until`` tokens."""
    sched = tiny_served()
    out = {}
    rids = [sched.submit(p, max_new_tokens=24, stream_callback=lambda r, t,
                         d: out.setdefault(r, []).append(int(t)))
            for p in prompts]

    def stop():
        if len(out.get(rids[0], ())) >= until:
            raise KeyboardInterrupt

    with pytest.raises(KeyboardInterrupt):
        sched.run(poll_fn=stop)
    kept = sched.lanes_at_exit
    assert sorted(kept.live) == [0, 1, 2]
    return [out[r] for r in rids], [kept.last_step(n) for n in range(3)]


def test_the_served_model_leaves_the_same_account_under_each_form(
        monkeypatch):
    """The scanned tiny model through the scheduler, its decode program
    traced once under each form (three lanes of one 64-position block
    against 3 x 8 rows: blocks by the rule; with the gathers priced at
    nothing: rows): the same tokens, the same rows chosen in every layer,
    and the query and weights that chose them the same bit for bit in the
    first layer and to the forms' own difference after it (a later layer's
    query is made of the attention's output before it)."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 128, size=n).tolist() for n in (20, 9, 14)]
    calls = []
    real = ia.attend_chosen_blocks
    monkeypatch.setattr(ia, "attend_chosen_blocks",
                        lambda *a: calls.append(1) or real(*a))
    with jax.default_matmul_precision("highest"):
        blocks_tokens, blocks_steps = serve(prompts, 6)
        assert calls            # the decode program holds the kernel's call
        monkeypatch.setattr(ia, "_NS_A_CHOSEN_ROW", 0.0)
        rows_tokens, rows_steps = serve(prompts, 6)
    jax.clear_caches()
    assert blocks_tokens == rows_tokens
    for a, b in zip(blocks_steps, rows_steps):
        assert sorted(a) == sorted(b) == ["choice_query", "choice_weights",
                                          "chosen_rows"]
        np.testing.assert_array_equal(a["chosen_rows"], b["chosen_rows"])
        for name in ("choice_query", "choice_weights"):
            np.testing.assert_array_equal(a[name][0], b[name][0])
            np.testing.assert_allclose(a[name], b[name], atol=5e-6, rtol=0)
        assert (np.asarray(a["chosen_rows"]) >= 0).sum(-1).tolist() == [8, 8]


FAMILIES = {
    "gpt": ("serve_hashes", ""),
    "hybrid": ("hybrid_hashes", "hybrid_"),
    "retention": ("retention_hashes", "retention_"),
    "latent": ("latent_hashes", "latent_"),
}


@pytest.mark.parametrize("family", FAMILIES)
def test_a_model_without_an_indexer_never_enters_the_module(monkeypatch,
                                                            family):
    """With the module's every entry patched to raise, the decode and
    prefill programs of the four families without an indexer still trace,
    and lower to the text on record (``tests/unit/data/
    gpt_program_hashes.json``: what they lowered to before the module had a
    second form)."""
    from unit import gpt_program_hashes as recorded_programs

    def refuse(*a, **kw):
        raise AssertionError("ops/indexed_attention.py entered")

    for name in ("decode_step", "attend_tiled", "attend_chosen_rows",
                 "attend_chosen_blocks", "reads_blocks", "choose",
                 "chosen_set", "rows_of"):
        monkeypatch.setattr(ia, name, refuse)
    fn, prefix = FAMILIES[family]
    got = getattr(recorded_programs, fn)()
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "gpt_program_hashes.json")) as fh:
        want = json.load(fh)
    programs = [k for k in got if "decode_k" in k or "prefill" in k]
    assert any("decode_k" in k for k in programs) \
        and any("prefill" in k for k in programs)
    assert all(k.startswith(prefix) for k in programs)
    assert {k: got[k] for k in programs} == {k: want[k] for k in programs}


def test_the_decode_program_of_a_model_with_an_indexer_sorts_nothing_there():
    """The tiny selected-attention model's decode program as it is lowered
    (its cache of 64 positions is longer than its ``topk`` of 8): the
    selection's scope holds the search's loops and neither a ``top_k`` nor a
    sort (the router's ``top_k`` of 3 experts is elsewhere)."""
    import re

    sched = tiny_served()
    sched._ensure_compiled()
    eng = sched.engine
    text = eng._decode_k_fn.fn.lower(
        eng.params, jnp.zeros((3,), jnp.int32), sched.lane_cache.shapes,
        jax.random.PRNGKey(0), jnp.float32(0.0), 1).as_text(debug_info=True)
    jax.clear_caches()
    assert "chlo.top_k" in text and "moe_router/top_k" in text
    under = set(re.findall(r'loc\("([^"]*/dsa_select/[^"]*)"', text))
    assert any(name.endswith("/while") for name in under)
    assert not [name for name in under if "top_k" in name or "sort" in name]
