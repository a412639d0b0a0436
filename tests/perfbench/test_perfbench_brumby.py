"""The attention-free serve cell's benchmark files: its configuration
against the catalog row, ``retention_flops.py`` against a hand count and the
program's parameter tree, the four new layer-metric files on a synthetic
context, the tiny cell through the harness, and the kind's ``check``
against a swapped token and a perturbed state."""
import copy
import json
import os
import types

import numpy as np
import pytest

import rehearsal
from brumby_tiny import (
    STAND_IN,
    TINY_BRUMBY,
    TINY_CELL,
    TINY_CLOSED_DECODED,
)
from perfbench import program_spans as ps
from perfbench import retention_flops, stats
from perfbench import trace_reduce as tr
from perfbench.builders import brumby_serve
from perfbench.readers import (
    cache_plan,
    decode_roofline_state,
    scope_roofline,
    scope_share,
)
from perfbench.traffic_kinds import serve_closed, serve_closed_decoded

ROOT = stats.repo_root()
BENCH = stats.load_json(os.path.join(ROOT, "BENCHMARK.json"))
PEAK = stats.load_json(os.path.join(ROOT, "perfbench", "peaks.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
(CELL,) = STAND_IN
ENTRY = next(c for c in BENCH["configs"] if c["file"].endswith(
    "brumby-14b-5layer.json"))
BODY = stats.load_json(os.path.join(ROOT, ENTRY["file"]))
NEW_METRICS = ["retention_share_of_decode", "retention_state_share_of_decode",
               "retention_share_of_prefill", "retention_state_roofline"]
ACCEPTED = ["compiles_in_window.serve", "sched_lane_occupancy",
            "admit_ms_p50", "ttft_p50_ms", "ttft_p95_ms.closed",
            "decode_step_ms_p50", "gap_p50_ms", "device_idle_share.serve",
            "hbm_peak_gb.serve", "idle_share.admit", "idle_share.step_host",
            "admit_dispatch_ms_p50", "admit_first_token_read_ms_p50",
            "admit_splice_ms_p50", "prefill_device_ms_p50",
            "queue_wait_ms_p50", "scope_unattributed_share.serve",
            "decode_ahead_share", "decode_roofline.ssm",
            "state_share_of_lane_cache"]
DECODE = ["deepspeed_tpu.inference.engine", "PROGRAM_DECODE_K"]
SIZES = brumby_serve.layer_sizes(BODY)


def catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG, encoding="utf-8") as f:
        for row in map(json.loads, f):
            if row["source_url"] == ENTRY["source"]:
                return row
    pytest.skip("the catalog no longer holds this configuration's row")


# ---------------------------------------------------------------------------
# the configuration and the entries
# ---------------------------------------------------------------------------
def test_every_published_key_is_in_the_file_under_its_key():
    row = catalog_row()
    for key, value in row["config"].items():
        if key not in ENTRY["reduced"]:
            assert key in BODY and BODY[key] == value, key
    assert BODY["num_hidden_layers"] == 5 < row["config"]["num_hidden_layers"]
    # the layer pattern's period is 1 (every block is the same): five is
    # the period and four more
    assert row["described_as"]["attention"] == "power retention layers"


def test_reduced_is_exactly_what_differs_from_the_catalog():
    """What ``test_configuration_entry_and_file`` checks, for a
    configuration that is cut (that test holds every configuration to
    ``reduced == []``): entry and file agree, ``reduced`` names keys of the
    file and no width, the file says what it assumed and which deployment
    it stands for, a cell runs it, its builders exist; and, where the
    catalog has the row, ``reduced`` is exactly the keys that differ."""
    import importlib

    assert BODY["name"] == ENTRY["name"] and BODY["source"] == ENTRY["source"]
    assert BODY["reduced"] == ENTRY["reduced"] == ["num_hidden_layers"]
    assert set(ENTRY) == {"name", "source", "file", "reduced", "why"}
    assert 1 <= len(ENTRY["why"]) <= 200 and len(ENTRY["source"]) <= 200
    for key in ENTRY["reduced"]:
        assert key in BODY
        assert not key.endswith(("_dim", "_rank", "_size")), key
    for role, builder in BODY["builders"].items():
        mod = importlib.import_module("perfbench.builders." + builder)
        assert callable(mod.build), (role, builder)
    assert set(BODY["assumed"]) >= {
        "degree", "gate", "normaliser", "qk_norm_and_rotary", "state",
        "prefill_chunk", "gate_bias_initialiser", "cache_positions",
        "decoding", "weights", "equations", "short_contexts"}
    assert all(len(why) > 10 for why in BODY["assumed"].values())
    assert "8 stages" in BODY["deployment"]
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == ENTRY["name"]] == [CELL]
    if os.path.exists(CATALOG):
        row = catalog_row()
        differs = sorted(k for k, v in row["config"].items()
                         if BODY.get(k, "missing") != v)
        assert differs == ENTRY["reduced"]


def test_serve_section_states_the_cache_and_the_limits():
    serve = BODY["serve"]
    assert serve["cache_positions"] == 1408 < BODY["max_position_embeddings"]
    # 24 lanes: the one change the issue permits, and why is in the file
    assert serve["serving"] == {"slots": 24} and "1.75%" in serve[
        "serving_why"]
    assert serve["state_dtype"] == "float32" and serve["dtype"] == "bf16"
    assert BODY["retention"]["degree"] == 2
    check = serve["decode_check"]
    upper = ["mean_margin", "largest_margin", "mean_state_error",
             "first_layer_head_state_error", "mean_tail_error"]
    assert set(check) >= {s + "_max" for s in upper} | {
        "share_within_tolerance_min", "live_lanes", "why",
        "system_readings", "lower_precision_readings"}
    assert 0 < check["mean_margin_max"] < check["largest_margin_max"]
    assert 0.5 < check["share_within_tolerance_min"] < 1.0
    # every limit has room above the largest reading the system gave ...
    sys_, low = check["system_readings"], check["lower_precision_readings"]
    assert sys_["runs"] >= 20
    for stat in upper:
        assert sys_[stat + "_largest"] * 1.05 < check[stat + "_max"], stat
    assert sys_["share_within_tolerance_smallest"] \
        > check["share_within_tolerance_min"]
    # ... and each lower precision is outside at least one, with room
    assert set(low) == {"bf16_state", "int8_weights"}
    for name, reading in low.items():
        outside = [stat for stat in upper
                   if reading[stat + "_smallest"]
                   > 1.05 * check[stat + "_max"]]
        assert outside, name
        assert reading["runs"] >= 3


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("use_sliding_window", True),
    ("rope_scaling", {"type": "yarn"})])
def test_another_form_of_the_block_is_refused(key, value):
    """The block is written in one form, the published one: builder and
    reference refuse a file that says otherwise, and a degree other than
    2."""
    from perfbench.reference import brumby

    other = dict(TINY_BRUMBY, **{key: value})
    with pytest.raises(ValueError, match="one form"):
        brumby_serve.model_config(other)
    with pytest.raises(ValueError, match="one form"):
        brumby.sizes(other)
    cubic = dict(TINY_BRUMBY, retention={"degree": 4, "eps": 1e-6})
    with pytest.raises(ValueError, match="degree 2"):
        brumby.sizes(cubic)


def test_the_traffic_file_is_the_issues():
    t = stats.load_json(os.path.join(
        ROOT, "perfbench", "traffic", "serve-closed-chat-long-32.json"))
    twin = stats.load_json(os.path.join(
        ROOT, "perfbench", "traffic", "serve-closed-chat-long-64.json"))
    assert t["kind"] == "serve_closed_decoded" and t["clients"] == 24
    # the two 40-value grids of the hybrid cell's mix, unchanged
    assert t["prompt_lengths"] == twin["prompt_lengths"]
    assert t["output_lengths"] == twin["output_lengths"]
    assert sum(t["output_lengths"]) == 7793
    assert (t["max_positions"], t["prompt_bucket"], t["ramp_output_step"],
            t["pregenerate_requests"], t["trace_seconds"],
            t["reference_samples"]) == (1408, 64, 4, 1200, 8, 4)
    assert set(t) == set(twin)
    assert serve_closed.bucketed(max(t["prompt_lengths"]), 64) \
        + max(t["output_lengths"]) == t["max_positions"] \
        == BODY["serve"]["cache_positions"]
    assert t["clients"] == BODY["serve"]["serving"]["slots"]


def test_the_new_metrics_are_appended_and_list_the_new_cell_alone():
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(NEW_METRICS[0])    # a later PR appends after them
    assert names[at:at + len(NEW_METRICS)] == NEW_METRICS
    assert at > names.index("kv_blocks_read_share")
    for m in BENCH["per_layer"][at:at + len(NEW_METRICS)]:
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["source"] == "device_trace"
        spec = stats.load_json(os.path.join(
            ROOT, "perfbench", "layer_metrics", m["name"] + ".json"))
        assert ps.program_constant(*spec["args"]["program"])
    by = {m["name"]: m for m in BENCH["per_layer"]}
    assert by["retention_state_roofline"]["better"] == "higher"
    assert by["retention_share_of_prefill"]["moves"] == "gap_p95_ms"
    # the accepted metrics that read what the shared scheduler emits or
    # are generic over a state list the cell, last; those that read keys
    # and values do not
    for name in ACCEPTED:
        assert by[name]["workloads"][-1] == CELL, name
    for name in ("kv_cache_share_of_decode", "kv_blocks_read_share",
                 "decode_roofline"):
        assert CELL not in by[name]["workloads"]
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(ACCEPTED) | set(NEW_METRICS)
    for m in BENCH["end_to_end"]:
        if m["name"] in ("serve_out_tokens_per_s", "gap_p95_ms"):
            assert m["workloads"][-1] == CELL
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == BENCH["workloads"][-1] and cell["chips"] == 1
    assert ENTRY == BENCH["configs"][-1]


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------
def test_counts_against_a_hand_count():
    c = BODY
    assert retention_flops.sym_dim(128) == 8256
    # q, o: 5120 x 5120 each; k, v: 5120 x 1024 each; the gate 5120 x 8 + 8;
    # q's and k's norm 128 each
    assert retention_flops.retention_params(5120, 40, 8, 128) \
        == 2 * 26_214_400 + 2 * 5_242_880 + 40_960 + 8 + 256 == 62_955_784
    assert retention_flops.gated_mlp_params(5120, 17408) == 267_386_880
    assert retention_flops.layer_params(5120, **SIZES) == 330_352_904
    assert retention_flops.model_params(5, c["vocab_size"], 5120, **SIZES) \
        == 5 * 330_352_904 + 2 * 777_912_320 + 5120 == 3_207_594_280
    assert retention_flops.state_bytes(8, 128) == 33_816_576
    assert retention_flops.norm_bytes(8, 128) == 264_192
    lane = 5 * (33_816_576 + 264_192)
    assert 32 * lane == pytest.approx(5.45e9, rel=1e-3)
    # a decode step reads the layers and the head, not the embedding
    assert retention_flops.decode_weight_bytes(
        5, c["vocab_size"], 5120, 2, **SIZES) \
        == 2 * (5 * 330_352_904 + 777_912_320 + 5120) \
        == pytest.approx(4.86e9, rel=1e-3)
    # the recurrence for 32 lanes in one layer: S and z twice, and q, y
    # [40, 128], k, v [8, 128], the gate [8] in float32
    small = 4 * (2 * 40 * 128 + 2 * 8 * 128 + 8)
    assert retention_flops.step_bytes(32, 40, 8, 128) \
        == 32 * (2 * 34_080_768 + small)
    # 13 operations a state element: 3 for the update, 2 for each of the
    # five query heads that share the read
    assert retention_flops.step_flops(32, 40, 8, 128) \
        == 32 * 8 * 8256 * 128 * 13
    assert 5 * retention_flops.step_flops(32, 40, 8, 128) \
        == pytest.approx(17.6e9, rel=2e-3)
    secs, bound = __import__("perfbench.flops", fromlist=["x"]) \
        .roofline_seconds(
            retention_flops.step_flops(32, 40, 8, 128),
            retention_flops.step_bytes(32, 40, 8, 128), PEAK["TPU v5 lite"])
    assert bound == "memory" and secs == pytest.approx(2.665e-3, rel=2e-3)


def test_counts_agree_with_the_programs_parameter_tree():
    """Shapes only (``jax.eval_shape``): the real configuration's tree has
    the counted parameters, leaf group by leaf group, and its lane cache
    holds at least the counted bytes: the program stores the symmetric
    square with 64 dead entries in 8,320 (ops/power_retention.py)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer_lm import GPT, num_params

    cfg = brumby_serve.model_config(BODY)
    model = GPT(cfg)
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 64), jnp.int32)))["params"]

    def count(tree):
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    block = shapes["h"]["block"]
    layers = BODY["num_hidden_layers"]
    assert count(block["attn"]) == layers * retention_flops.retention_params(
        5120, 40, 8, 128)
    assert count(block["mlp"]) == layers * retention_flops.gated_mlp_params(
        5120, 17408)
    assert count(shapes) == num_params(cfg) == retention_flops.model_params(
        layers, BODY["vocab_size"], 5120, **SIZES)
    assert {x.dtype for x in jax.tree.leaves(shapes)} == {
        jnp.dtype(jnp.bfloat16)}
    cache = jax.eval_shape(
        lambda p: model.apply({"params": p}, jnp.zeros((32, 1), jnp.int32),
                              deterministic=True, decode=True,
                              mutable=["cache"])[1]["cache"], shapes)
    leaves = cache["h"]["block"]["attn"]
    assert set(leaves) == {"ret_state", "ret_norm", "clock"}
    assert leaves["ret_state"].shape == (5, 32, 8, 128, 8320)
    assert leaves["ret_norm"].shape == (5, 32, 8, 8320)
    stored = sum(int(np.prod(x.shape)) * 4 for x in (
        leaves["ret_state"], leaves["ret_norm"]))
    counted = 32 * layers * (retention_flops.state_bytes(8, 128)
                             + retention_flops.norm_bytes(8, 128))
    assert stored * 8256 == counted * 8320


# ---------------------------------------------------------------------------
# the layer-metric files on a synthetic context
# ---------------------------------------------------------------------------
def _ctx(rows=None, modules=(), info=None, series=None, program=None,
         **system):
    from deepspeed_tpu.inference import engine

    name = program or engine.PROGRAM_DECODE_K
    mods = [types.SimpleNamespace(name=name + "(1)", start=a, end=b)
            for a, b in modules]
    red = tr.Reduced(devices={0: tr.Device(modules=mods)}, window=(0.0, 1e9))
    ctx = types.SimpleNamespace(
        red=red, notes={}, series=series or {},
        env=types.SimpleNamespace(peak=PEAK["TPU v5 lite"]),
        system=types.SimpleNamespace(info=info or {}, **system))
    full = None if rows is None else [
        dict(program=name, instruction="i%d" % i, path=path, seconds=secs,
             count=1) for i, (path, secs) in enumerate(rows)]
    setattr(ctx, "_program_spans", ps.Program(
        red=red, spans=[], rows=full, scopes=ps.program_module()))
    return ctx


def _spec(name):
    spec = stats.load_json(os.path.join(
        ROOT, "perfbench", "layer_metrics", name + ".json"))
    reader = {"scope_share": scope_share,
              "scope_roofline": scope_roofline}[spec["reader"]]
    return reader, spec["args"]


LAYER = "jit(decode_k)/while/body/GPT/h/block/"
ROWS = [(LAYER + "attn/ret_proj/c_attn/dot", 1.0),
        (LAYER + "attn/ret_qk_norm_rope/mul", 0.5),
        (LAYER + "attn/ret_state/ret_step", 6.0),
        (LAYER + "attn/ret_state/dot", 0.5),
        (LAYER + "attn/ret_out_proj/c_proj/dot", 1.0),
        ("jit(decode_k)/while/body/ret_state_carry/copy", 1.0),
        (LAYER + "mlp/c_fc/dot", 6.0),
        ("jit(decode_k)/while/body/GPT/lm_head/dot", 4.0)]


def test_the_share_files_read_the_four_scopes_and_the_carry_tag():
    reader, args = _spec("retention_share_of_decode")
    ctx = _ctx(rows=ROWS)
    assert reader.read(ctx, **args) == pytest.approx(100 * 9.0 / 20.0)
    note = ctx.notes["scope_share:" + "+".join(args["scopes"])]
    assert set(note) == {"ret_proj", "ret_qk_norm_rope", "ret_state",
                         "ret_out_proj"}
    reader, args = _spec("retention_state_share_of_decode")
    ctx = _ctx(rows=ROWS)
    assert reader.read(ctx, **args) == pytest.approx(100 * 7.5 / 20.0)
    assert ctx.notes["scope_share:ret_state+ret_state_carry"][
        "ret_state_carry"] == pytest.approx(100 * 1.0 / 20.0)
    # the prefill file reads the prefill programs alone
    from deepspeed_tpu.inference import engine

    reader, args = _spec("retention_share_of_prefill")
    assert reader.read(_ctx(rows=ROWS), **args) is None
    rows = [(p.replace("decode_k", "prefill"), s) for p, s in ROWS]
    assert reader.read(_ctx(rows=rows, program=engine.PROGRAM_PREFILL),
                       **args) == pytest.approx(100 * 9.0 / 20.0)
    # a program without the scopes (the parent's) reads nothing of them
    plain = [r for r in ROWS if "ret_" not in r[0]]
    for name in NEW_METRICS[:2]:
        reader, args = _spec(name)
        assert not reader.read(_ctx(rows=plain), **args)
        assert reader.read(_ctx(rows=None), **args) is None


def test_the_roofline_file_reads_the_steps_least_time_over_the_scopes():
    reader, args = _spec("retention_state_roofline")
    assert args["scope"] == "ret_state" and args["program"] == DECODE
    counts = {"flops": retention_flops.step_flops(32, 40, 8, 128),
              "bytes": retention_flops.step_bytes(32, 40, 8, 128),
              "calls_per_step": 5}
    least = counts["bytes"] / 819e9            # 2.665 ms a call
    rows = [(LAYER + "attn/ret_state/ret_step", 2 * 5 * least / 0.8),
            (LAYER + "attn/ret_state/dot", 2 * 5 * least / 0.8 / 4),
            (LAYER + "mlp/c_fc/dot", 3.0)]
    ctx = _ctx(rows=rows, modules=[(0, 26e6), (27e6, 53e6)],
               info={args["counts"]: counts})
    assert reader.read(ctx, **args) == pytest.approx(80.0 / 1.25)
    note = ctx.notes["scope_roofline:ret_state"]
    assert note["runs"] == 2 and note["calls"] == 10
    assert note["bound"] == "memory"
    assert note["least_ms_per_call"] == pytest.approx(2.665, rel=2e-3)
    # a program without the scope, a builder without the counts, a trace
    # without scopes: nothing, and nothing raised
    assert reader.read(_ctx(rows=rows[2:], modules=[(0, 1)], info={
        args["counts"]: counts}), **args) is None
    assert reader.read(_ctx(rows=rows, modules=[(0, 1)]), **args) is None
    assert reader.read(_ctx(rows=None, info={args["counts"]: counts}),
                       **args) is None


def test_the_accepted_state_metrics_read_a_cache_without_keys_and_values():
    """``decode_roofline.ssm`` with ``kv_bytes_per_position`` 0 and
    ``state_share_of_lane_cache`` on the plan event of a cache that is all
    state."""
    info = {"decode_program": "jit_decode_k", "slots": 32,
            "weight_bytes": 4.86e9, "kv_bytes_per_position": 0.0,
            "state_bytes_per_lane": 5 * 34_080_768.0}
    series = {"live_positions": [400, 600], "lanes_active": [32, 32]}
    ctx = _ctx(modules=[(i * 27e6, i * 27e6 + 26e6) for i in range(5)],
               info=info, series=series)
    nbytes = 4.86e9 + 32 * 2 * 5 * 34_080_768
    assert decode_roofline_state.read(ctx) == pytest.approx(
        100 * (nbytes / 819e9 * 1e3) / 26.0)
    plan = {"kind": "serve.cache_plan", "slots": 32, "kv_bytes_per_lane": 20,
            "state_bytes_per_lane": 5 * 34_345_280,
            "norm_bytes_per_lane": 5 * 266_240, "conv_bytes_per_lane": 0,
            "decode_attention": "none", "decode_attention_block": 0}
    plan["bytes_per_lane"] = plan["kv_bytes_per_lane"] \
        + plan["state_bytes_per_lane"]
    spec = stats.load_json(os.path.join(
        ROOT, "perfbench", "layer_metrics",
        "state_share_of_lane_cache.json"))
    got = cache_plan.read(_ctx(cache_plan=plan), **spec["args"])
    assert 99.9999 < got < 100


# ---------------------------------------------------------------------------
# the cell through the harness, and the kind's verdict
# ---------------------------------------------------------------------------
def test_the_stand_in_is_registered_for_any_subset_of_the_tests():
    assert rehearsal.CONFIGS[TINY_BRUMBY["name"]] is TINY_BRUMBY
    assert rehearsal.TRAFFIC[TINY_CELL["traffic"]] is TINY_CLOSED_DECODED
    assert TINY_CELL in rehearsal.CELLS
    assert rehearsal.STAND_IN[CELL] == TINY_CELL["name"]
    # every published key of the real file is in the tiny one
    published = set(BODY) - {"assumed", "deployment"}
    assert published <= set(TINY_BRUMBY), published - set(TINY_BRUMBY)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(tmp_path, trace):
    root = rehearsal.make_root(tmp_path)
    rc, last, err = rehearsal.run_cell(root, TINY_CELL["name"], trace=trace,
                                       seed=2 ** 31 + 17, seconds=1.5)
    assert rc == 0, err[-2000:]
    assert last["correct"] is True and last["rehearsal"] is True
    assert last["failed"] == 0 and last["attempted"] > 0
    if trace:
        # no device plane on the CPU: the trace's readers find nothing and
        # say nothing; the program's event is read
        assert last["metrics"]["compiles_in_window.serve"]["value"] == 0
        assert 99 < last["metrics"]["state_share_of_lane_cache"]["value"] \
            <= 100
        assert not set(NEW_METRICS) & set(last["metrics"])
        assert "kv_blocks_read_share" not in last["metrics"]
    else:
        assert set(last["metrics"]) == {"serve_out_tokens_per_s",
                                        "gap_p95_ms", "setup_s"}
    run = next(json.loads(ln) for ln in err.splitlines()
               if ln.startswith("{") and '"event": "run"' in ln)
    assert run["verdict"]["decode"]["ok"] is True
    assert run["verdict"]["decode"]["positions"] > 0
    assert run["verdict"]["decode"]["lanes"] == 2


class WindowEnds(Exception):
    pass


def serve_until(system, prompts, wants, polls):
    """What ``serve_closed.drive`` records, for requests submitted at once
    and a run that ``poll_fn`` ends at its ``polls``-th call, with the
    requests that ask for more than that still in their lanes."""
    sched, by_rid, done, count = system.scheduler, {}, [], []

    def on_token(rid, token, ended):
        req = by_rid[rid]
        req.times.append(2.0 + len(req.times))
        req.tokens.append(int(token))
        if ended:
            done.append(req)

    def poll():
        count.append(1)
        if len(count) == polls:
            raise WindowEnds

    for i, (p, want) in enumerate(zip(prompts, wants)):
        rid = sched.submit(p, max_new_tokens=want, stream_callback=on_token)
        by_rid[rid] = serve_closed.Req(client=i, prompt=p, want=want,
                                       ramp=False, t_submit=1.0)
    with pytest.raises(WindowEnds):
        sched.run(poll_fn=poll)
    sched._pending.clear()
    return {"done": done, "by_rid": by_rid,
            "in_flight": [r for r in by_rid.values() if r not in done]}


def tiny_env(config, seed):
    return types.SimpleNamespace(
        config=config, traffic=TINY_CLOSED_DECODED, seed=seed, t_open=0.0,
        t_close=100.0)


PLAN = types.SimpleNamespace(vocab=128)


def tiny_prompts(seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 128, size=n).tolist() for n in (9, 20, 5, 30)]


def test_check_fails_a_swapped_token_and_a_perturbed_state():
    """The tiny system serves two requests to their end and is stopped
    with two more in their lanes. ``check`` over that record is correct
    and has read both live lanes' state out of the scheduler's cache, in
    the program's stored order, against the reference's. With a token
    after the first swapped in the record of the completed requests it is
    not, and no request counts as failed; nor with a swapped first token;
    nor where a live lane has taken in other tokens than its client was
    streamed; nor where one KV head of a lane's state, or its normaliser,
    is off by a hundredth. Without live lanes there is no verdict."""
    env = tiny_env(TINY_BRUMBY, 11)
    system = brumby_serve.build(env, None)
    try:
        record = serve_until(system, tiny_prompts(0), (6, 30, 6, 30), 12)
    finally:
        system.unsubscribe(system.on_bus)
    assert system.cache_plan["slots"] == 4
    assert system.cache_plan["decode_attention"] == "none"
    assert len(record["done"]) == 2 and len(record["in_flight"]) == 2
    kept = system.scheduler.lanes_at_exit
    assert len(kept.live) == 2
    real_lanes = system.live_lanes

    def checked(edit=None, lanes=None):
        rec = copy.deepcopy(record)
        rec["by_rid"] = {rid: next(
            x for x in rec["done"] + rec["in_flight"] if x.client == r.client)
            for rid, r in record["by_rid"].items()}
        if edit:
            edit(rec)
        system.scheduler.lanes_at_exit = kept     # ``check`` lets it go
        system.live_lanes = (lambda n, rng: lanes(real_lanes(n, rng))) \
            if lanes else real_lanes
        return serve_closed_decoded.check(env, system, PLAN, rec)

    def swap(where, k):
        def edit(rec):
            for i, r in enumerate(rec[where]):
                r.tokens[k] = (r.tokens[k] + 1 + i) % 128
        return edit

    def perturb(leaf):
        def lanes(found):
            for lane in found:      # one KV head of the first layer
                lane[leaf] = lane[leaf].at[0, 1].multiply(1.01)
            return found
        return lanes

    good = checked()
    assert good["correct"] is True and good["decode"]["positions"] == 10
    assert good["decode"]["lanes"] == 2
    assert system.scheduler.lanes_at_exit is None
    assert all(6 < lane["taken_in"] < 30 for lane in good["live_lanes"])
    # float32 against float32: the state the timed steps left is the
    # reference's, to the order of the sums
    assert good["decode"]["mean_state_error"] < 2e-6
    assert good["decode"]["first_layer_head_state_error"] < 2e-6
    assert good["decode"]["mean_tail_error"] < 2e-6
    bad = checked(swap("done", 3))
    assert bad["correct"] is False and bad["failed"] == 0
    assert bad["decode"]["ok"] is False
    assert all(f["margin"] == 0.0 for f in bad["reference"])
    first = checked(swap("done", 0))
    assert first["correct"] is False
    assert any(f["margin"] > f["tolerance"] for f in first["reference"])
    other = checked(swap("in_flight", 2))
    assert other["correct"] is False
    assert other["live_lanes_streamed_their_tokens"] is False
    state = checked(lanes=perturb("ret_state"))
    assert state["correct"] is False and state["failed"] == 0
    assert state["decode"]["first_layer_head_state_error"] \
        == pytest.approx(0.01, rel=1e-2)
    assert state["decode"]["mean_tail_error"] < 2e-6
    assert state["decode"]["mean_margin"] == 0.0       # tokens cannot tell
    norm = checked(lanes=perturb("ret_norm"))
    assert norm["correct"] is False
    assert norm["decode"]["mean_tail_error"] > 1e-3
    assert norm["decode"]["mean_state_error"] < 2e-6
    system.live_lanes = real_lanes
    system.scheduler.lanes_at_exit = None
    none = serve_closed_decoded.check(env, system, PLAN, record)
    assert none["correct"] is False and none["decode"]["lanes"] == 0


@pytest.mark.parametrize("control", ["bf16_state", "int8_weights"])
def test_check_fails_a_lower_precision(control):
    """The two controls the cell's limits were set against, at the tiny
    size in float32, where the system reads ~1e-6: a state and normaliser
    kept in bfloat16 move no token and are outside the limits on state and
    normaliser, read from the lanes the run left; weights rounded to 8
    bits a column (served rounded, the reference reading the originals
    through the builder's ``reference_params``) are outside them too."""
    import jax
    import jax.numpy as jnp

    config = copy.deepcopy(TINY_BRUMBY)
    if control == "bf16_state":
        config["serve"]["state_dtype"] = "bfloat16"
    env = tiny_env(config, 12)
    system = brumby_serve.build(env, None)
    system.unsubscribe(system.on_bus)
    eng = system.engine
    system.scheduler._ensure_compiled()
    original = eng.params
    if control == "int8_weights":
        def round8(w):
            scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
            return jnp.round(w / scale) * scale

        flat, tree = jax.tree_util.tree_flatten_with_path(original)
        eng._params = jax.tree_util.tree_unflatten(tree, [
            round8(leaf) if str(getattr(path[-1], "key", "")) in (
                "kernel", "lm_head") else leaf for path, leaf in flat])
        system.reference_params = lambda: original
    record = serve_until(system, tiny_prompts(1), (6, 30, 6, 30), 12)
    verdict = serve_closed_decoded.check(env, system, PLAN, record)
    decode = verdict["decode"]
    assert verdict["correct"] is False and verdict["failed"] == 0
    assert decode["lanes"] == 2
    assert decode["first_layer_head_state_error"] \
        > 10 * decode["limits"]["first_layer_head_state_error_max"]
    assert decode["mean_tail_error"] \
        > 10 * decode["limits"]["mean_tail_error_max"]
    if control == "bf16_state":
        assert decode["mean_margin"] == 0.0            # tokens cannot tell


def test_what_two_accepted_tests_pinned_holds_but_for_the_lists():
    """``test_perfbench_kv_blocks_read.py::
    test_entry_and_file_name_what_the_program_exports`` holds its metric's
    cells to EVERY serve cell, and ``test_perfbench_falcon_h1.py::
    test_the_new_metrics_are_appended_and_list_the_new_cell_alone`` holds
    two metrics that are generic over a state to ONE cell; the contract
    lets a later PR append a cell to a list, and a cell without keys and
    values reports no share of them read. Both fail since this cell exists
    (PERF.md, section 7, (10) and (11)); this holds everything else they
    asserted."""
    from deepspeed_tpu.inference import scheduler as scheduler_mod
    from deepspeed_tpu.telemetry import spans

    by = {m["name"]: m for m in BENCH["per_layer"]}
    entry = by["kv_blocks_read_share"]
    assert entry["source"] == "program_span" and entry["unit"] == "%"
    assert entry["better"] == "lower" and entry["layer"] == "decode step"
    # every serve cell whose lanes hold keys and values, and no other
    assert entry["workloads"] == by["kv_cache_share_of_decode"]["workloads"]
    assert set(by["decode_step_ms_p50"]["workloads"]) \
        - set(entry["workloads"]) == {CELL}
    spec = stats.load_json(os.path.join(
        ROOT, "perfbench", "layer_metrics", "kv_blocks_read_share.json"))
    assert spec["reader"] == "span_attr_mean" and spec["args"] == {
        "span": spans.SERVE_DECODE_STEP, "attr": "kv_blocks_read_share",
        "scale": 100.0}
    assert "kv_blocks_read_share=" in open(
        scheduler_mod.__file__, encoding="utf-8").read()
    hybrid = ["ssm_share_of_decode", "ssm_state_share_of_decode",
              "ssm_share_of_prefill", "ssm_state_roofline",
              "decode_roofline.ssm", "state_share_of_lane_cache"]
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(hybrid[0])
    assert names[at:at + len(hybrid)] == hybrid
    for name in hybrid:
        cells = by[name]["workloads"]
        assert cells[0] == "falcon-h1-34b-serve-closed"
        assert by[name]["unit"] == "%"
        assert cells[1:] == ([CELL] if name in hybrid[4:] else [])
