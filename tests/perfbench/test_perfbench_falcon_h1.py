"""The hybrid serve cell's benchmark files: its configuration against the
catalog row, ``ssm_flops.py`` against a hand count and the program's
parameter tree, the three new readers on synthetic contexts, the tiny cell
through the harness, and the kind's ``check`` against a swapped token."""
import json
import os
import types

import numpy as np
import pytest

import rehearsal
from falcon_h1_tiny import (
    STAND_IN,
    TINY_CELL,
    TINY_CLOSED_DECODED,
    TINY_FALCON_H1,
)
from perfbench import program_spans as ps
from perfbench import ssm_flops, stats
from perfbench import trace_reduce as tr
from perfbench.builders import falcon_h1_serve
from perfbench.readers import cache_plan, decode_roofline_state, scope_roofline
from perfbench.traffic_kinds import serve_closed, serve_closed_decoded

ROOT = stats.repo_root()
BENCH = stats.load_json(os.path.join(ROOT, "BENCHMARK.json"))
PEAK = stats.load_json(os.path.join(ROOT, "perfbench", "peaks.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
(CELL,) = STAND_IN
ENTRY = next(c for c in BENCH["configs"] if c["file"].endswith(
    "falcon-h1-34b-6layer.json"))
BODY = stats.load_json(os.path.join(ROOT, ENTRY["file"]))
NEW_METRICS = ["ssm_share_of_decode", "ssm_state_share_of_decode",
               "ssm_share_of_prefill", "ssm_state_roofline",
               "decode_roofline.ssm", "state_share_of_lane_cache"]
DECODE = ["deepspeed_tpu.inference.engine", "PROGRAM_DECODE_K"]


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
    assert BODY["num_hidden_layers"] == 6 < row["config"]["num_hidden_layers"]
    # six is the layer pattern's period (1: every block is the same) + 5
    assert row["config"]["attn_layer_indices"] is None


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
    assert set(BODY["assumed"]) >= {"state", "conv_tail", "cache_positions",
                                    "decoding", "weights", "equations"}
    assert "12 chips" in BODY["deployment"]
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == ENTRY["name"]] == [CELL]
    if os.path.exists(CATALOG):
        row = catalog_row()
        differs = sorted(k for k, v in row["config"].items()
                         if BODY.get(k, "missing") != v)
        assert differs == ENTRY["reduced"]


def test_serve_section_states_the_cache_and_the_three_limits():
    serve = BODY["serve"]
    assert serve["cache_positions"] == 1408 < BODY["max_position_embeddings"]
    assert serve["serving"] == {"slots": 64}
    assert serve["state_dtype"] == "float32" and serve["dtype"] == "bf16"
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


@pytest.mark.parametrize("key", ["mamba_conv_bias", "mamba_proj_bias",
                                 "mamba_rms_norm", "mamba_norm_before_gate"])
def test_another_form_of_the_mixer_is_refused_by_name(key):
    """The mixer is written in one form, the published one: builder and
    reference refuse a file that sets one of the four flags otherwise."""
    from perfbench.reference import falcon_h1

    other = dict(TINY_FALCON_H1, **{key: not TINY_FALCON_H1[key]})
    with pytest.raises(ValueError, match=key):
        falcon_h1_serve.model_config(other)
    with pytest.raises(ValueError, match=key):
        falcon_h1.sizes(other)


def test_the_traffic_file_is_the_issues():
    t = stats.load_json(os.path.join(
        ROOT, "perfbench", "traffic", "serve-closed-chat-long-64.json"))
    short = stats.load_json(os.path.join(
        ROOT, "perfbench", "traffic", "serve-closed-chat.json"))
    assert t["kind"] == "serve_closed_decoded" and t["clients"] == 64
    assert t["prompt_lengths"] == short["prompt_lengths"]
    outs = t["output_lengths"]
    assert len(outs) == 40 and sum(outs) == 7793 and outs == sorted(outs)
    assert (outs[0], outs[-1]) == (33, 512)
    # the 40 quantiles (i + 0.5) / 40 of a log-normal(160, 0.7), clipped
    from statistics import NormalDist

    want = [min(512, max(16, round(160 * np.exp(
        0.7 * NormalDist().inv_cdf((i + 0.5) / 40))))) for i in range(40)]
    assert outs == want
    assert (t["max_positions"], t["prompt_bucket"], t["ramp_output_step"],
            t["pregenerate_requests"], t["trace_seconds"],
            t["reference_samples"]) == (1408, 64, 4, 1200, 8, 4)
    assert serve_closed.bucketed(max(t["prompt_lengths"]), 64) + max(outs) \
        == t["max_positions"] == BODY["serve"]["cache_positions"]


def test_the_new_metrics_are_appended_and_list_the_new_cell_alone():
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(NEW_METRICS[0])    # a later PR appends after them
    assert names[at:at + len(NEW_METRICS)] == NEW_METRICS
    for m in BENCH["per_layer"][at:at + len(NEW_METRICS)]:
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        spec = stats.load_json(os.path.join(
            ROOT, "perfbench", "layer_metrics", m["name"] + ".json"))
        program = spec["args"].get("program")
        if program:
            assert ps.program_constant(*program)
    # the accepted roofline of the decode step has no state term: the new
    # cell is not in its list, and is in the list of its own
    by = {m["name"]: m for m in BENCH["per_layer"]}
    assert CELL not in by["decode_roofline"]["workloads"]
    for name in ("compiles_in_window.serve", "kv_cache_share_of_decode",
                 "decode_ahead_share", "hbm_peak_gb.serve",
                 "device_idle_share.serve", "prefill_device_ms_p50"):
        assert CELL in by[name]["workloads"]
    for m in BENCH["end_to_end"]:
        if m["name"] in ("serve_out_tokens_per_s", "gap_p95_ms"):
            assert CELL in m["workloads"]


def test_the_metric_before_the_new_ones_is_where_it_was():
    """What ``test_perfbench_decode_ahead.py::
    test_entry_and_file_name_what_the_program_exports`` asserts of its
    entry, but for "it is the last": the metrics appended since follow it
    (and whatever a later PR appends follows those)."""
    from deepspeed_tpu.telemetry import spans

    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index("decode_ahead_share")
    assert names[at + 1:at + 1 + len(NEW_METRICS)] == NEW_METRICS
    entry = BENCH["per_layer"][at]
    assert entry["source"] == "program_span" and entry["unit"] == "%"
    assert entry["better"] == "higher" and entry["layer"] == "scheduler"
    moved = next(e for e in BENCH["end_to_end"]
                 if e["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])
    serve_cells = {w for m in BENCH["per_layer"]
                   if m["name"] == "idle_share.step_host"
                   for w in m["workloads"]}
    assert set(entry["workloads"]) == serve_cells
    spec = stats.load_json(os.path.join(
        ROOT, "perfbench", "layer_metrics", "decode_ahead_share.json"))
    assert spec["reader"] == "span_attr_mean" and spec["how"]
    assert spec["args"]["span"] == spans.SERVE_DECODE_STEP


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------
def test_counts_against_a_hand_count():
    c = BODY
    sizes = falcon_h1_serve.layer_sizes(c)
    # 5120 -> 4096 + 5120 + 32 = 9248; conv (4 + 1) x 5120; A, D, dt_bias;
    # the norm's 4096; 4096 -> 5120
    assert ssm_flops.mixer_params(5120, 4096, 2, 256, 32, 4) \
        == 5120 * 9248 + 5 * 5120 + 96 + 4096 + 4096 * 5120 == 68_351_072
    assert ssm_flops.attention_params(5120, 20, 4, 128) == 31_457_280
    assert ssm_flops.gated_mlp_params(5120, 21504) == 330_301_440
    assert ssm_flops.hybrid_layer_params(5120, **sizes) == 430_120_032
    assert ssm_flops.hybrid_params(6, c["vocab_size"], 5120, **sizes) \
        == 6 * 430_120_032 + 2 * 1_336_934_400 + 5120 == 5_254_594_112
    assert ssm_flops.state_bytes(32, 128, 256) == 4_194_304
    assert ssm_flops.conv_tail_bytes(4096, 2, 256, 4) == 30_720
    assert ssm_flops.kv_bytes_per_position(6, 4, 128) == 12_288
    lane = 6 * (4_194_304 + 30_720) + 1408 * 12_288
    assert lane == pytest.approx(42.6e6, rel=2e-3)
    assert 64 * lane == pytest.approx(2.73e9, rel=2e-3)
    # a decode step reads the layers and the head, not the embedding
    assert ssm_flops.decode_weight_bytes(
        6, c["vocab_size"], 5120, 2, **sizes) \
        == 2 * (6 * 430_120_032 + 1_336_934_400 + 5120) \
        == pytest.approx(7.84e9, rel=2e-3)
    # the recurrence for 64 lanes in one layer: the state twice, and
    # x, y [32, 128], B, C [2, 256], dt [32] in float32
    small = 4 * (2 * 4096 + 2 * 512 + 32)
    assert ssm_flops.scan_step_bytes(64, 32, 128, 256, 2) \
        == 64 * (2 * 4_194_304 + small)
    secs, bound = __import__("perfbench.flops", fromlist=["x"]) \
        .roofline_seconds(
            ssm_flops.scan_step_flops(64, 32, 128, 256),
            ssm_flops.scan_step_bytes(64, 32, 128, 256, 2),
            PEAK["TPU v5 lite"])
    assert bound == "memory" and secs == pytest.approx(0.657e-3, rel=5e-3)


def test_counts_agree_with_the_programs_parameter_tree():
    """Shapes only (``jax.eval_shape``): the real configuration's tree has
    the counted parameters, leaf group by leaf group; the tiny one's lane
    cache has the counted bytes."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer_lm import GPT, num_params

    cfg = falcon_h1_serve.model_config(BODY)
    model = GPT(cfg)
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 64), jnp.int32)))["params"]

    def count(tree):
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    sizes = falcon_h1_serve.layer_sizes(BODY)
    block = shapes["h"]["block"]
    layers = BODY["num_hidden_layers"]
    assert count(block["mamba"]) == layers * ssm_flops.mixer_params(
        5120, 4096, 2, 256, 32, 4)
    assert count(block["attn"]) == layers * ssm_flops.attention_params(
        5120, 20, 4, 128)
    assert count(block["mlp"]) == layers * ssm_flops.gated_mlp_params(
        5120, 21504)
    assert count(shapes) == num_params(cfg) == ssm_flops.hybrid_params(
        layers, BODY["vocab_size"], 5120, **sizes)
    assert {x.dtype for x in jax.tree.leaves(shapes)} == {
        jnp.dtype(jnp.bfloat16)}


# ---------------------------------------------------------------------------
# readers
# ---------------------------------------------------------------------------
def _ctx(rows=None, modules=(), info=None, series=None, **system):
    from deepspeed_tpu.inference import engine

    name = engine.PROGRAM_DECODE_K
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


def test_cache_plan_reads_the_events_shares():
    plan = {"kind": "serve.cache_plan", "slots": 64,
            "kv_bytes_per_lane": 17_301_504 + 1408 * 6 + 24,
            "state_bytes_per_lane": 25_165_824,
            "conv_bytes_per_lane": 184_320}
    plan["bytes_per_lane"] = sum(v for k, v in plan.items()
                                 if k.endswith("_per_lane"))
    spec = stats.load_json(os.path.join(
        ROOT, "perfbench", "layer_metrics",
        "state_share_of_lane_cache.json"))
    got = cache_plan.read(_ctx(cache_plan=plan), **spec["args"])
    assert got == pytest.approx(100 * 25_350_144 / plan["bytes_per_lane"])
    assert 59 < got < 60
    assert cache_plan.read(_ctx(), **spec["args"]) is None    # no event
    assert cache_plan.read(_ctx(cache_plan=None), **spec["args"]) is None


def test_decode_roofline_with_state_adds_the_state_both_ways():
    info = {"decode_program": "jit_decode_k", "slots": 64,
            "weight_bytes": 7.84e9, "kv_bytes_per_position": 12288.0,
            "state_bytes_per_lane": 25_350_144.0}
    series = {"live_positions": [400, 600], "lanes_active": [64, 64]}
    step_ns = 20e6
    ctx = _ctx(modules=[(i * 21e6, i * 21e6 + step_ns) for i in range(5)],
               info=info, series=series)
    nbytes = 7.84e9 + 64 * (500 * 12288 + 2 * 25_350_144)
    want = 100 * (nbytes / 819e9 * 1e3) / 20.0
    assert decode_roofline_state.read(ctx) == pytest.approx(want)
    assert ctx.notes["decode_roofline_state"]["state_bytes_moved"] \
        == pytest.approx(64 * 2 * 25_350_144)
    # nothing to read: no state in the builder's info, or no step traced
    plain = dict(info)
    del plain["state_bytes_per_lane"]
    assert decode_roofline_state.read(_ctx(
        modules=[(0, step_ns)], info=plain, series=series)) is None
    assert decode_roofline_state.read(_ctx(info=info, series=series)) is None


def test_scope_roofline_is_least_time_over_the_scopes_time():
    counts = {"flops": ssm_flops.scan_step_flops(64, 32, 128, 256),
              "bytes": ssm_flops.scan_step_bytes(64, 32, 128, 256, 2),
              "calls_per_step": 6}
    least = counts["bytes"] / 819e9            # 0.657 ms a call
    rows = [("jit(decode_k)/while/body/GPT/h/block/mamba/ssm_scan/mul",
             2 * 6 * least / 0.5),              # at half its roofline
            ("jit(decode_k)/while/body/GPT/h/block/mamba/ssm_conv/add", 1.0),
            ("jit(decode_k)/while/body/GPT/h/block/mlp/c_fc/dot", 3.0)]
    ctx = _ctx(rows=rows, modules=[(0, 20e6), (21e6, 41e6)],
               info={"scan_step": counts})
    got = scope_roofline.read(ctx, "ssm_scan", DECODE, "scan_step")
    assert got == pytest.approx(50.0)
    note = ctx.notes["scope_roofline:ssm_scan"]
    assert note["runs"] == 2 and note["calls"] == 12
    assert note["bound"] == "memory"
    # a program without the scope, a builder without the counts, a trace
    # without scopes: nothing, and nothing raised
    assert scope_roofline.read(_ctx(rows=rows[2:], modules=[(0, 1)], info={
        "scan_step": counts}), "ssm_scan", DECODE, "scan_step") is None
    assert scope_roofline.read(_ctx(rows=rows, modules=[(0, 1)]),
                               "ssm_scan", DECODE, "scan_step") is None
    assert scope_roofline.read(_ctx(rows=None, info={"scan_step": counts}),
                               "ssm_scan", DECODE, "scan_step") is None
    assert scope_roofline.read(_ctx(rows=rows, modules=[(0, 1)], info={
        "scan_step": counts}), "ssm_scan", ["no.such.module", "X"],
        "scan_step") is None


def test_a_trace_without_a_device_plane_reads_nothing():
    ctx = types.SimpleNamespace(
        red=tr.Reduced(), notes={}, series={},
        env=types.SimpleNamespace(peak=PEAK["TPU v5 lite"]),
        system=types.SimpleNamespace(info={"scan_step": {}}))
    assert scope_roofline.read(ctx, "ssm_scan", DECODE, "scan_step") is None
    assert decode_roofline_state.read(ctx) is None
    assert cache_plan.read(ctx, ["a"], "b") is None


# ---------------------------------------------------------------------------
# the cell through the harness, and the kind's verdict
# ---------------------------------------------------------------------------
def test_the_stand_in_is_registered_for_any_subset_of_the_tests():
    assert rehearsal.CONFIGS[TINY_FALCON_H1["name"]] is TINY_FALCON_H1
    assert rehearsal.TRAFFIC[TINY_CELL["traffic"]] is TINY_CLOSED_DECODED
    assert TINY_CELL in rehearsal.CELLS
    assert rehearsal.STAND_IN[CELL] == TINY_CELL["name"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_hybrid_cell_rehearses(tmp_path, trace):
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
        assert 0 < last["metrics"]["state_share_of_lane_cache"]["value"] \
            < 100
        assert "ssm_state_roofline" not in last["metrics"]
    else:
        assert set(last["metrics"]) == {"serve_out_tokens_per_s",
                                        "gap_p95_ms", "setup_s"}
    run = next(json.loads(ln) for ln in err.splitlines()
               if ln.startswith("{") and '"event": "run"' in ln)
    assert run["verdict"]["decode"]["ok"] is True
    assert run["verdict"]["decode"]["positions"] > 0


LIMITS = {"mean_margin_max": 0.01, "share_within_tolerance_min": 0.9,
          "largest_margin_max": 0.5, "mean_state_error_max": 0.01,
          "first_layer_head_state_error_max": 0.007,
          "mean_tail_error_max": 0.008}


@pytest.mark.parametrize("margins,ok", [
    ([0.0] * 99 + [0.04], True),
    ([0.0] * 99 + [0.6], False),             # one token far off: the cap
    ([0.02] * 100, False),                   # all a little off: the mean
    ([0.0] * 80 + [0.06] * 20, False),       # a fifth beyond the tolerance
])
def test_judge_decode_holds_three_statistics_of_the_margins(margins, ok):
    got = serve_closed_decoded.judge_decode(margins, 0.05, LIMITS)
    assert got["ok"] is ok and got["positions"] == 100
    assert got["largest_margin"] == max(margins)
    assert set(got["limits"]) == {"mean_margin_max", "largest_margin_max",
                                  "share_within_tolerance_min"}


def lane_errors(first_layer_heads, second_layer=0.008, tail=0.004):
    """A lane's ``state_errors`` of a two-layer model with three heads."""
    return {"by_layer": [float(np.mean(first_layer_heads)), second_layer],
            "by_head": [list(first_layer_heads), [second_layer] * 3],
            "tail_by_layer": [tail, tail]}


@pytest.mark.parametrize("lanes,ok", [
    ([lane_errors([0.004, 0.005, 0.004]),
      lane_errors([0.005, 0.004, 0.0045])], True),
    # every layer: weights in fewer bits
    ([lane_errors([0.009, 0.010, 0.009], 0.016, 0.009)] * 2, False),
    # one head of the first layer, on average over the lanes: how the
    # state is stored (the layer's own mean, 0.0067, would not tell)
    ([lane_errors([0.004, 0.012, 0.004]),
      lane_errors([0.004, 0.010, 0.004])], False),
    # a head far off in one lane only is that lane's noise
    ([lane_errors([0.004, 0.009, 0.004]),
      lane_errors([0.004, 0.004, 0.004])], True),
    # the tail alone
    ([lane_errors([0.004, 0.005, 0.004], tail=0.02)] * 2, False),
])
def test_judge_decode_holds_the_live_lanes_state_and_tail(lanes, ok):
    got = serve_closed_decoded.judge_decode([0.0] * 10, 0.05, LIMITS, lanes)
    assert got["ok"] is ok and got["lanes"] == 2
    assert got["first_layer_head_state_error"] == pytest.approx(
        max(np.mean([e["by_head"][0] for e in lanes], axis=0)))
    assert len(got["state_error_by_layer"]) == 2
    assert len(got["first_layer_state_error_by_head"]) == 3


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


def test_check_reads_the_live_lanes_and_fails_a_swapped_token():
    """The tiny system serves two requests to their end and is stopped
    with two more in their lanes. ``check`` over that record is correct
    and has read both live lanes' state out of the scheduler's cache. With
    a token after the first swapped in the record of the completed
    requests (same count, inside the vocabulary: what ``serve_closed``'s
    check cannot see) it is not, and no request counts as failed; nor with
    a swapped first token; nor where a live lane has taken in other tokens
    than its client was streamed. Without live lanes there is no verdict.
    """
    import copy

    env = tiny_env(TINY_FALCON_H1, 11)
    system = falcon_h1_serve.build(env, None)
    try:
        record = serve_until(system, tiny_prompts(0), (6, 30, 6, 30), 12)
    finally:
        system.unsubscribe(system.on_bus)
    assert system.cache_plan["slots"] == 4
    assert len(record["done"]) == 2 and len(record["in_flight"]) == 2
    kept = system.scheduler.lanes_at_exit
    assert len(kept.live) == 2

    def checked(edit=None):
        rec = copy.deepcopy(record)
        rec["by_rid"] = {rid: next(
            x for x in rec["done"] + rec["in_flight"] if x.client == r.client)
            for rid, r in record["by_rid"].items()}
        if edit:
            edit(rec)
        system.scheduler.lanes_at_exit = kept     # ``check`` lets it go
        return serve_closed_decoded.check(env, system, PLAN, rec)

    def swap(where, k):
        def edit(rec):
            for i, r in enumerate(rec[where]):
                r.tokens[k] = (r.tokens[k] + 1 + i) % 128
        return edit

    good = checked()
    assert good["correct"] is True and good["decode"]["positions"] == 10
    assert good["decode"]["lanes"] == 2
    assert system.scheduler.lanes_at_exit is None
    assert all(6 < lane["taken_in"] < 30 for lane in good["live_lanes"])
    # float32 against float32: the state the timed steps left is the
    # reference's, to the order of the sums
    assert good["decode"]["mean_state_error"] < 1e-6
    assert good["decode"]["first_layer_head_state_error"] < 1e-6
    assert good["decode"]["mean_tail_error"] < 1e-6
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
    system.scheduler.lanes_at_exit = None
    none = serve_closed_decoded.check(env, system, PLAN, record)
    assert none["correct"] is False and none["decode"]["lanes"] == 0


@pytest.mark.parametrize("control", ["bf16_state", "int8_weights"])
def test_check_fails_a_lower_precision(control):
    """The two controls the cell's limits were set against, at the tiny
    size in float32, where the system reads ~2e-7: a recurrent state kept
    in bfloat16 moves no token and is outside the limit on the first
    layer's heads, read from the lanes the run left; weights rounded to 8
    bits a column (served rounded, the reference reading the originals
    through the builder's ``reference_params``) are outside the tail's
    limit too."""
    import copy

    import jax
    import jax.numpy as jnp

    config = copy.deepcopy(TINY_FALCON_H1)
    if control == "bf16_state":
        config["serve"]["state_dtype"] = "bfloat16"
    env = tiny_env(config, 12)
    system = falcon_h1_serve.build(env, None)
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
    if control == "bf16_state":
        assert decode["mean_margin"] == 0.0            # tokens cannot tell
        assert decode["mean_tail_error"] < 1e-6        # nor the tail
    else:
        assert decode["mean_tail_error"] \
            > 10 * decode["limits"]["mean_tail_error_max"]
