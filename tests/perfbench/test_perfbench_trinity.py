"""The window-and-full serve cell's benchmark files: its configuration
against the catalog row, ``afmoe_flops.py`` against a hand count and the
program's parameter tree, the seven new layer-metric files on a synthetic
context, the two new traffic files, the tiny cell through the harness, and
the new kind's ``check`` against a swapped token, a perturbed row of the
full layer and a perturbed row of a ring. Every entry of ``BENCHMARK.json``
is found BY NAME: nothing here says where in a list an entry stands or how
long a list is, so the next appended cell breaks none of it."""
import copy
import importlib
import json
import os
import types

import numpy as np
import pytest

import rehearsal
from perfbench import afmoe_flops, mla_flops, stats
from perfbench import program_spans as ps
from perfbench import trace_reduce as tr
from perfbench.builders import afmoe_serve
from perfbench.readers import (
    cache_plan,
    decode_roofline_window,
    scope_roofline,
    scope_share,
    step_expert_load,
)
from perfbench.traffic_kinds import serve_resident, serve_resident_decoded
from trinity_tiny import STAND_IN, TINY_CELL, TINY_TRAFFIC, TINY_TRINITY

ROOT = stats.repo_root()
BENCH = stats.load_json(os.path.join(ROOT, "BENCHMARK.json"))
PEAK = stats.load_json(os.path.join(ROOT, "perfbench", "peaks.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL, OVER = "trinity-large-serve-resident-16k", "gpt-1.3b-serve-open-over"
CONFIG = "trinity-large-ep8-5layer"
REDUCED = ["num_hidden_layers", "num_dense_layers", "num_experts",
           "vocab_size"]
ENTRY = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
BODY = stats.load_json(os.path.join(ROOT, ENTRY["file"]))
TRAFFIC = stats.load_json(os.path.join(
    ROOT, "perfbench", "traffic", "serve-resident-longctx-24.json"))
NEW_METRICS = ["window_attn_share_of_decode", "full_attn_share_of_decode",
               "window_attn_roofline", "full_attn_roofline",
               "decode_roofline.swa", "window_share_of_lane_cache",
               "moe_experts_with_rows_share"]
DECODE = ["deepspeed_tpu.inference.engine", "PROGRAM_DECODE_K"]
READERS = {"scope_share": scope_share, "scope_roofline": scope_roofline,
           "decode_roofline_window": decode_roofline_window,
           "cache_plan": cache_plan, "step_expert_load": step_expert_load}
HEADS = dict(n_heads=48, n_kv_heads=8, head_dim=128)


def catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG, encoding="utf-8") as f:
        for row in map(json.loads, f):
            if row["source_url"] == ENTRY["source"]:
                return row
    pytest.skip("the catalog no longer holds this configuration's row")


def spec_of(name):
    spec = stats.load_json(os.path.join(
        ROOT, "perfbench", "layer_metrics", name + ".json"))
    return READERS[spec["reader"]], spec["args"]


def per_layer(name):
    (metric,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    return metric


# ---------------------------------------------------------------------------
# the configuration and the entries, by name
# ---------------------------------------------------------------------------
def test_every_published_key_is_in_the_file_under_its_key():
    row = catalog_row()
    for key, value in row["config"].items():
        if key not in REDUCED + ["layer_types"]:
            assert key in BODY and BODY[key] == value, key
    # the cut: published layer 0 and layers 8-11, one whole period
    held = BODY["published"]["layers_held"]
    assert held == [0, 8, 9, 10, 11]
    assert BODY["layer_types"] == [row["config"]["layer_types"][i]
                                   for i in held]
    assert BODY["published"]["layer_types"] == row["config"]["layer_types"]
    assert [k[0] for k in BODY["layer_types"]] == list("ssssf")
    for key in REDUCED:
        assert BODY["published"][key] == row["config"][key] > BODY[key]
    assert (BODY["num_hidden_layers"], BODY["num_dense_layers"],
            BODY["num_experts"], BODY["vocab_size"]) == (5, 1, 32, 25024)
    assert BODY["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert "window and full attention mixed" in row["mechanisms"]


def test_reduced_is_exactly_what_differs_from_the_catalog():
    """What ``test_configuration_entry_and_file`` checks, for a
    configuration that is cut (that test holds every configuration to
    ``reduced == []``): entry and file agree, ``reduced`` names keys of the
    file and no width, the file says what it assumed and which deployment
    it stands for, one cell runs it, its builder exists; and, where the
    catalog has the row, the four keys and the kinds of the layers kept
    are all that differ."""
    assert BODY["name"] == ENTRY["name"] and BODY["source"] == ENTRY["source"]
    assert BODY["reduced"] == ENTRY["reduced"] == REDUCED
    assert set(ENTRY) == {"name", "source", "file", "reduced", "why"}
    assert 1 <= len(ENTRY["why"]) <= 200 and len(ENTRY["source"]) <= 200
    for key in REDUCED:
        assert key in BODY
        assert not key.endswith(("_dim", "_rank", "_size")) or \
            key == "vocab_size", key
    for role, builder in BODY["builders"].items():
        mod = importlib.import_module("perfbench.builders." + builder)
        assert callable(mod.build), (role, builder)
    assert set(BODY["assumed"]) >= {
        "expert_bias", "balance_rule", "sandwich_norm", "gate_proj",
        "qk_norm", "rotary", "window", "ring_slack", "prefill", "decoding",
        "weights", "cache_positions"}
    assert all(len(why) > 10 for why in BODY["assumed"].values())
    assert "8-chip expert-parallel" in BODY["deployment"]
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == CONFIG] == [CELL]
    row = catalog_row()
    differs = sorted(k for k, v in row["config"].items()
                     if BODY.get(k, "missing") != v)
    assert differs == sorted(REDUCED + ["layer_types"])


def test_the_file_reckons_its_own_bytes():
    b, serve = BODY["bytes"], BODY["serve"]
    held = BODY["moe"]["experts_held"][1]
    assert BODY["moe"]["routed_over"] == 256 and held == 32 == \
        BODY["num_experts"]
    weights = afmoe_flops.decode_weight_bytes(
        5, 1, 25024, 3072, 12288, 3072, experts_read=held, n_shared=1,
        n_routed=256, **HEADS)
    # (a step reads one row of the embedding a lane: not among its bytes)
    assert b["parameter_bytes"] == weights + 25024 * 3072 * 2
    assert b["parameters"] == 4321903872
    window, full = afmoe_flops.lane_cache_bytes(
        4, 1, 4096 + serve["window_slack"], serve["cache_positions"], 8, 128)
    assert (window, full) == (71303168, 100663296)
    assert b["window_bytes_per_lane"] == window
    assert 0 < b["lane_bytes"] - window - full < 200_000    # masks, clocks
    assert b["lane_cache_bytes"] == 24 * b["lane_bytes"]
    # 12.8 GB of 15.75 GiB before a pass's temporaries; dense caches in
    # every layer would not leave room for 16 lanes
    assert 12.7e9 < b["parameter_bytes"] + b["lane_cache_bytes"] < 12.9e9
    assert 16 * 5 * full + b["parameter_bytes"] > 16.9e9 * 0.98


def test_serve_section_states_the_cache_and_the_limits():
    serve = BODY["serve"]
    assert serve["cache_positions"] == 24576 \
        < BODY["max_position_embeddings"]
    assert serve["window_slack"] == 256 and BODY["sliding_window"] == 4096
    assert serve["serving"] == {"slots": 24, "prompt_bucket": 2048}
    assert serve["dtype"] == "bf16"
    check = serve["decode_check"]
    upper = ["mean_margin", "largest_margin", "mean_state_error",
             "first_layer_head_state_error", "mean_tail_error"]
    assert set(check) >= {s + "_max" for s in upper} | {
        "share_within_tolerance_min", "why", "system_readings",
        "lower_precision_readings"}
    assert 0 < check["mean_margin_max"] < check["largest_margin_max"]
    assert 0.5 < check["share_within_tolerance_min"] < 1.0
    # every limit has room above the largest reading the system gave ...
    sys_, low = check["system_readings"], check["lower_precision_readings"]
    assert sys_["runs"] >= 10
    for stat in upper:
        assert sys_[stat + "_largest"] * 1.05 < check[stat + "_max"], stat
    assert sys_["share_within_tolerance_smallest"] \
        > check["share_within_tolerance_min"]
    # ... and each lower precision is outside at least one, in every run
    assert set(low) == {"bf16_softmax_and_router", "int8_weights"}
    for name, reading in low.items():
        assert reading["runs"] >= 2 and reading["refused"] is True
        outside = [stat for stat in upper
                   if reading[stat + "_smallest"] > check[stat + "_max"]]
        assert outside, name


def test_the_traffic_files():
    t = TRAFFIC
    base = stats.load_json(os.path.join(
        ROOT, "perfbench", "traffic", "serve-resident-longctx-32.json"))
    assert t["kind"] == "serve_resident_decoded" and t["clients"] == 24
    assert {k: t[k] for k in t if k not in (
        "kind", "why", "grid", "clients", "prompt_lengths")} == {
        k: base[k] for k in base if k not in (
            "kind", "why", "grid", "clients", "prompt_lengths")}
    want = [round(4097 * (16384 / 4097) ** ((i + 0.5) / 24))
            for i in range(24)]
    assert t["prompt_lengths"] == want
    buckets = {-(-n // 2048) * 2048 for n in want}
    assert buckets == {6144, 8192, 10240, 12288, 14336, 16384}
    assert max(buckets) + t["output_tokens"] == t["max_positions"] \
        == BODY["serve"]["cache_positions"]
    over = stats.load_json(os.path.join(
        ROOT, "perfbench", "traffic", "serve-open-chat-40rps.json"))
    under = stats.load_json(os.path.join(
        ROOT, "perfbench", "traffic", "serve-open-chat-16rps.json"))
    assert (over["rate_per_s"], over["pregenerate_requests"]) == (40.0, 2400)
    assert {k: v for k, v in over.items() if k not in (
        "why", "grid", "rate_per_s", "pregenerate_requests")} == {
        k: v for k, v in under.items() if k not in (
            "why", "grid", "rate_per_s", "pregenerate_requests")}


def test_the_entries_list_the_cells_by_name():
    """Both cells report ``serve_out_tokens_per_s`` and ``setup_s`` (not
    ``gap_p95_ms``); the seven new metrics list the new configuration's
    cell alone; each accepted metric either cell lists moves one of its
    two end-to-end metrics and has its file."""
    ends = {m["name"]: m for m in BENCH["end_to_end"]}
    for cell in (CELL, OVER):
        assert cell in ends["serve_out_tokens_per_s"]["workloads"]
        assert cell not in ends["gap_p95_ms"]["workloads"]
        listing = {m["name"] for m in BENCH["per_layer"]
                   if cell in m.get("workloads", ())}
        assert listing
        for name in listing:
            assert per_layer(name)["moves"] in ("serve_out_tokens_per_s",
                                                "setup_s"), name
            assert os.path.exists(os.path.join(
                ROOT, "perfbench", "layer_metrics", name + ".json")), name
    assert "workloads" not in ends["setup_s"]
    for name in NEW_METRICS:
        metric = per_layer(name)
        assert metric["workloads"] == [CELL] and metric["unit"] == "%"
        assert metric["moves"] == "serve_out_tokens_per_s"
        assert set(metric) == {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
    assert per_layer("window_attn_roofline")["source"] == "device_trace"
    assert per_layer("window_share_of_lane_cache")["source"] \
        == "program_counter"
    mine = {m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", ())}
    # as its sibling lists them, but for the sibling's own mechanism
    sibling = {m["name"] for m in BENCH["per_layer"]
               if "keye-vl-2.0-serve-resident-16k" in m.get("workloads", ())}
    assert mine - set(NEW_METRICS) == {
        n for n in sibling if not n.startswith(("dsa_", "index_key_"))
        and n != "decode_roofline.dsa"}
    over = {m["name"] for m in BENCH["per_layer"]
            if OVER in m.get("workloads", ())}
    under = {m["name"] for m in BENCH["per_layer"]
             if "gpt-1.3b-serve-open-08" in m.get("workloads", ())}
    assert over == {n for n in under if per_layer(n)["moves"] in (
        "serve_out_tokens_per_s", "setup_s")}
    assert {"open_queue_depth_at_close", "queue_wait_ms_p50",
            "decode_roofline"} <= over
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert cells[CELL]["traffic"] == "serve-resident-longctx-24"
    assert cells[OVER] == {
        "name": OVER, "config": "gpt-1.3b-bf16",
        "traffic": "serve-open-chat-40rps", "chips": 1,
        "why": cells[OVER]["why"]}
    assert all(len(cells[c]["why"]) <= 200 for c in (CELL, OVER))
    assert "8x" in cells[CELL]["why"]
    # at most a quarter of the cells takes four chips
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(BENCH["workloads"]) == 13


# ---------------------------------------------------------------------------
# operations and bytes from shapes
# ---------------------------------------------------------------------------
def test_counts_against_a_hand_count():
    assert afmoe_flops.attention_layer_params(3072, 48, 8, 128) \
        == 3072 * 6144 * 3 + 2 * 3072 * 1024 + 256 == 62914816
    assert afmoe_flops.kv_bytes_per_position(8, 128) == 4096
    # a step over 24 lanes at a mean context of 11k
    window = afmoe_flops.attention_step(24, 24 * 4096, **HEADS)
    full = afmoe_flops.attention_step(24, 24 * 11000, **HEADS)
    assert window["bytes"] == 24 * 4096 * 4096 + 3 * 24 * 6144 * 2
    assert window["flops"] == 4.0 * 24 * 4096 * 6144
    assert 1.08e9 < full["bytes"] < 1.09e9
    # both far under the ridge (240 operations a byte on the v5e)
    assert window["flops"] / window["bytes"] < 7
    ten = afmoe_flops.decode_weight_bytes(
        5, 1, 25024, 3072, 12288, 3072, experts_read=10, n_shared=1,
        n_routed=256, **HEADS)
    every = afmoe_flops.decode_weight_bytes(
        5, 1, 25024, 3072, 12288, 3072, experts_read=32, n_shared=1,
        n_routed=256, **HEADS)
    assert every - ten == 4 * 22 * 3 * 3072 * 3072 * 2
    assert 3.5e9 < ten < 3.6e9 and 8.4e9 < every < 8.5e9
    step = afmoe_flops.decode_step_bytes(ten, 24 * 4096, 24 * 11000, 4, 1,
                                         8, 128)
    assert step == ten + 4096 * (4 * 24 * 4096 + 24 * 11000)


def test_counts_agree_with_the_programs_parameter_tree():
    """Shapes only (``jax.eval_shape``): the real configuration's tree has
    the counted parameters, stack by stack, born bfloat16 but the routers
    and their biases, and its lane cache holds the counted bytes a kind."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer_lm import GPT

    model = GPT(afmoe_serve.model_config(BODY))
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 64), jnp.int32)))["params"]

    def count(tree):
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    def nbytes(tree):
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree.leaves(tree))

    h = shapes["h"]
    assert sorted(h) == ["attention", "window", "window_dense"]
    attn = afmoe_flops.attention_layer_params(3072, **HEADS)
    assert count(h["window"]["attn"]) == 3 * attn
    assert count(h["attention"]["attn"]) == count(
        h["window_dense"]["attn"]) == attn
    assert count(h["window_dense"]["mlp"]) \
        == mla_flops.gated_mlp_params(3072, 12288)
    assert h["window"]["mlp"]["experts"]["wi"].shape == (3, 32, 3072, 3072)
    assert h["window"]["mlp"]["gate"]["kernel"].shape == (3, 3072, 256)
    assert h["window"]["mlp"]["gate"]["kernel"].dtype == jnp.float32
    assert h["window"]["mlp"]["expert_bias"].dtype == jnp.float32
    assert h["window"]["mlp"]["experts"]["wi"].dtype == jnp.bfloat16
    assert shapes["lm_head"].shape == (3072, 25024)
    # the embedding's rows are read one a lane: not among a step's bytes
    assert nbytes(shapes) - nbytes(shapes["wte"]) \
        == afmoe_flops.decode_weight_bytes(
            5, 1, 25024, 3072, 12288, 3072, experts_read=32, n_shared=1,
            n_routed=256, **HEADS) \
        == BODY["bytes"]["parameter_bytes"] - 25024 * 3072 * 2
    assert count(shapes) == BODY["bytes"]["parameters"]


# ---------------------------------------------------------------------------
# the layer-metric files on a synthetic context
# ---------------------------------------------------------------------------
def _ctx(rows=None, modules=(), info=None, **system):
    from deepspeed_tpu.inference import engine

    name = engine.PROGRAM_DECODE_K
    mods = [types.SimpleNamespace(name=name + "(1)", start=a, end=b)
            for a, b in modules]
    red = tr.Reduced(devices={0: tr.Device(modules=mods)}, window=(0.0, 1e9))
    ctx = types.SimpleNamespace(
        red=red, notes={}, series={},
        env=types.SimpleNamespace(peak=PEAK["TPU v5 lite"]),
        system=types.SimpleNamespace(info=info or {}, **system))
    full = None if rows is None else [
        dict(program=name, instruction="i%d" % i, path=path, seconds=secs,
             count=1) for i, (path, secs) in enumerate(rows)]
    setattr(ctx, "_program_spans", ps.Program(
        red=red, spans=[], rows=full, scopes=ps.program_module()))
    return ctx


WINDOW = "jit(decode_k)/while/body/GPT/h/while/body/window/Block/"
FULL = "jit(decode_k)/while/body/GPT/h/while/body/attention/Block/"
ROWS = [(WINDOW + "attn/window_attn/decode_attn", 3.0),
        (WINDOW + "attn/window_attn/mul", 0.5),
        (FULL + "attn/full_attn/decode_attn", 1.5),
        (WINDOW + "attn/kv_cache_write/scatter", 0.5),
        (WINDOW + "mlp/moe_experts/ragged-dot-gmm", 3.5),
        ("jit(decode_k)/while/body/GPT/lm_head/dot", 1.0)]
INFO = {"slots": 24, "decode_program": "jit_decode_k",
        "experts_held": 32,
        "attention": {"heads": HEADS, "itemsize": 2, "window_layers": 4,
                      "full_layers": 1},
        "weights": dict(n_layers=5, n_dense=1, vocab=25024, hidden=3072,
                        dense_width=12288, expert_width=3072, n_shared=1,
                        n_routed=256, itemsize=2, **HEADS)}


@pytest.mark.parametrize("name,share", [
    ("window_attn_share_of_decode", 35.0),
    ("full_attn_share_of_decode", 15.0)])
def test_the_share_files_read_their_scopes_of_the_decode_program(name, share):
    reader, args = spec_of(name)
    assert args["program"] == DECODE
    assert reader.read(_ctx(ROWS), **args) == pytest.approx(share)
    assert reader.read(_ctx(None), **args) is None
    assert reader.read(_ctx([(ROWS[-1])]), **args) in (None, 0.0)


@pytest.mark.parametrize("name,kind,positions,layers", [
    ("window_attn_roofline", "window", 24 * 4096, 4),
    ("full_attn_roofline", "full", 24 * 11000, 1)])
def test_the_attention_rooflines_read_the_builders_late_counts(
        name, kind, positions, layers):
    """``scope_roofline`` over the counts the builder's ``info`` gives once
    the window has run: the kind's visible positions from the scheduler's
    clocks, one layer's bytes, times the kind's layers."""
    reader, args = spec_of(name)
    assert args["scope"] == kind + "_attn" and args["program"] == DECODE
    counts = dict(afmoe_flops.attention_step(24, positions, **HEADS),
                  calls_per_step=layers)
    ctx = _ctx(ROWS, modules=[(0, 10)],
               info=dict(INFO, **{args["counts"]: counts}))
    got = reader.read(ctx, **args)
    least = counts["bytes"] / (PEAK["TPU v5 lite"]["hbm_gb_per_s"] * 1e9)
    actual = {"window": 3.5, "full": 1.5}[kind]
    assert got == pytest.approx(100 * least * layers / actual)
    assert reader.read(_ctx(ROWS, modules=[(0, 10)], info=INFO),
                       **args) is None        # no counts yet: says nothing


def test_the_builders_info_gains_the_attention_counts_after_the_window():
    env = types.SimpleNamespace(t_open=10.0, t_close=20.0, config=BODY,
                                seed=1)
    system = afmoe_serve.WindowServeSystem(env, None, None, None)
    system.info = INFO
    assert "window_attention_step" not in system.info
    for t, live, seen in ((9.0, 5, 5), (12.0, 24 * 10000, 24 * 4096),
                          (18.0, 24 * 12000, 24 * 4096), (21.0, 7, 7)):
        system.on_bus({"kind": "serve.stats", "live_positions": live,
                       "live_window_positions": seen})
        system.live_positions[-1] = (t, live)
        system.live_window_positions[-1] = (t, seen)
    info = system.info
    assert info["full_attention_step"] == dict(
        afmoe_flops.attention_step(24, 24 * 11000, **HEADS),
        calls_per_step=1)
    assert info["window_attention_step"] == dict(
        afmoe_flops.attention_step(24, 24 * 4096, **HEADS),
        calls_per_step=4)


def test_the_step_roofline_counts_weights_rows_and_the_experts_that_got_one():
    reader, args = spec_of("decode_roofline.swa")
    system = dict(mean_live_window_positions=lambda: 24 * 4096,
                  mean_live_positions=lambda: 24 * 11000,
                  step_expert_load=lambda: {
                      "experts_with_rows_share": 10 / 32})
    ctx = _ctx(ROWS, modules=[(0, 12e6), (20e6, 32e6)], info=INFO, **system)
    weights = afmoe_flops.decode_weight_bytes(experts_read=10.0,
                                              **INFO["weights"])
    nbytes = afmoe_flops.decode_step_bytes(weights, 24 * 4096, 24 * 11000,
                                           4, 1, 8, 128)
    least_ms = nbytes / (PEAK["TPU v5 lite"]["hbm_gb_per_s"] * 1e9) * 1e3
    assert reader.read(ctx, **args) == pytest.approx(100 * least_ms / 12.0)
    assert ctx.notes["decode_roofline_window"]["bytes"] == nbytes
    # a program from before the counter, or a run without live positions
    old = dict(system, step_expert_load=lambda: {"max_over_mean": 2.0})
    assert reader.read(_ctx(ROWS, modules=[(0, 12e6)], info=INFO, **old),
                       **args) is None
    assert reader.read(_ctx(ROWS, modules=[(0, 12e6)], info=INFO),
                       **args) is None


def test_the_counter_files_read_the_programs_events():
    reader, args = spec_of("window_share_of_lane_cache")
    plan = {"window_bytes_per_lane": 71303168, "bytes_per_lane": 172078100}
    assert 41 < reader.read(_ctx(cache_plan=plan), **args) < 42
    assert reader.read(_ctx(cache_plan=None), **args) is None
    reader, args = spec_of("moe_experts_with_rows_share")
    assert reader.read(_ctx(step_expert_load=lambda: {
        "experts_with_rows_share": 0.3125}), **args) == 31.25
    assert reader.read(_ctx(step_expert_load=lambda: {}), **args) is None
    assert reader.read(_ctx(), **args) is None


# ---------------------------------------------------------------------------
# the cell through the harness, and the kind's verdict
# ---------------------------------------------------------------------------
def test_the_stand_in_is_registered_for_any_subset_of_the_tests():
    assert rehearsal.CONFIGS[TINY_TRINITY["name"]] is TINY_TRINITY
    assert rehearsal.TRAFFIC[TINY_CELL["traffic"]] is TINY_TRAFFIC
    assert TINY_CELL in rehearsal.CELLS
    assert rehearsal.STAND_IN[CELL] == TINY_CELL["name"]
    assert STAND_IN[OVER] == rehearsal.STAND_IN["gpt-1.3b-serve-open-08"]
    # every published key of the real file is in the tiny one
    published = set(BODY) - {"assumed", "deployment", "published", "bytes"}
    assert published <= set(TINY_TRINITY), published - set(TINY_TRINITY)
    # and the tiny one keeps the shape of the thing
    assert sorted(TINY_TRINITY["layer_types"]) == sorted(BODY["layer_types"])
    assert TINY_TRINITY["num_dense_layers"] == BODY["num_dense_layers"]
    assert set(TINY_TRAFFIC) == set(TRAFFIC) - {"why", "grid"}


def test_the_new_kind_is_the_resident_kind_but_for_its_check():
    for name in ("ROLE", "plan", "warm_up", "drive", "series",
                 "end_to_end"):
        assert getattr(serve_resident_decoded, name) \
            is getattr(serve_resident, name), name
    assert serve_resident_decoded.check is not serve_resident.check


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(tmp_path, trace):
    root = rehearsal.make_root(tmp_path)
    rc, last, err = rehearsal.run_cell(root, TINY_CELL["name"], trace=trace,
                                       seed=2 ** 31 + 39, seconds=1.5)
    assert rc == 0, err[-2000:]
    assert last["correct"] is True and last["rehearsal"] is True
    assert last["failed"] == 0 and last["attempted"] > 0
    if not trace:
        assert set(last["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}
    else:
        # no device plane on the CPU: the trace's readers find nothing and
        # say nothing; the program's events are read
        assert last["metrics"]["compiles_in_window.serve"]["value"] == 0
        assert 40 < last["metrics"]["window_share_of_lane_cache"][
            "value"] < 43
        assert 0 < last["metrics"]["moe_experts_with_rows_share"][
            "value"] <= 100
        assert not {"window_attn_share_of_decode", "full_attn_roofline",
                    "decode_roofline.swa"} & set(last["metrics"])


def serve_until(system, prompts, want, polls):
    """Serve ``prompts`` on the system's scheduler until ``polls`` loop
    iterations have passed; ``(record, env times)`` as the kind keeps
    them."""
    import time

    sched = system.scheduler
    done, by_rid = [], {}

    class Stop(Exception):
        pass

    def poll(state={"n": 0}):
        state["n"] += 1
        if state["n"] > polls:
            raise Stop

    t0 = time.monotonic()
    for prompt, n in zip(prompts, want):
        req = types.SimpleNamespace(prompt=list(prompt), want=n, tokens=[],
                                    times=[])
        rid = sched.submit(
            req.prompt, max_new_tokens=n,
            stream_callback=lambda r, t, d: (
                by_rid[r].tokens.append(int(t)),
                by_rid[r].times.append(time.monotonic()),
                d and done.append(by_rid[r])))
        by_rid[rid] = req
    try:
        sched.run(poll_fn=poll)
    except Stop:
        pass
    in_flight = [r for r in by_rid.values() if r not in done]
    return {"done": done, "by_rid": by_rid, "in_flight": in_flight}, \
        (t0, time.monotonic())


def tiny_system(seed=7):
    import jax

    env = types.SimpleNamespace(
        config=copy.deepcopy(TINY_TRINITY), seed=seed,
        traffic=dict(TINY_TRAFFIC, reference_samples=2))
    with jax.default_matmul_precision("highest"):
        system = afmoe_serve.build(env, None)
        system.scheduler._ensure_compiled()
    return env, system


@pytest.mark.parametrize("fault", ["none", "token", "full_row", "ring_row",
                                   "ring_position"])
def test_check_fails_a_swapped_token_and_a_perturbed_row_of_either_kind(
        fault):
    """The kind's ``check`` on the tiny system: correct as served; a served
    token swapped for another, the full layer's stored keys moved by 1%, a
    window layer's ring moved by 1%, or a ring that says it holds other
    positions than it does, and it is not."""
    import jax
    import jax.numpy as jnp

    env, system = tiny_system()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 128, size=n).tolist()
               for n in (5, 9, 20, 23)]
    with jax.default_matmul_precision("highest"):
        record, (env.t_open, env.t_close) = serve_until(
            system, prompts, [36, 36, 36, 36], polls=24)
        assert len(record["in_flight"]) == 4
        if fault == "token":
            for victim in record["in_flight"]:
                victim.tokens[1] = (victim.tokens[1] + 1) % 128
        kept = system.scheduler.lanes_at_exit
        if fault == "token":
            # the lanes took the served tokens in; the record now differs
            for comp in kept.live.values():
                comp.tokens[1] = (comp.tokens[1] + 1) % 128
        leaves = kept.cache["h"]
        if fault == "full_row":
            leaf = leaves["attention"]["attn"]
            leaf["cached_key"] = leaf["cached_key"] * jnp.float32(1.01)
        if fault == "ring_row":
            leaf = leaves["window"]["attn"]
            leaf["cached_value"] = leaf["cached_value"] * jnp.float32(1.01)
        if fault == "ring_position":
            leaf = leaves["window"]["attn"]
            leaf["slot_pos"] = jnp.where(leaf["slot_pos"] >= 0,
                                         leaf["slot_pos"] - 1, -1)
        plan = types.SimpleNamespace(vocab=128)
        if fault == "ring_position":
            with pytest.raises(ValueError, match="ring does not hold"):
                serve_resident_decoded.check(env, system, plan, record)
            return
        verdict = serve_resident_decoded.check(env, system, plan, record)
    assert verdict["correct"] is (fault == "none"), verdict["decode"]
    assert verdict["live_lanes_streamed_their_tokens"]
    decode = verdict["decode"]
    if fault == "token":
        assert decode["largest_margin"] > decode["limits"][
            "largest_margin_max"]
    if fault == "full_row":
        assert decode["mean_state_error"] > decode["limits"][
            "mean_state_error_max"]
        assert decode["mean_tail_error"] < 1e-4
    if fault == "ring_row":
        assert decode["mean_tail_error"] > decode["limits"][
            "mean_tail_error_max"]
        assert decode["mean_state_error"] < 1e-4
    if fault == "none":
        assert len(decode["state_error_by_layer"]) == 1      # full
        assert len(decode["tail_error_by_layer"]) == 4       # window
        assert len(decode["first_layer_state_error_by_head"]) == 2
        assert decode["positions"] > 2 * 12     # the rings have wrapped
