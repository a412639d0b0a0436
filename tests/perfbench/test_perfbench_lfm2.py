"""The mixed-kinds serve cell's benchmark files: its configuration against
the catalog row, ``lfm2_flops.py`` against a hand count and the program's
parameter tree, the three new layer-metric files and the accepted ones the
cell lists on a synthetic context, the tiny cell through the harness, and
the kind's ``check`` against a swapped token, a perturbed key and a
perturbed tail. Every entry of ``BENCHMARK.json`` is found BY NAME: nothing
here says where in a list an entry stands or how long a list is, so the
next appended cell breaks none of it."""
import copy
import json
import os
import types

import numpy as np
import pytest

import rehearsal
from brumby_tiny import TINY_CLOSED_DECODED
from lfm2_tiny import STAND_IN, TINY_CELL, TINY_LFM2
from perfbench import flops, lfm2_flops, mla_flops, stats
from perfbench import program_spans as ps
from perfbench import trace_reduce as tr
from perfbench.builders import lfm2_serve
from perfbench.readers import (
    cache_plan,
    decode_roofline_state,
    expert_load,
    scope_roofline,
    scope_share,
)
from perfbench.traffic_kinds import serve_closed_decoded

ROOT = stats.repo_root()
BENCH = stats.load_json(os.path.join(ROOT, "BENCHMARK.json"))
PEAK = stats.load_json(os.path.join(ROOT, "perfbench", "peaks.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
(CELL,) = STAND_IN
CONFIG = "lfm2-8b-a1b-12layer"
ENTRY = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
BODY = stats.load_json(os.path.join(ROOT, ENTRY["file"]))
TRAFFIC = stats.load_json(os.path.join(
    ROOT, "perfbench", "traffic", "serve-closed-code-long-256.json"))
NEW_METRICS = ["conv_share_of_decode", "conv_share_of_prefill",
               "moe_bias_changed_share"]
# the accepted per-layer metrics that list the cell: those that move
# ``serve_out_tokens_per_s`` or ``setup_s`` and whose reader finds
# something to read here
ACCEPTED = ["compiles_in_window.serve", "sched_lane_occupancy",
            "ttft_p95_ms.closed", "decode_step_ms_p50",
            "device_idle_share.serve", "hbm_peak_gb.serve",
            "idle_share.admit", "idle_share.step_host", "queue_wait_ms_p50",
            "decode_ahead_share", "scope_unattributed_share.serve",
            "setup_trace_s", "setup_lower_s", "setup_compile_or_load_s",
            "setup_programs_built", "setup_cache_misses",
            "setup_first_dispatch_s.serve", "kv_cache_share_of_decode",
            "kv_blocks_read_share", "state_share_of_lane_cache",
            "moe_share_of_decode", "moe_experts_roofline.decode",
            "moe_expert_load_max_over_mean.serve", "decode_roofline.ssm"]
DECODE = ["deepspeed_tpu.inference.engine", "PROGRAM_DECODE_K"]
PREFILL = ["deepspeed_tpu.inference.engine", "PROGRAM_PREFILL"]
SIZES = lfm2_serve.layer_sizes(BODY)
KINDS = [lfm2_serve.KINDS[k] for k in BODY["layer_types"]]
READERS = {"scope_share": scope_share, "scope_roofline": scope_roofline,
           "decode_roofline_state": decode_roofline_state,
           "cache_plan": cache_plan, "expert_load": expert_load}


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
        if key not in ("num_hidden_layers", "layer_types"):
            assert key in BODY and BODY[key] == value, key
    # the cut: the first 12 of the 24 published layers, kinds and all
    assert BODY["num_hidden_layers"] == 12 < row["config"][
        "num_hidden_layers"] == BODY["published"]["num_hidden_layers"]
    assert BODY["layer_types"] == row["config"]["layer_types"][:12]
    assert BODY["published"]["layer_types"] == row["config"]["layer_types"]
    assert "".join(k[0] for k in BODY["layer_types"]) == "ccfcccfcccfc"
    assert BODY["num_dense_layers"] == 2 and BODY["num_experts"] == 32
    assert "mixture of experts" in row["mechanisms"]


def test_reduced_is_exactly_what_differs_from_the_catalog():
    """What ``test_configuration_entry_and_file`` checks, for a
    configuration that is cut (that test holds every configuration to
    ``reduced == []``): entry and file agree, ``reduced`` names a key of
    the file and no width, the file says what it assumed and which
    deployment it stands for, one cell runs it, its builder exists; and,
    where the catalog has the row, the depth and the kinds of the layers
    kept are all that differ."""
    import importlib

    assert BODY["name"] == ENTRY["name"] and BODY["source"] == ENTRY["source"]
    assert BODY["reduced"] == ENTRY["reduced"] == ["num_hidden_layers"]
    assert set(ENTRY) == {"name", "source", "file", "reduced", "why"}
    assert 1 <= len(ENTRY["why"]) <= 200 and len(ENTRY["source"]) <= 200
    for role, builder in BODY["builders"].items():
        mod = importlib.import_module("perfbench.builders." + builder)
        assert callable(mod.build), (role, builder)
    assert set(BODY["assumed"]) >= {
        "tie_word_embeddings", "router", "expert_bias", "qk_norm", "rotary",
        "short_conv", "conv_cache", "decoding", "weights", "prompt_bucket",
        "cache_positions"}
    assert all(len(why) > 10 for why in BODY["assumed"].values())
    assert "two stages of 12 layers" in BODY["deployment"]
    assert "WHOLE" in BODY["deployment"]
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == CONFIG] == [CELL]
    if os.path.exists(CATALOG):
        row = catalog_row()
        others = [k for k, v in row["config"].items()
                  if k not in ("num_hidden_layers", "layer_types")
                  and BODY.get(k, "missing") != v]
        assert others == []


def test_the_file_reckons_its_own_bytes():
    reckoned = BODY["bytes"]
    weights = lfm2_flops.decode_weight_bytes(
        KINDS, BODY["num_dense_layers"], BODY["vocab_size"],
        dense_width=BODY["intermediate_size"],
        expert_width=BODY["moe_intermediate_size"],
        n_experts=BODY["num_experts"], **SIZES)
    assert weights / 1e9 == pytest.approx(reckoned["parameters_gb"],
                                          abs=0.005)
    assert sum(v for k, v in reckoned["parameters_m"].items()
               if k != "total") == pytest.approx(
                   reckoned["parameters_m"]["total"], abs=0.1)
    per_position = lfm2_flops.kv_bytes_per_position(
        KINDS.count("attention"), SIZES["n_kv_heads"], SIZES["head_dim"])
    assert per_position == reckoned["kv_bytes_per_position"] == 6144
    serve = BODY["serve"]
    assert serve["serving"]["slots"] * serve["cache_positions"] \
        * per_position / 1e9 == pytest.approx(reckoned["lane_cache_gb"],
                                              abs=0.005)
    assert serve["serving"]["slots"] * lfm2_flops.conv_tail_bytes(
        KINDS.count("conv"), SIZES["hidden"], SIZES["taps"]) / 1e6 \
        == pytest.approx(reckoned["conv_tails_mb"], abs=0.05)
    # above the driver's floor of a quarter of the chip, with room
    total = reckoned["parameters_gb"] + reckoned["lane_cache_gb"]
    assert 0.7 < total / PEAK["TPU v5 lite"]["hbm_gb"] < 0.85


def test_serve_section_states_the_cache_and_the_limits():
    serve = BODY["serve"]
    assert serve["cache_positions"] == 2944 < BODY["max_position_embeddings"]
    assert serve["cache_positions"] == TRAFFIC["max_positions"]
    assert serve["serving"]["slots"] == TRAFFIC["clients"]
    assert serve["dtype"] == "bf16"
    check = serve["decode_check"]
    upper = ["mean_margin", "largest_margin", "mean_state_error",
             "first_layer_head_state_error", "mean_tail_error"]
    assert set(check) >= {s + "_max" for s in upper} | {
        "share_within_tolerance_min", "live_lanes", "why",
        "system_readings", "lower_precision_readings"}
    assert 0 < check["mean_margin_max"] < check["largest_margin_max"]
    assert 0.5 < check["share_within_tolerance_min"] < 1.0
    # every limit has room above the largest reading the system gave over
    # at least twenty seeds: no limit within a spread of the readings ...
    sys_, low = check["system_readings"], check["lower_precision_readings"]
    assert sys_["runs"] >= 20
    for stat in upper:
        spread = sys_[stat + "_largest"] - sys_[stat + "_smallest"]
        assert sys_[stat + "_largest"] + spread < check[stat + "_max"], stat
        assert sys_[stat + "_largest"] * 1.05 < check[stat + "_max"], stat
    assert sys_["share_within_tolerance_smallest"] \
        > check["share_within_tolerance_min"]
    assert sys_["first_token_margin_largest"] * 1.5 \
        < serve["first_token_tolerance"]
    # ... and 8-bit weights, the nearest precision below the one the
    # configuration states, are refused by at least one of them in both
    # their runs. A bfloat16 router is told apart by none (every reading
    # inside the system's own range): the file says so, with the reason,
    # and no limit was narrowed into the system's spread to make it so
    assert set(low) == {"bf16_router", "int8_weights"}
    for name, read in low.items():
        assert read["runs"] >= 2
        outside = [s for s in upper
                   if read[s + "_smallest"] > check[s + "_max"]]
        if read["share_within_tolerance_largest"] \
                < check["share_within_tolerance_min"]:
            outside.append("share_within_tolerance")
        assert bool(outside) is read["told_apart"], (name, outside)
    assert len(low["bf16_router"]["why_not"]) > 100
    for stat in upper:
        assert sys_[stat + "_smallest"] * 0.95 \
            < low["bf16_router"][stat + "_smallest"] \
            and low["bf16_router"][stat + "_largest"] \
            < sys_[stat + "_largest"] * 1.05, stat


def test_the_traffic_file_is_the_one_the_latent_cell_runs():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["traffic"] == "serve-closed-code-long-256"
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell["config"] == CONFIG
    sibling = next(w for w in BENCH["workloads"]
                   if w["name"] == "deepseek-v2-serve-closed-256")
    assert sibling["traffic"] == cell["traffic"]
    assert TRAFFIC["kind"] == "serve_closed_decoded"
    assert (TRAFFIC["clients"], TRAFFIC["max_positions"],
            TRAFFIC["prompt_bucket"]) == (256, 2944, 64)


def test_the_entries_list_the_cell_by_name():
    """The cell reports ``serve_out_tokens_per_s`` and ``setup_s`` (not
    ``gap_p95_ms``: at 256 lanes a gap's tail is the admissions'); the
    three new metrics list it alone; each accepted metric it lists moves
    one of its two end-to-end metrics and has its file."""
    ends = {m["name"]: m for m in BENCH["end_to_end"]}
    assert CELL in ends["serve_out_tokens_per_s"]["workloads"]
    assert CELL not in ends["gap_p95_ms"]["workloads"]
    assert "workloads" not in ends["setup_s"]
    for name in NEW_METRICS:
        metric = per_layer(name)
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "serve_out_tokens_per_s"
        assert set(metric) == {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
    assert per_layer("conv_share_of_decode")["layer"] == "decode step"
    assert per_layer("conv_share_of_decode")["source"] == "device_trace"
    assert per_layer("moe_bias_changed_share")["source"] == "program_counter"
    listing = {m["name"] for m in BENCH["per_layer"]
               if CELL in m.get("workloads", ())}
    assert listing == set(ACCEPTED) | set(NEW_METRICS)
    for name in listing:
        assert per_layer(name)["moves"] in ("serve_out_tokens_per_s",
                                            "setup_s")
        assert os.path.exists(os.path.join(
            ROOT, "perfbench", "layer_metrics", name + ".json")), name
    # a share of the whole decode step of its roofline, and the experts'
    assert {"decode_roofline.ssm", "moe_experts_roofline.decode"} <= listing
    # at most a quarter of the cells takes four chips
    assert 4 * sum(w["chips"] == 4 for w in BENCH["workloads"]) \
        <= max(4, len(BENCH["workloads"]))


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------
def test_counts_against_a_hand_count():
    # in_proj 2048 x 6144, out_proj 2048 x 2048
    assert lfm2_flops.conv_layer_params(2048) == 12_582_912 + 4_194_304 \
        == 16_777_216
    # q 2048 x 2048, k and v 2048 x 512 each, o 2048 x 2048, two norms
    assert lfm2_flops.attention_layer_params(2048, 32, 8, 64) \
        == 4_194_304 + 2 * 1_048_576 + 4_194_304 + 128 == 10_485_888
    assert mla_flops.gated_mlp_params(2048, 7168) == 44_040_192
    assert mla_flops.gated_mlp_params(2048, 1792) == 11_010_048
    weights = lfm2_flops.decode_weight_bytes(
        KINDS, 2, 65536, 2048, 7168, 1792, 32, 3, 32, 8, 64)
    assert weights == 2 * (
        65536 * 2048 + 2048 + 12 * 2 * 2048
        + 9 * (16_777_216 + 3 * 2048) + 3 * 10_485_888
        + 2 * 44_040_192 + 10 * 32 * 11_010_048) \
        + 4 * 10 * (2048 * 32 + 32)
    assert weights == pytest.approx(7.86e9, rel=1e-3)
    # the experts' matrices are nine tenths of what a step reads
    assert 2 * 10 * 32 * 11_010_048 / weights == pytest.approx(0.897,
                                                               abs=0.002)
    assert lfm2_flops.kv_bytes_per_position(3, 8, 64) == 6144
    assert lfm2_flops.kv_bytes_per_position(12, 8, 64) == 4 * 6144
    assert 256 * 2944 * 6144 == pytest.approx(4.63e9, rel=1e-3)
    assert lfm2_flops.conv_tail_bytes(9, 2048, 3) == 73_728
    experts = lfm2_flops.experts_step(1024, 2048, 1792, 32)
    assert experts == mla_flops.held_experts_step(1024, 2048, 1792, 32)
    assert experts["flops"] == 6 * 1024 * 2048 * 1792
    assert experts["bytes"] == pytest.approx(0.7046e9 + 1024 * 11520 * 2,
                                             rel=2e-3)
    # 32 rows an expert: far under the ridge, bound by the matrices
    assert flops.roofline_seconds(experts["flops"], experts["bytes"],
                                  PEAK["TPU v5 lite"])[1] == "memory"


def test_counts_agree_with_the_programs_parameter_tree():
    """Shapes only (``jax.eval_shape``): the real configuration's tree has
    the counted parameters, stack by stack, born bfloat16 but the routers
    and their biases, and its lane cache holds the counted bytes a kind."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer_lm import GPT

    model = GPT(lfm2_serve.model_config(BODY))
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 64), jnp.int32)))["params"]

    def count(tree):
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    def nbytes(tree):
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree.leaves(tree))

    h = shapes["h"]
    assert sorted(h) == ["attention", "conv", "conv_dense"]
    assert count(h["conv_dense"]["conv"]) == 2 * (
        lfm2_flops.conv_layer_params(2048) + 3 * 2048)
    assert count(h["conv"]["conv"]) == 7 * (
        lfm2_flops.conv_layer_params(2048) + 3 * 2048)
    assert count(h["attention"]["attn"]) \
        == 3 * lfm2_flops.attention_layer_params(2048, 32, 8, 64)
    assert count(h["conv_dense"]["mlp"]) \
        == 2 * mla_flops.gated_mlp_params(2048, 7168)
    assert h["conv"]["mlp"]["experts"]["wi"].shape == (7, 32, 2048, 1792)
    assert h["attention"]["mlp"]["experts"]["wo"].shape \
        == (3, 32, 1792, 2048)
    assert h["conv"]["mlp"]["gate"]["kernel"].shape == (7, 2048, 32)
    assert h["conv"]["mlp"]["gate"]["kernel"].dtype == jnp.float32
    assert h["conv"]["mlp"]["expert_bias"].dtype == jnp.float32
    assert h["conv"]["mlp"]["experts"]["wi"].dtype == jnp.bfloat16
    assert "lm_head" not in shapes      # tied
    # a step reads every parameter (the tied embedding is the head)
    assert nbytes(shapes) == lfm2_flops.decode_weight_bytes(
        KINDS, 2, 65536, dense_width=7168, expert_width=1792, n_experts=32,
        **SIZES)
    cache = jax.eval_shape(
        lambda p: model.apply({"params": p}, jnp.zeros((256, 1), jnp.int32),
                              deterministic=True, decode=True,
                              mutable=["cache"])[1]["cache"], shapes)["h"]
    # two KV heads of 64 side by side in a row of 128 lanes
    assert cache["attention"]["attn"]["cached_key"].shape \
        == (3, 256, 2944, 4, 128)
    assert cache["conv"]["conv"]["conv_tail"].shape == (9, 256, 2, 2048)
    assert nbytes([cache["attention"]["attn"]["cached_key"],
                   cache["attention"]["attn"]["cached_value"]]) \
        == 256 * 2944 * lfm2_flops.kv_bytes_per_position(3, 8, 64)
    assert nbytes(cache["conv"]) \
        == 256 * lfm2_flops.conv_tail_bytes(9, 2048, 3)


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


CONV = "jit(decode_k)/while/body/GPT/h/while/body/conv/Block/"
ATTN = "jit(decode_k)/while/body/GPT/h/attention/Block/"
ROWS = [(CONV + "conv/conv_in_proj/in_proj/dot", 1.0),
        (CONV + "conv/conv_gate_conv/mul", 0.25),
        (CONV + "conv/conv_out_proj/out_proj/dot", 0.75),
        (ATTN + "attn/attn_core/decode_attn", 1.0),
        (ATTN + "attn/kv_cache_write/scatter", 0.5),
        (CONV + "mlp/moe_router/gate/dot", 0.5),
        (CONV + "mlp/moe_dispatch/sort", 0.5),
        (CONV + "mlp/moe_experts/ragged-dot-gmm", 12.0),
        (CONV + "mlp/moe_combine/mul", 1.0),
        ("jit(decode_k)/while/body/GPT/lm_head/dot", 2.5)]
INFO = {"slots": 256, "decode_program": "jit_decode_k",
        "weight_bytes": 7.86e9, "kv_bytes_per_position": 6144.0,
        "state_layers": 9, "state_bytes_per_lane": 73728.0,
        "held_experts_step": dict(lfm2_flops.experts_step(
            1024, 2048, 1792, 32), calls_per_step=10)}


@pytest.mark.parametrize("name,share", [
    ("conv_share_of_decode", 2.0), ("moe_share_of_decode", 14.0),
    ("kv_cache_share_of_decode", 0.5)])
def test_the_share_files_read_their_scopes_of_the_decode_program(name, share):
    reader, args = spec_of(name)
    assert args["program"] == DECODE
    ctx = _ctx(rows=ROWS)
    assert reader.read(ctx, **args) == pytest.approx(100 * share / 20.0)
    # a program without the scopes (the parent's) reads nothing of them,
    # and a trace without a scope table nothing at all: nothing raised
    plain = [r for r in ROWS if "conv_" not in r[0] and "moe_" not in r[0]
             and "kv_cache" not in r[0]]
    assert not reader.read(_ctx(rows=plain), **args)
    assert reader.read(_ctx(rows=None), **args) is None


def test_the_three_convolution_scopes_are_logged_apart():
    reader, args = spec_of("conv_share_of_decode")
    assert args["scopes"] == ["conv_in_proj", "conv_gate_conv",
                              "conv_out_proj"]
    ctx = _ctx(rows=ROWS)
    reader.read(ctx, **args)
    assert set(ctx.notes["scope_share:" + "+".join(args["scopes"])]) \
        == set(args["scopes"])


def test_the_prefill_share_reads_the_prefill_programs_alone():
    from deepspeed_tpu.inference import engine

    reader, args = spec_of("conv_share_of_prefill")
    assert args["program"] == PREFILL
    assert reader.read(_ctx(rows=ROWS), **args) is None
    rows = [(p.replace("decode_k", "prefill"), s) for p, s in ROWS]
    assert reader.read(_ctx(rows=rows, program=engine.PROGRAM_PREFILL),
                       **args) == pytest.approx(100 * 2.0 / 20.0)


def test_the_experts_roofline_reads_all_the_matrices_once_a_layer():
    reader, args = spec_of("moe_experts_roofline.decode")
    counts = INFO["held_experts_step"]
    least = counts["bytes"] / 819e9            # 0.89 ms a layer
    rows = [(CONV + "mlp/moe_experts/ragged-dot-gmm", 2 * 10 * least / 0.8)]
    ctx = _ctx(rows=rows, modules=[(0, 26e6), (27e6, 53e6)], info=INFO)
    assert reader.read(ctx, **args) == pytest.approx(80.0)
    assert ctx.notes["scope_roofline:moe_experts"]["bound"] == "memory"
    assert ctx.notes["scope_roofline:moe_experts"]["calls"] == 20


def test_the_step_roofline_counts_weights_live_rows_and_tails():
    """``decode_roofline.ssm``'s reader on this cell's ``info``: the
    weights once, 6,144 bytes a live position (three layers of keys and
    values), the nine tails read and written a live lane."""
    reader, args = spec_of("decode_roofline.ssm")
    steps = [(i * 27e6, i * 27e6 + 15e6) for i in range(5)]
    ctx = _ctx(modules=steps, info=INFO,
               series={"lanes_active": [256], "live_positions": [700.0]})
    least = (7.86e9 + 256 * (700 * 6144 + 2 * 73728)) / 819e9 * 1e3
    assert reader.read(ctx, **args) == pytest.approx(100 * least / 15.0)
    note = ctx.notes["decode_roofline_state"]
    assert note["state_bytes_moved"] == 2 * 73728 * 256
    # nothing to read, and nothing raised, without the live positions
    assert reader.read(_ctx(modules=steps, info=INFO), **args) is None


def test_the_counter_files_read_the_programs_events():
    reader, args = spec_of("state_share_of_lane_cache")
    plan = {"kind": "serve.cache_plan", "slots": 256,
            "state_bytes_per_lane": 0, "conv_bytes_per_lane": 73728,
            "kv_bytes_per_lane": 2944 * 6144 + 3 * 2948,
            "bytes_per_lane": 2944 * 6144 + 3 * 2948 + 73728,
            "leaf_layers": {"cached_key": 3, "cached_value": 3,
                            "conv_tail": 9}}
    assert reader.read(_ctx(cache_plan=plan), **args) == pytest.approx(
        100 * 73728 / plan["bytes_per_lane"])
    assert reader.read(_ctx(cache_plan=plan), **args) < 1.0
    assert reader.read(_ctx(cache_plan=None), **args) is None
    reader, args = spec_of("moe_bias_changed_share")
    assert args == {"field": "bias_changed_share"}
    load = {"max_over_mean": 1.4, "bias_changed_share": 0.31}
    assert reader.read(_ctx(expert_load=lambda: load), **args) == 0.31
    # the parent's event has no such field, or no event at all: nothing
    # to read, nothing raised
    assert reader.read(_ctx(expert_load=lambda: {"max_over_mean": 1.4}),
                       **args) is None
    assert reader.read(_ctx(), **args) is None


# ---------------------------------------------------------------------------
# the cell through the harness, and the kind's verdict
# ---------------------------------------------------------------------------
def test_the_stand_in_is_registered_for_any_subset_of_the_tests():
    assert rehearsal.CONFIGS[TINY_LFM2["name"]] is TINY_LFM2
    assert rehearsal.TRAFFIC[TINY_CELL["traffic"]] is TINY_CLOSED_DECODED
    assert TINY_CELL in rehearsal.CELLS
    assert rehearsal.STAND_IN[CELL] == TINY_CELL["name"]
    # every published key of the real file is in the tiny one
    published = set(BODY) - {"assumed", "deployment", "published", "bytes"}
    assert published <= set(TINY_LFM2), published - set(TINY_LFM2)
    # and the tiny one keeps the shape of the thing
    assert TINY_LFM2["layer_types"] == BODY["layer_types"][:8]
    assert TINY_LFM2["num_dense_layers"] == BODY["num_dense_layers"]
    assert TINY_LFM2["num_experts_per_tok"] == BODY["num_experts_per_tok"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(tmp_path, trace):
    root = rehearsal.make_root(tmp_path)
    rc, last, err = rehearsal.run_cell(root, TINY_CELL["name"], trace=trace,
                                       seed=2 ** 31 + 39, seconds=1.5)
    assert rc == 0, err[-2000:]
    assert last["correct"] is True and last["rehearsal"] is True
    assert last["failed"] == 0 and last["attempted"] > 0
    assert set(last["metrics"]) >= {"serve_out_tokens_per_s", "setup_s"} \
        if not trace else True
    if trace:
        # no device plane on the CPU: the trace's readers find nothing and
        # say nothing; the program's events are read
        assert last["metrics"]["compiles_in_window.serve"]["value"] == 0
        assert 0 < last["metrics"]["state_share_of_lane_cache"]["value"] < 20
        assert last["metrics"]["moe_expert_load_max_over_mean.serve"][
            "value"] >= 1.0
        assert 0 < last["metrics"]["moe_bias_changed_share"]["value"] < 1
        assert not {"conv_share_of_decode", "conv_share_of_prefill"} \
            & set(last["metrics"])


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
        config=copy.deepcopy(TINY_LFM2), seed=seed,
        traffic=dict(TINY_CLOSED_DECODED, reference_samples=2))
    with jax.default_matmul_precision("highest"):
        system = lfm2_serve.build(env, None)
        system.scheduler._ensure_compiled()
    return env, system


@pytest.mark.parametrize("fault", ["none", "token", "key", "tail"])
def test_check_fails_a_swapped_token_a_perturbed_key_and_a_perturbed_tail(
        fault):
    """The kind's ``check`` on the tiny system: correct as served; a
    served token swapped for another, a lane's stored keys moved by 1%, or
    a convolution layer's tail moved by 1%, and it is not."""
    import jax
    import jax.numpy as jnp

    env, system = tiny_system()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 128, size=n).tolist()
               for n in (5, 9, 20, 30, 7, 12)]
    with jax.default_matmul_precision("highest"):
        record, (env.t_open, env.t_close) = serve_until(
            system, prompts, [4, 5, 6, 3, 40, 40], polls=30)
        assert record["done"] and record["in_flight"]
        if fault == "token":
            victim = record["done"][0]
            victim.tokens[1] = (victim.tokens[1] + 1) % 128
            env.traffic["reference_samples"] = len(record["done"])
        kept = system.scheduler.lanes_at_exit
        if fault in ("key", "tail"):
            path = {"key": ("attention", "attn", "cached_key"),
                    "tail": ("conv", "conv", "conv_tail")}[fault]
            leaf = kept.cache["h"]
            for name in path[:-1]:
                leaf = leaf[name]
            leaf[path[-1]] = leaf[path[-1]] * jnp.asarray(1.01,
                                                          leaf[path[-1]].dtype)
        plan = types.SimpleNamespace(vocab=128)
        verdict = serve_closed_decoded.check(env, system, plan, record)
    assert verdict["correct"] is (fault == "none"), verdict["decode"]
    assert verdict["live_lanes_streamed_their_tokens"]
    decode = verdict["decode"]
    if fault == "key":
        assert decode["mean_state_error"] > decode["limits"][
            "mean_state_error_max"]
        assert decode["mean_tail_error"] < 1e-4
    if fault == "tail":
        assert decode["mean_tail_error"] > decode["limits"][
            "mean_tail_error_max"]
        assert decode["mean_state_error"] < 1e-4
    if fault == "none":
        assert len(decode["state_error_by_layer"]) == 2      # attention
        assert len(decode["tail_error_by_layer"]) == 6       # convolution
        assert len(decode["first_layer_state_error_by_head"]) == 2
