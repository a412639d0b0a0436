"""The two-latent-kinds serve cell's benchmark files: its configuration
against the catalog row, ``dots3_flops.py`` against a hand count and the
program's parameter tree, the eight new layer-metric files on a synthetic
context, the new traffic file, the tiny cell through the harness, and the
new kind's ``check`` against a swapped token, a perturbed latent row, a
perturbed ring row, a ring that lies about its positions and a planted
wrong selection. Every entry of ``BENCHMARK.json`` is found BY NAME:
nothing here says where in a list an entry stands or how long a list is,
so the next appended cell breaks none of it."""
import copy
import importlib
import json
import os
import types

import numpy as np
import pytest

import rehearsal
from dots3_tiny import STAND_IN, TINY_CELL, TINY_DOTS3, TINY_TRAFFIC
from perfbench import dots3_flops, mla_flops, stats
from perfbench import program_spans as ps
from perfbench import trace_reduce as tr
from perfbench.builders import dots3_serve
from perfbench.readers import (
    cache_plan,
    decode_roofline_latent_kinds,
    latent_selected_share,
    scope_roofline,
    scope_share,
)
from perfbench.traffic_kinds import serve_resident, serve_resident_latent

ROOT = stats.repo_root()
BENCH = stats.load_json(os.path.join(ROOT, "BENCHMARK.json"))
PEAK = stats.load_json(os.path.join(ROOT, "perfbench", "peaks.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL, CONFIG = "dots3-note-serve-resident-16k", "dots3-note-ep8-5layer"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
ENTRY = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
BODY = stats.load_json(os.path.join(ROOT, ENTRY["file"]))
TRAFFIC = stats.load_json(os.path.join(
    ROOT, "perfbench", "traffic", "serve-resident-longctx-48.json"))
NEW_METRICS = ["window_latent_attn_share_of_decode",
               "window_latent_attn_roofline",
               "sparse_latent_attn_share_of_decode",
               "sparse_latent_attn_roofline", "latent_index_share_of_decode",
               "latent_select_share_of_decode", "latent_selected_share",
               "decode_roofline.mla_kinds", "latent_kinds_share_of_decode"]
DECODE = ["deepspeed_tpu.inference.engine", "PROGRAM_DECODE_K"]
READERS = {"scope_share": scope_share, "scope_roofline": scope_roofline,
           "decode_roofline_latent_kinds": decode_roofline_latent_kinds,
           "latent_selected_share": latent_selected_share,
           "cache_plan": cache_plan}
FULL = dict(n_heads=128, kv_rank=512, rope=64)
WINDOW = dict(n_heads=64, kv_rank=1024, rope=64)
FULL_KIND = dict(FULL, nope=128, v_dim=128, q_rank=1024, ix_heads=64,
                 ix_dim=128)
WINDOW_KIND = dict(WINDOW, nope=192, v_dim=128, q_rank=1024)
KINDS = [FULL_KIND, FULL_KIND, WINDOW_KIND, WINDOW_KIND, WINDOW_KIND]


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
    # the cut: published layers 0-4, the dense layer and one whole period
    held = BODY["published"]["layers_held"]
    assert held == [0, 1, 2, 3, 4]
    assert BODY["layer_types"] == row["config"]["layer_types"][:5]
    assert BODY["published"]["layer_types"] == row["config"]["layer_types"]
    assert [k[0] for k in BODY["layer_types"]] == list("ffsss")
    for key in REDUCED:
        assert BODY["published"][key] == row["config"][key] > BODY[key]
    assert (BODY["num_hidden_layers"], BODY["n_routed_experts"],
            BODY["vocab_size"]) == (5, 32, 19008)
    assert BODY["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert {"latent attention (MLA)", "learned sparse attention",
            "window and full attention mixed"} <= set(row["mechanisms"])
    assert "vision tower" in BODY["published"]["not_built"]


def test_reduced_is_exactly_what_differs_from_the_catalog():
    """What ``test_configuration_entry_and_file`` checks, for a
    configuration that is cut: entry and file agree, ``reduced`` names keys
    of the file and no width, the file says what it assumed and which
    deployment it stands for, one cell runs it, its builder exists; and,
    where the catalog has the row, the three keys and the kinds of the
    layers kept are all that differ."""
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
        "mla_rescale", "attention_gate", "indexer", "expert_bias", "rotary",
        "window", "ring_slack", "prefill", "decoding", "weights",
        "cache_positions", "towers"}
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
        BODY["n_routed_experts"]
    weights = dots3_flops.decode_weight_bytes(
        KINDS, 1, 19008, 5120, 13824, 1536, experts_read=held, n_shared=1,
        n_routed=256)
    # (a step reads one row of the embedding a lane: not among its bytes;
    # the indexers' LayerNorm scale and bias ride with the attention)
    assert b["parameter_bytes"] == weights + 19008 * 5120 * 2
    assert b["parameters"] == 4087154176
    ring, dense, index = dots3_flops.lane_cache_bytes(
        3, 2, 513 + serve["window_slack"], serve["cache_positions"], WINDOW,
        FULL, 128)
    assert (ring, dense, index) == (6684672, 56623104, 12582912)
    assert b["window_bytes_per_lane"] == ring
    assert b["latent_bytes_per_lane"] == ring + dense
    assert b["index_key_bytes_per_lane"] == index
    assert 0 < b["lane_bytes"] - ring - dense - index < 200_000
    assert b["lane_cache_bytes"] == 48 * b["lane_bytes"]
    # 11.8 GB of 15.75 GiB before a pass's temporaries; keys and values a
    # head would leave room for one lane
    assert 11.8e9 < b["parameter_bytes"] + b["lane_cache_bytes"] < 11.9e9
    assert 2 * 24576 * 128 * (192 + 128) * 2 > 4.0e9


def test_serve_section_states_the_cache_and_the_limits():
    serve = BODY["serve"]
    assert serve["cache_positions"] == 24576 \
        < BODY["max_position_embeddings"]
    assert serve["window_slack"] == 511 and BODY["sliding_window_size"] == 513
    assert serve["serving"] == {"slots": 48, "prompt_bucket": 2048}
    assert serve["dtype"] == "bf16"
    check = serve["decode_check"]
    upper = ["mean_margin", "largest_margin", "mean_state_error",
             "first_layer_head_state_error", "mean_tail_error",
             "mean_index_key_error", "mean_selection_miss",
             "mean_choice_miss", "mean_step_row_error"]
    assert set(check) >= {s + "_max" for s in upper} | {
        "share_within_tolerance_min", "why", "system_readings",
        "lower_precision_readings"}
    assert 0 < check["mean_margin_max"] < check["largest_margin_max"]
    assert 0.5 < check["share_within_tolerance_min"] < 1.0
    # every limit has room above the largest reading the system gave ...
    sys_, low = check["system_readings"], check["lower_precision_readings"]
    assert sys_["runs"] >= 10
    for stat in upper:
        assert sys_[stat + "_largest"] * 1.05 <= check[stat + "_max"], stat
    assert sys_["share_within_tolerance_smallest"] \
        > check["share_within_tolerance_min"]
    assert sys_["step_runs"] >= 4
    # ... and each lower precision is outside at least one, in every run,
    # but for the one the file itself says it cannot tell
    assert set(low) == {"bf16_scores", "bf16_softmax", "bf16_router",
                        "int8_weights"}
    for name, reading in low.items():
        outside = [stat for stat in upper
                   if reading[stat + "_smallest"] > check[stat + "_max"]]
        assert reading["refused"] is bool(outside), name
        assert reading["refused"] is (name != "bf16_router")
        assert reading["runs"] >= (2 if reading["refused"] else 1)
    # the decode softmax alone is told by the replayed step, and by it alone
    assert [stat for stat in upper
            if low["bf16_softmax"][stat + "_smallest"]
            > check[stat + "_max"]] == ["mean_step_row_error"]


def test_the_traffic_file():
    t = TRAFFIC
    base = stats.load_json(os.path.join(
        ROOT, "perfbench", "traffic", "serve-resident-longctx-32.json"))
    assert t["kind"] == "serve_resident_latent" and t["clients"] == 48
    assert {k: t[k] for k in t if k not in (
        "kind", "why", "grid", "clients", "prompt_lengths")} == {
        k: base[k] for k in base if k not in (
            "kind", "why", "grid", "clients", "prompt_lengths")}
    want = [round(4097 * (16384 / 4097) ** ((i + 0.5) / 48))
            for i in range(48)]
    assert t["prompt_lengths"] == want
    buckets = {-(-n // 2048) * 2048 for n in want}
    assert buckets == {6144, 8192, 10240, 12288, 14336, 16384}
    assert max(buckets) + t["output_tokens"] == t["max_positions"] \
        == BODY["serve"]["cache_positions"]
    assert t["clients"] == BODY["serve"]["serving"]["slots"]


def test_the_entries_list_the_cell_by_name():
    """The cell reports ``serve_out_tokens_per_s`` and ``setup_s`` (not
    ``gap_p95_ms``); the nine new metrics list it alone; each accepted
    metric it lists moves one of its two end-to-end metrics and has its
    file; what its sibling's own mechanism reads it does not list."""
    ends = {m["name"]: m for m in BENCH["end_to_end"]}
    assert CELL in ends["serve_out_tokens_per_s"]["workloads"]
    assert CELL not in ends["gap_p95_ms"]["workloads"]
    assert "workloads" not in ends["setup_s"]
    mine = {m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", ())}
    for name in mine:
        assert per_layer(name)["moves"] in ("serve_out_tokens_per_s",
                                            "setup_s"), name
        assert os.path.exists(os.path.join(
            ROOT, "perfbench", "layer_metrics", name + ".json")), name
    for name in NEW_METRICS:
        metric = per_layer(name)
        assert metric["workloads"] == [CELL] and metric["unit"] == "%"
        assert metric["moves"] == "serve_out_tokens_per_s"
        assert metric["layer"] == "decode step"
        assert set(metric) == {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
    assert per_layer("latent_selected_share")["source"] == "program_counter"
    assert per_layer("sparse_latent_attn_roofline")["source"] \
        == "device_trace"
    # as the window-and-full sibling lists them, but for that sibling's own
    # mechanism; and the two shares of a lane's cache that count by
    # declaration
    sibling = {m["name"] for m in BENCH["per_layer"]
               if "trinity-large-serve-resident-16k" in m.get("workloads",
                                                              ())}
    assert mine - set(NEW_METRICS) == {
        n for n in sibling if not n.startswith(("window_attn_", "full_attn_"))
        and n != "decode_roofline.swa"} | {
        "latent_share_of_lane_cache", "index_key_share_of_lane_cache"}
    # the new entries stand after every accepted one, in the order given
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-len(NEW_METRICS):] == NEW_METRICS
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": CONFIG,
        "traffic": "serve-resident-longctx-48", "chips": 1,
        "why": cells[CELL]["why"]}
    assert len(cells[CELL]["why"]) <= 200 and "8x" in cells[CELL]["why"]
    # at most a quarter of the cells takes four chips
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(json.dumps(BENCH)) < 64 * 1024


# ---------------------------------------------------------------------------
# operations and bytes from shapes
# ---------------------------------------------------------------------------
def test_counts_against_a_hand_count():
    assert dots3_flops.indexer_params(5120, 1024, 64, 128) \
        == 1024 * 8192 + 5120 * 128 + 256 + 5120 * 64 == 9371904
    assert dots3_flops.attention_params(5120, **FULL_KIND) \
        == (5120 * 1024 + 1024 * 128 * 192 + 5120 * 576 + 512 * 128 * 256
            + 128 * 128 * 5120 + 1024 + 512 + 5120 * 128 + 9371904) \
        == 144049920
    assert dots3_flops.attention_params(5120, **WINDOW_KIND) \
        == (5120 * 1024 + 1024 * 64 * 256 + 5120 * 1088 + 1024 * 64 * 320
            + 64 * 128 * 5120 + 1024 + 1024 + 5120 * 64) == 90834944
    assert dots3_flops.latent_row_bytes(512, 64) == 1152
    assert dots3_flops.latent_row_bytes(1024, 64) == 2176
    # a step over 48 lanes: a window layer's 513 rows a lane, a full
    # layer's 2,048 chosen of ~12k live
    window = dots3_flops.absorbed_attention_step(48, 48 * 513, **WINDOW)
    assert window["bytes"] == 2 * (48 * 513 * 1088 + 48 * 64 * 2112)
    assert window["flops"] == 2.0 * 48 * 513 * 64 * 2112
    sparse = dots3_flops.absorbed_attention_step(48, 48 * 2048, **FULL)
    assert sparse["flops"] == 2.0 * 48 * 2048 * 128 * 1088
    # the absorbed form sits on the chip's ridge (240 operations a byte)
    assert 200 < sparse["flops"] / sparse["bytes"] < 260
    index = dots3_flops.index_step(48, 48 * 12000, 1024, 5120, 64, 128)
    assert index["bytes"] == 2 * (48 * 12000 * 128 + 9371904)
    assert index["flops"] == 2.0 * 48 * (1024 * 8192 + 5120 * 192) \
        + 48 * 12000 * 64 * 258.0
    ten = dots3_flops.decode_weight_bytes(
        KINDS, 1, 19008, 5120, 13824, 1536, experts_read=10, n_shared=1,
        n_routed=256)
    every = dots3_flops.decode_weight_bytes(
        KINDS, 1, 19008, 5120, 13824, 1536, experts_read=32, n_shared=1,
        n_routed=256)
    assert every - ten == 4 * 22 * 3 * 5120 * 1536 * 2
    assert 7.9e9 < every < 8.0e9
    step = dots3_flops.decode_step(
        every, 48, 48 * 513, 48 * 12000, 48 * 2048, 3, 2, WINDOW, FULL, 64,
        128)
    assert step["bytes"] == every + 3 * window["bytes"] + 2 * (
        sparse["bytes"] + 2 * 128 * 48 * 12000)
    assert step["flops"] == 3 * window["flops"] + 2 * (
        sparse["flops"] + 48 * 12000 * 64 * 258.0)


def test_counts_agree_with_the_programs_parameter_tree():
    """Shapes only (``jax.eval_shape``): the real configuration's tree has
    the counted parameters, stack by stack, born bfloat16 but the routers
    and their biases."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer_lm import GPT

    model = GPT(dots3_serve.model_config(BODY))
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 64), jnp.int32)))["params"]

    def count(tree):
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    def nbytes(tree):
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree.leaves(tree))

    h = shapes["h"]
    assert sorted(h) == ["attention", "attention_dense", "window"]
    assert count(h["window"]["attn"]) == 3 * dots3_flops.attention_params(
        5120, **WINDOW_KIND)
    assert count(h["attention"]["attn"]) == count(
        h["attention_dense"]["attn"]) == dots3_flops.attention_params(
            5120, **FULL_KIND)
    assert count(h["attention_dense"]["mlp"]) \
        == mla_flops.gated_mlp_params(5120, 13824)
    assert count(h["attention_dense"]) == dots3_flops.layer_params(
        5120, FULL_KIND, dense_width=13824)
    assert count(h["window"]) == 3 * dots3_flops.layer_params(
        5120, WINDOW_KIND, expert_width=1536, held=32, n_shared=1,
        n_routed=256)
    assert h["window"]["mlp"]["experts"]["wi"].shape == (3, 32, 5120, 1536)
    assert h["window"]["mlp"]["gate"]["kernel"].dtype == jnp.float32
    assert h["window"]["mlp"]["expert_bias"].dtype == jnp.float32
    assert h["attention"]["attn"]["indexer"]["wq"]["kernel"].shape \
        == (1, 1024, 64 * 128)
    assert shapes["lm_head"].shape == (5120, 19008)
    assert nbytes(shapes) - nbytes(shapes["wte"]) \
        == dots3_flops.decode_weight_bytes(
            KINDS, 1, 19008, 5120, 13824, 1536, experts_read=32, n_shared=1,
            n_routed=256) \
        == BODY["bytes"]["parameter_bytes"] - 19008 * 5120 * 2
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


RING = "jit(decode_k)/while/body/GPT/h/while/body/window/Block/"
DENSE = "jit(decode_k)/while/body/GPT/h/while/body/attention/Block/"
ROWS = [(RING + "attn/window_latent_attn/mla_decode_attn", 1.0),
        (DENSE + "attn/sparse_latent_attn/mla_decode_attn",
         3.0),
        (DENSE + "attn/indexer/latent_index/dot_general", 0.5),
        (DENSE + "attn/latent_index/dot_general", 1.0),
        (DENSE + "attn/latent_select/while/body/reduce_sum", 0.5),
        (RING + "attn/kv_cache_write/scatter", 0.5),
        (RING + "mlp/moe_experts/ragged-dot-gmm", 2.5),
        ("jit(decode_k)/while/body/GPT/lm_head/dot", 1.0)]
INFO = {"slots": 48, "decode_program": "jit_decode_k", "experts_held": 32,
        "attention": {"itemsize": 2, "window": WINDOW, "full": FULL,
                      "indexer": dict(q_rank=1024, hidden=5120, ix_heads=64,
                                      ix_dim=128),
                      "window_layers": 3, "full_layers": 2},
        "weights": dict(kinds=KINDS, n_dense=1, vocab=19008, hidden=5120,
                        dense_width=13824, expert_width=1536, n_shared=1,
                        n_routed=256, itemsize=2)}


@pytest.mark.parametrize("name,share", [
    ("window_latent_attn_share_of_decode", 10.0),
    ("sparse_latent_attn_share_of_decode", 30.0),
    ("latent_index_share_of_decode", 15.0),
    ("latent_select_share_of_decode", 5.0),
    ("latent_kinds_share_of_decode", 60.0)])
def test_the_share_files_read_their_scopes_of_the_decode_program(name, share):
    reader, args = spec_of(name)
    assert args["program"] == DECODE
    assert reader.read(_ctx(ROWS), **args) == pytest.approx(share)
    assert reader.read(_ctx(None), **args) is None
    assert reader.read(_ctx([(ROWS[-1])]), **args) in (None, 0.0)


@pytest.mark.parametrize("name,scope,counts,seconds,layers", [
    ("window_latent_attn_roofline", "window_latent_attn",
     dots3_flops.absorbed_attention_step(48, 48 * 513, **WINDOW), 1.0, 3),
    ("sparse_latent_attn_roofline", "sparse_latent_attn",
     dots3_flops.absorbed_attention_step(48, 48 * 12000, **FULL), 3.0, 2)])
def test_the_attention_rooflines_read_the_builders_late_counts(
        name, scope, counts, seconds, layers):
    from perfbench import flops

    reader, args = spec_of(name)
    assert args["scope"] == scope and args["program"] == DECODE
    counts = dict(counts, calls_per_step=layers)
    ctx = _ctx(ROWS, modules=[(0, 10)],
               info=dict(INFO, **{args["counts"]: counts}))
    least, _ = flops.roofline_seconds(counts["flops"], counts["bytes"],
                                      PEAK["TPU v5 lite"])
    assert reader.read(ctx, **args) == pytest.approx(
        100 * least * layers / seconds)
    assert reader.read(_ctx(ROWS, modules=[(0, 10)], info=INFO),
                       **args) is None        # no counts yet: says nothing


def system_after(events, t_open=10.0, t_close=20.0):
    env = types.SimpleNamespace(t_open=t_open, t_close=t_close, config=BODY,
                                seed=1)
    system = dots3_serve.LatentKindsServeSystem(env, None, None, None)
    system.info = INFO
    for t, ev in events:
        system.on_bus(dict(ev, kind="serve.stats"))
        for series in (system.live_positions, system.live_window_positions,
                       system.live_chosen_positions):
            if series:
                series[-1] = (t, series[-1][1])
    return system


def test_the_builders_info_gains_the_counts_of_what_the_equations_need():
    def ev(live):
        return dict(live_positions=48 * live,
                    live_window_positions=48 * min(live, 513),
                    live_chosen_positions=48 * min(live, 2048))

    assert "window_latent_attention_step" not in dots3_serve \
        .LatentKindsServeSystem(types.SimpleNamespace(
            t_open=0, t_close=1, config=BODY, seed=1), None, None,
            None).info
    system = system_after([(9.0, ev(5)), (12.0, ev(10000)),
                           (18.0, ev(12000)), (21.0, ev(7))])
    assert system.mean_live_chosen_positions() == 48 * 2048
    info = system.info
    assert info["window_latent_attention_step"] == dict(
        dots3_flops.absorbed_attention_step(48, 48 * 513, **WINDOW),
        calls_per_step=3)
    # the chosen rows, not the live rows the kernel walks to reach them
    assert info["sparse_latent_attention_step"] == dict(
        dots3_flops.absorbed_attention_step(48, 48 * 2048, **FULL),
        calls_per_step=2)
    assert info["latent_index_step"] == dict(
        dots3_flops.index_step(48, 48 * 11000, 1024, 5120, 64, 128),
        calls_per_step=2)
    # contexts shorter than ``index_topk``: all of them are chosen
    short = system_after([(12.0, ev(1000)), (18.0, ev(1400))])
    assert short.info["sparse_latent_attention_step"] == dict(
        dots3_flops.absorbed_attention_step(48, 48 * 1200, **FULL),
        calls_per_step=2)


def test_the_step_roofline_counts_weights_rows_and_the_experts_that_got_one():
    reader, args = spec_of("decode_roofline.mla_kinds")
    system = dict(mean_live_window_positions=lambda: 48 * 513,
                  mean_live_positions=lambda: 48 * 12000,
                  mean_live_chosen_positions=lambda: 48 * 2048,
                  step_expert_load=lambda: {
                      "experts_with_rows_share": 24 / 32})
    ctx = _ctx(ROWS, modules=[(0, 16e6), (20e6, 36e6)], info=INFO, **system)
    weights = dots3_flops.decode_weight_bytes(experts_read=24.0,
                                              **INFO["weights"])
    need = dots3_flops.decode_step(
        weights, 48, 48 * 513, 48 * 12000, 48 * 2048, 3, 2, WINDOW, FULL, 64,
        128)
    peak = PEAK["TPU v5 lite"]
    least_ms = max(need["bytes"] / (peak["hbm_gb_per_s"] * 1e9),
                   need["flops"] / (peak["bf16_tflops"] * 1e12)) * 1e3
    assert reader.read(ctx, **args) == pytest.approx(100 * least_ms / 16.0)
    assert ctx.notes["decode_roofline_latent_kinds"]["bytes"] \
        == need["bytes"]
    # a program from before the counters, or a run without live positions
    old = dict(system, mean_live_chosen_positions=lambda: None)
    assert reader.read(_ctx(ROWS, modules=[(0, 16e6)], info=INFO, **old),
                       **args) is None
    assert reader.read(_ctx(ROWS, modules=[(0, 16e6)], info=INFO),
                       **args) is None


def test_the_counter_files_read_the_programs_events():
    reader, args = spec_of("latent_selected_share")
    assert reader.read(_ctx(
        mean_live_chosen_positions=lambda: 48 * 2048,
        mean_live_positions=lambda: 48 * 10240), **args) == 20.0
    assert reader.read(_ctx(mean_live_positions=lambda: 5), **args) is None
    assert reader.read(_ctx(), **args) is None
    plan = {"window_bytes_per_lane": 6684672,
            "index_key_bytes_per_lane": 12582912,
            "latent_bytes_per_lane": 63307776, "bytes_per_lane": 76004884}
    for name, lo, hi in (("window_share_of_lane_cache", 8.7, 8.9),
                         ("index_key_share_of_lane_cache", 16.5, 16.6),
                         ("latent_share_of_lane_cache", 83.2, 83.4)):
        reader, args = spec_of(name)
        assert lo < reader.read(_ctx(cache_plan=plan), **args) < hi, name
        assert reader.read(_ctx(cache_plan=None), **args) is None


# ---------------------------------------------------------------------------
# the cell through the harness, and the kind's verdict
# ---------------------------------------------------------------------------
def test_the_stand_in_is_registered_for_any_subset_of_the_tests():
    assert rehearsal.CONFIGS[TINY_DOTS3["name"]] is TINY_DOTS3
    assert rehearsal.TRAFFIC[TINY_CELL["traffic"]] is TINY_TRAFFIC
    assert TINY_CELL in rehearsal.CELLS
    assert rehearsal.STAND_IN[CELL] == STAND_IN[CELL] == TINY_CELL["name"]
    # every published key of the real file is in the tiny one
    published = set(BODY) - {"assumed", "deployment", "published", "bytes"}
    assert published <= set(TINY_DOTS3), published - set(TINY_DOTS3)
    # and the tiny one keeps the shape of the thing
    assert TINY_DOTS3["layer_types"] == BODY["layer_types"]
    assert TINY_DOTS3["first_k_dense_replace"] \
        == BODY["first_k_dense_replace"]
    assert set(TINY_TRAFFIC) == set(TRAFFIC) - {"why", "grid"}
    assert set(TINY_DOTS3["serve"]["decode_check"]) == {
        k for k in BODY["serve"]["decode_check"] if k.endswith(("_max",
                                                               "_min"))}


def test_the_new_kind_is_the_resident_kind_but_for_its_check():
    for name in ("ROLE", "plan", "warm_up", "drive", "series",
                 "end_to_end", "judge_selection"):
        assert getattr(serve_resident_latent, name) \
            is getattr(serve_resident, name), name
    assert serve_resident_latent.check is not serve_resident.check


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(tmp_path, trace):
    root = rehearsal.make_root(tmp_path)
    rc, last, err = rehearsal.run_cell(root, TINY_CELL["name"], trace=trace,
                                       seed=2 ** 31 + 59, seconds=1.5)
    assert rc == 0, err[-2000:]
    assert last["correct"] is True and last["rehearsal"] is True
    assert last["failed"] == 0 and last["attempted"] > 0
    if not trace:
        assert set(last["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}
    else:
        # no device plane on the CPU: the trace's readers find nothing and
        # say nothing; the program's events are read
        assert last["metrics"]["compiles_in_window.serve"]["value"] == 0
        assert 15 < last["metrics"]["window_share_of_lane_cache"][
            "value"] < 16
        assert 23 < last["metrics"]["index_key_share_of_lane_cache"][
            "value"] < 24
        assert 0 < last["metrics"]["latent_selected_share"]["value"] < 100
        assert 0 < last["metrics"]["moe_experts_with_rows_share"][
            "value"] <= 100
        assert not {"sparse_latent_attn_share_of_decode",
                    "window_latent_attn_roofline",
                    "decode_roofline.mla_kinds"} & set(last["metrics"])


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
        config=copy.deepcopy(TINY_DOTS3), seed=seed,
        traffic=dict(TINY_TRAFFIC, reference_samples=2))
    with jax.default_matmul_precision("highest"):
        system = dots3_serve.build(env, None)
        system.scheduler._ensure_compiled()
    return env, system


@pytest.fixture(scope="module")
def served():
    """The tiny system after ONE run that ended with four requests in
    flight: ``(env, system, record, what the run left)``; each case below
    reads a copy of what it left."""
    import jax

    env, system = tiny_system()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 128, size=n).tolist()
               for n in (5, 9, 20, 23)]
    with jax.default_matmul_precision("highest"):
        record, (env.t_open, env.t_close) = serve_until(
            system, prompts, [36, 36, 36, 36], polls=24)
    assert len(record["in_flight"]) == 4
    kept, system.scheduler.lanes_at_exit = \
        system.scheduler.lanes_at_exit, None
    return env, system, record, kept


@pytest.mark.parametrize("fault", ["none", "token", "latent_row",
                                   "index_row", "ring_row", "ring_position",
                                   "selection", "last_row"])
def test_check_fails_a_swapped_token_a_perturbed_row_and_a_wrong_selection(
        served, fault):
    """The kind's ``check`` on the tiny system: correct as served; a served
    token swapped for another, the full layers' stored latents or index
    keys moved by 1%, a window layer's ring moved by 5%, a ring that says
    it holds other positions than it does, a last step that says it read
    another row than its scores choose, or a last step whose written row
    is 5% off (ONE row a lane: the statistics over every row hardly see
    it, the replayed step does), and it is not."""
    import jax
    import jax.numpy as jnp

    env, system, record, left = served
    # this case's own copy of what the run left and of its record
    kept = copy.copy(left)
    kept.cache = jax.tree.map(lambda a: a, left.cache)
    kept.live = copy.deepcopy(left.live)
    record = dict(record, in_flight=copy.deepcopy(record["in_flight"]))
    record["by_rid"] = {
        rid: next((r for r in record["in_flight"]
                   if r.prompt == req.prompt), req)
        for rid, req in record["by_rid"].items()}
    system.scheduler.lanes_at_exit = kept
    with jax.default_matmul_precision("highest"):
        if fault == "token":
            # the lanes took the served tokens in; the record now differs
            for victim in record["in_flight"]:
                victim.tokens[1] = (victim.tokens[1] + 1) % 128
            for comp in kept.live.values():
                comp.tokens[1] = (comp.tokens[1] + 1) % 128
        full = kept.cache["h"]["attention"]["attn"]
        ring = kept.cache["h"]["window"]["attn"]
        if fault == "latent_row":
            full["cached_latent"] = full["cached_latent"] * jnp.float32(1.01)
        if fault == "index_row":
            # (a uniform factor moves no choice: only the stored keys' own
            # statistic tells it)
            full["cached_index_key"] = full["cached_index_key"] \
                * jnp.float32(1.01)
        if fault == "ring_row":
            ring["cached_rope_key"] = ring["cached_rope_key"] \
                * jnp.float32(1.05)
        if fault == "ring_position":
            ring["slot_pos"] = jnp.where(ring["slot_pos"] >= 0,
                                         ring["slot_pos"] - 1, -1)
        if fault == "selection":
            # every lane's first chosen row swapped for the row before it
            # (which the step did not choose, or is nobody's)
            rows = full["chosen_rows"]
            full["chosen_rows"] = rows.at[..., 0].set(
                jnp.maximum(rows[..., 0] - 1, 0))
        if fault == "last_row":
            # the row the second full layer's last step wrote, a lane
            at = full["cache_index"][0] - 1
            full["cached_latent"] = full["cached_latent"].at[
                0, jnp.arange(at.shape[0]), at].multiply(1.05)
        plan = types.SimpleNamespace(vocab=128)
        if fault == "ring_position":
            with pytest.raises(ValueError, match="ring does not hold"):
                serve_resident_latent.check(env, system, plan, record)
            return
        verdict = serve_resident_latent.check(env, system, plan, record)
    assert verdict["correct"] is (fault == "none"), verdict["decode"]
    assert verdict["live_lanes_streamed_their_tokens"]
    decode = verdict["decode"]
    told = {"token": "largest_margin", "latent_row": "mean_state_error",
            "index_row": "mean_index_key_error",
            "ring_row": "mean_tail_error",
            "selection": "mean_choice_miss",
            "last_row": "mean_step_row_error"}
    for stat in ("largest_margin", "mean_state_error",
                 "mean_index_key_error", "mean_tail_error",
                 "mean_choice_miss", "mean_step_row_error"):
        outside = decode[stat] > decode["limits"][stat + "_max"]
        if fault in told and stat == told[fault]:
            assert outside, (fault, stat, decode[stat])
        elif fault == "none" or (stat != "mean_step_row_error" and fault in (
                "latent_row", "index_row", "ring_row")):
            assert not outside, (fault, stat, decode[stat])
    if fault == "none":
        assert len(decode["state_error_by_layer"]) == 2      # full
        assert len(decode["index_key_error_by_layer"]) == 2
        assert len(decode["tail_error_by_layer"]) == 3       # window
        assert decode["mean_selection_miss"] == 0.0
        # every live lane's last step, the four layers after the first
        assert decode["step_lanes"] == 4
        assert len(decode["step_row_error_by_layer"]) == 4
        assert decode["positions"] > 2 * 8      # the rings have wrapped
