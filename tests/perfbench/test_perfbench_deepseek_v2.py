"""The latent-attention serve cell's benchmark files: its configuration
against the catalog row, ``mla_flops.py`` against a hand count and the
program's parameter tree, the nine new layer-metric files on a synthetic
context and against the scopes of a CPU lowering, the tiny cell through
the harness, and the kind's ``check`` against a swapped token, a perturbed
latent and two lower precisions."""
import copy
import json
import os
import types

import numpy as np
import pytest

import rehearsal
from brumby_tiny import TINY_CLOSED_DECODED
from deepseek_v2_tiny import STAND_IN, TINY_CELL, TINY_DEEPSEEK
from perfbench import flops, mla_flops, stats
from perfbench import program_spans as ps
from perfbench import trace_reduce as tr
from perfbench.builders import deepseek_v2_serve
from perfbench.readers import (
    cache_plan,
    decode_roofline_latent,
    expert_load,
    latent_attention_roofline,
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
    "deepseek-v2-ep8-5layer.json"))
BODY = stats.load_json(os.path.join(ROOT, ENTRY["file"]))
TRAFFIC = stats.load_json(os.path.join(
    ROOT, "perfbench", "traffic", "serve-closed-code-long-256.json"))
NEW_METRICS = ["mla_share_of_decode", "mla_attn_share_of_decode",
               "mla_attn_roofline", "moe_share_of_decode",
               "moe_experts_roofline.decode", "mla_share_of_prefill",
               "latent_share_of_lane_cache",
               "moe_expert_load_max_over_mean.serve", "decode_roofline.mla"]
# the accepted per-layer metrics that list the cell: those that move
# ``serve_out_tokens_per_s`` or ``setup_s``. The seven that move
# ``gap_p95_ms`` (the admission's medians, ``gap_p50_ms``, ``ttft_p50_ms``)
# cannot: the cell does not report that tail end to end (below)
ACCEPTED = ["compiles_in_window.serve", "sched_lane_occupancy",
            "ttft_p95_ms.closed", "decode_step_ms_p50",
            "device_idle_share.serve", "hbm_peak_gb.serve",
            "idle_share.admit", "idle_share.step_host", "queue_wait_ms_p50",
            "scope_unattributed_share.serve", "decode_ahead_share",
            "setup_trace_s", "setup_lower_s", "setup_compile_or_load_s",
            "setup_programs_built", "setup_cache_misses",
            "setup_first_dispatch_s.serve"]
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
DECODE = ["deepspeed_tpu.inference.engine", "PROGRAM_DECODE_K"]
ATTN = deepseek_v2_serve.attention_sizes(BODY)
READERS = {"scope_share": scope_share, "scope_roofline": scope_roofline,
           "latent_attention_roofline": latent_attention_roofline,
           "decode_roofline_latent": decode_roofline_latent,
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


# ---------------------------------------------------------------------------
# the configuration and the entries
# ---------------------------------------------------------------------------
def test_every_published_key_is_in_the_file_under_its_key():
    row = catalog_row()
    for key, value in row["config"].items():
        if key not in ENTRY["reduced"]:
            assert key in BODY and BODY[key] == value, key
    for key in REDUCED:
        assert BODY[key] < row["config"][key] == BODY["published"][key]
    assert (BODY["num_hidden_layers"], BODY["n_routed_experts"],
            BODY["vocab_size"]) == (5, 20, 12800)
    # the leading dense layer and four expert layers; one of 8 groups of
    # the 160 experts the router scores; an eighth of the vocabulary
    assert BODY["moe"]["routed_over"] == 160 == 8 * BODY["n_routed_experts"]
    assert BODY["moe"]["experts_held"] == [0, 20]
    assert 8 * BODY["vocab_size"] == BODY["published"]["vocab_size"]
    assert "latent attention (MLA)" in row["mechanisms"]


def test_reduced_is_exactly_what_differs_from_the_catalog():
    """What ``test_configuration_entry_and_file`` checks, for a
    configuration that is cut (that test holds every configuration to
    ``reduced == []``): entry and file agree, ``reduced`` names keys of the
    file and no width, the file says what it assumed and which deployment
    it stands for, a cell runs it, its builders exist; and, where the
    catalog has the row, ``reduced`` is exactly the keys that differ."""
    import importlib

    assert BODY["name"] == ENTRY["name"] and BODY["source"] == ENTRY["source"]
    assert BODY["reduced"] == ENTRY["reduced"] == REDUCED
    assert set(ENTRY) == {"name", "source", "file", "reduced", "why"}
    assert 1 <= len(ENTRY["why"]) <= 200 and len(ENTRY["source"]) <= 200
    for key in ENTRY["reduced"]:
        assert key in BODY
        assert not key.endswith(("_dim", "_rank")) and "intermediate" \
            not in key and key != "hidden_size", key
    for role, builder in BODY["builders"].items():
        mod = importlib.import_module("perfbench.builders." + builder)
        assert callable(mod.build), (role, builder)
    assert set(BODY["assumed"]) >= {
        "decoding", "weights", "rotary_order", "router_init",
        "absorbed_decode", "cache", "prompt_bucket", "cache_positions",
        "shared_experts", "balance_losses"}
    assert all(len(why) > 10 for why in BODY["assumed"].values())
    assert "8-chip expert-parallel" in BODY["deployment"]
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == ENTRY["name"]] == [CELL]
    if os.path.exists(CATALOG):
        row = catalog_row()
        differs = [k for k in REDUCED if BODY.get(k) != row["config"][k]]
        others = [k for k, v in row["config"].items()
                  if k not in REDUCED and BODY.get(k, "missing") != v]
        assert differs == REDUCED and others == []


def test_serve_section_states_the_cache_and_the_limits():
    serve = BODY["serve"]
    assert serve["cache_positions"] == 2944 < BODY["max_position_embeddings"]
    assert serve["serving"]["slots"] == TRAFFIC["clients"]
    assert serve["dtype"] == "bf16" and len(serve["serving_why"]) > 20
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
    assert sys_["runs"] >= 10
    for stat in upper:
        assert sys_[stat + "_largest"] * 1.05 < check[stat + "_max"], stat
    assert sys_["share_within_tolerance_smallest"] \
        > check["share_within_tolerance_min"]
    # ... and each lower precision is refused by at least one of them
    assert len(low) >= 2
    for name, control in low.items():
        assert control["runs"] >= 1 and len(control["what"]) > 20
        assert any(control[stat + "_smallest"] > check[stat + "_max"]
                   for stat in upper if stat + "_smallest" in control), name


def test_the_traffic_file_is_the_issues_mix():
    twin = stats.load_json(os.path.join(
        ROOT, "perfbench", "traffic", "serve-closed-chat-long-64.json"))
    t = TRAFFIC
    assert t["kind"] == "serve_closed_decoded" and set(t) == set(twin)
    assert t["prompt_lengths"] == twin["prompt_lengths"]
    # the 40 quantiles (i + 0.5) / 40 of a log-normal(median 640, sigma
    # 0.7) clipped to 64..2048
    from statistics import NormalDist

    want = [int(min(2048, max(64, round(640 * np.exp(
        0.7 * NormalDist().inv_cdf((i + 0.5) / 40)))))) for i in range(40)]
    assert max(abs(a - b) for a, b in zip(t["output_lengths"], want)) <= 1
    assert round(np.mean(t["output_lengths"])) == 779
    assert (t["max_positions"], t["prompt_bucket"], t["ramp_output_step"],
            t["pregenerate_requests"], t["trace_seconds"],
            t["reference_samples"]) == (2944, 64, 4, 2400, 8, 4)
    assert serve_closed.bucketed(max(t["prompt_lengths"]), 64) \
        + max(t["output_lengths"]) == t["max_positions"] \
        == BODY["serve"]["cache_positions"]
    # the ramp's longest request fits, and the grid's buckets fit the ramp
    assert t["ramp_output_step"] * t["clients"] + 896 <= t["max_positions"]
    assert BODY["serve"]["decode_check"]["live_lanes"] == 4


def test_the_new_entries_are_appended_and_list_the_new_cell_alone():
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(NEW_METRICS[0])    # a later PR appends after them
    assert names[at:at + len(NEW_METRICS)] == NEW_METRICS
    assert at > names.index("setup_first_dispatch_s.serve")
    by = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW_METRICS:
        m = by[name]
        assert m["workloads"][0] == CELL, name
        assert m["moves"] == "serve_out_tokens_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        reader, args = spec_of(name)
        if "program" in args:
            assert ps.program_constant(*args["program"])
    for name in ("mla_attn_roofline", "moe_experts_roofline.decode",
                 "decode_roofline.mla"):
        assert by[name]["unit"] == "%" and by[name]["better"] == "higher"
    # the accepted metrics that read what the shared scheduler emits list
    # the cell; those whose readers count keys and values per head do not
    for name in ACCEPTED:
        assert CELL in by[name]["workloads"], name
    for name in ("kv_cache_share_of_decode", "kv_blocks_read_share",
                 "decode_roofline", "decode_roofline.ssm",
                 "state_share_of_lane_cache"):
        assert CELL not in by[name]["workloads"]
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(ACCEPTED) | set(NEW_METRICS)
    # end to end the cell reports tokens per second and set-up. NOT the
    # gaps' 95th percentile: at 256 lanes it sits on the edge between a
    # gap with the longest prefill and a gap with two admissions (103 or
    # 105.5 ms by the seed: 2.5% over ten seeds, 5.1% at 128 lanes, where a
    # new cell may spread 1.75%), so no per-layer metric that moves it
    # lists the cell either (PERF.md, section 6, PR 39)
    ends = {m["name"]: m for m in BENCH["end_to_end"]}
    assert CELL in ends["serve_out_tokens_per_s"]["workloads"]
    assert CELL not in ends["gap_p95_ms"]["workloads"]
    assert all(m["moves"] in ("serve_out_tokens_per_s", "setup_s")
               for m in BENCH["per_layer"] if CELL in m.get("workloads", ()))
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell["traffic"] == "serve-closed-code-long-256"
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(BENCH["workloads"]) >= 9


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------
def test_counts_against_a_hand_count():
    # W_qa 5120 x 1536, W_qb 1536 x 128 x 192, W_kva 5120 x 576, W_kvb
    # 512 x 128 x 256, W_o 16384 x 5120, the two norms
    assert mla_flops.attention_params(5120, **ATTN) \
        == 7_864_320 + 37_748_736 + 2_949_120 + 16_777_216 + 83_886_080 \
        + 1536 + 512 == 149_227_520
    assert mla_flops.gated_mlp_params(5120, 12288) == 188_743_680
    assert mla_flops.gated_mlp_params(5120, 1536) == 23_592_960
    assert mla_flops.expert_layer_params(5120, 1536, 20, 2) \
        == 22 * 23_592_960
    weights = mla_flops.decode_weight_bytes(
        5, 1, 12800, 5120, 12288, 1536, 20, 2, 160, 2, **ATTN)
    assert weights == 2 * (5 * (149_227_520 + 10_240) + 188_743_680
                           + 4 * 22 * 23_592_960 + 12800 * 5120 + 5120) \
        + 4 * 5120 * 160 * 4
    assert weights == pytest.approx(6.17e9, rel=2e-3)
    # 1,152 bytes a position and layer where 128 heads of keys and values
    # would be 81,920
    assert mla_flops.latent_bytes_per_position(1, 512, 64) == 1152
    assert mla_flops.latent_bytes_per_position(5, 512, 64) == 5760
    assert 128 * (192 + 128) * 2 == 81_920
    assert 256 * 2944 * 5760 == pytest.approx(4.34e9, rel=1e-3)
    # 278.5 kFLOP a cached position and layer, on the chip's ridge
    assert mla_flops.absorbed_attention_flops(1, 128, 512, 64) == 278_528
    assert 278_528 / 1152 == pytest.approx(241.8, abs=0.1)
    assert PEAK["TPU v5 lite"]["bf16_tflops"] * 1e12 \
        / (PEAK["TPU v5 lite"]["hbm_gb_per_s"] * 1e9) \
        == pytest.approx(240.5, abs=0.1)
    # decompressing instead: 33.5 MFLOP a position, 120 times as much
    assert 2 * 512 * 128 * 256 == 33_554_432
    assert 33_554_432 / 278_528 == pytest.approx(120.5, abs=0.1)
    assert mla_flops.absorb_flops(256, 128, 512, 128, 128) \
        == 256 * 33_554_432
    step = mla_flops.absorbed_step(256, 224_000, 128, 512, 128, 64, 128)
    assert step["flops"] == 224_000 * 278_528 + 256 * 33_554_432
    assert step["bytes"] == 2 * (224_000 * 576 + 512 * 128 * 256
                                 + 256 * 128 * 320)
    experts = mla_flops.held_experts_step(192, 5120, 1536, 20)
    assert experts["flops"] == 6 * 192 * 5120 * 1536
    assert experts["bytes"] == pytest.approx(0.944e9 + 192 * 13312 * 3,
                                             rel=2e-3)
    assert flops.roofline_seconds(experts["flops"], experts["bytes"],
                                  PEAK["TPU v5 lite"])[1] == "memory"
    assert mla_flops.prefill_attention_flops(896, 128, 512, 128, 64, 128) \
        == 2 * 896 * 512 * 128 * 256 + 896 * 896 * 128 * 320


def test_counts_agree_with_the_programs_parameter_tree():
    """Shapes only (``jax.eval_shape``): the real configuration's tree has
    the counted parameters, leaf group by leaf group, born bfloat16 but
    the routers, and its lane cache holds the counted bytes."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer_lm import GPT

    cfg = deepseek_v2_serve.model_config(BODY)
    model = GPT(cfg)
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 64), jnp.int32)))["params"]

    def count(tree):
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    def nbytes(tree):
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree.leaves(tree))

    dense, block = shapes["h"]["dense_0"], shapes["h"]["block"]
    assert count(dense["attn"]) == mla_flops.attention_params(5120, **ATTN)
    assert count(block["attn"]) == 4 * count(dense["attn"])
    assert count(dense["mlp"]) == mla_flops.gated_mlp_params(5120, 12288)
    assert count(block["mlp"]["experts"]) + count(block["mlp"]["shared"]) \
        == 4 * mla_flops.expert_layer_params(5120, 1536, 20, 2)
    assert block["mlp"]["experts"]["wi"].shape == (4, 20, 5120, 1536)
    assert block["mlp"]["gate"]["kernel"].shape == (4, 5120, 160)
    assert block["mlp"]["gate"]["kernel"].dtype == jnp.float32
    # a step reads everything but the embedding
    assert nbytes(shapes) - nbytes(shapes["wte"]) \
        == mla_flops.decode_weight_bytes(
            5, 1, 12800, 5120, 12288, 1536, 20, 2, 160, 2, **ATTN)
    assert nbytes(shapes) == pytest.approx(6.30e9, rel=1e-3)
    cache = jax.eval_shape(
        lambda p: model.apply({"params": p}, jnp.zeros((256, 1), jnp.int32),
                              deterministic=True, decode=True,
                              mutable=["cache"])[1]["cache"], shapes)["h"]
    assert cache["cached_latent"].shape == (5, 256, 2944, 512)
    assert cache["cached_rope_key"].shape == (5, 256, 2944, 64)
    assert nbytes([cache["cached_latent"], cache["cached_rope_key"]]) \
        == 256 * 2944 * mla_flops.latent_bytes_per_position(5, 512, 64)


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


LAYER = "jit(decode_k)/while/body/GPT/h/while/body/block/"
ROWS = [(LAYER + "attn/mla_q_proj/q_a/dot", 1.0),
        (LAYER + "attn/mla_kv_proj/kv_a/dot", 0.5),
        (LAYER + "attn/mla_absorb/einsum", 0.5),
        (LAYER + "attn/mla_attn/dot", 5.0),
        (LAYER + "attn/mla_out_proj/c_proj/dot", 1.0),
        (LAYER + "attn/kv_cache_write/scatter", 0.5),
        (LAYER + "mlp/moe_router/gate/dot", 0.5),
        (LAYER + "mlp/moe_dispatch/sort", 0.5),
        (LAYER + "mlp/moe_experts/ragged-dot-gmm", 6.0),
        (LAYER + "mlp/moe_combine/mul", 0.5),
        (LAYER + "mlp/moe_shared/shared/c_fc/dot", 1.5),
        ("jit(decode_k)/while/body/GPT/lm_head/dot", 2.5)]
INFO = {"slots": 256, "decode_program": "jit_decode_k",
        "weight_bytes": 6.17e9, "kv_bytes_per_position": 5760.0,
        "latent_attention": dict(layers=5, itemsize=2, n_heads=128,
                                 kv_rank=512, nope=128, rope=64, v_dim=128),
        "held_experts_step": dict(mla_flops.held_experts_step(
            192, 5120, 1536, 20), calls_per_step=4)}


@pytest.mark.parametrize("name,share", [
    ("mla_share_of_decode", 8.0), ("mla_attn_share_of_decode", 5.5),
    ("moe_share_of_decode", 9.0)])
def test_the_share_files_read_their_scopes_of_the_decode_program(name, share):
    reader, args = spec_of(name)
    assert args["program"] == DECODE
    ctx = _ctx(rows=ROWS)
    assert reader.read(ctx, **args) == pytest.approx(100 * share / 20.0)
    assert set(ctx.notes["scope_share:" + "+".join(args["scopes"])]) \
        == set(args["scopes"])
    # a program without the scopes (the parent's) reads nothing of them,
    # and a trace without a scope table nothing at all
    plain = [r for r in ROWS if "mla_" not in r[0] and "moe_" not in r[0]]
    assert not reader.read(_ctx(rows=plain), **args)
    assert reader.read(_ctx(rows=None), **args) is None


def test_the_prefill_share_reads_the_prefill_programs_alone():
    from deepspeed_tpu.inference import engine

    reader, args = spec_of("mla_share_of_prefill")
    assert reader.read(_ctx(rows=ROWS), **args) is None
    rows = [(p.replace("decode_k", "prefill"), s) for p, s in ROWS]
    assert reader.read(_ctx(rows=rows, program=engine.PROGRAM_PREFILL),
                       **args) == pytest.approx(100 * 8.0 / 20.0)


def test_the_attention_roofline_counts_the_live_positions():
    reader, args = spec_of("mla_attn_roofline")
    assert args["program"] == DECODE
    need = mla_flops.absorbed_step(256, 224_000, 128, 512, 128, 64, 128)
    least = max(need["flops"] / 197e12, need["bytes"] / 819e9)
    rows = [(LAYER + "attn/mla_attn/dot", 2 * 5 * least / 0.5 * 0.8),
            (LAYER + "attn/mla_absorb/einsum", 2 * 5 * least / 0.5 * 0.2),
            (LAYER + "mlp/moe_experts/ragged-dot-gmm", 3.0)]
    ctx = _ctx(rows=rows, modules=[(0, 26e6), (27e6, 53e6)], info=INFO,
               mean_live_positions=lambda: 224_000.0)
    assert reader.read(ctx, **args) == pytest.approx(50.0)
    note = ctx.notes["latent_attention_roofline"]
    assert note["runs"] == 2 and note["layers"] == 5
    assert note["live_positions"] == 224_000 and note["lanes"] == 256
    # nothing to read, and nothing raised: no live positions (a program
    # whose serve.stats events carry none), no sizes, no scopes, no table
    for ctx in (_ctx(rows=rows, modules=[(0, 1)], info=INFO,
                     mean_live_positions=lambda: None),
                _ctx(rows=rows, modules=[(0, 1)], info=INFO),
                _ctx(rows=rows, modules=[(0, 1)],
                     mean_live_positions=lambda: 1.0),
                _ctx(rows=rows[2:], modules=[(0, 1)], info=INFO,
                     mean_live_positions=lambda: 1.0),
                _ctx(rows=None, info=INFO,
                     mean_live_positions=lambda: 1.0)):
        assert reader.read(ctx, **args) is None


def test_the_experts_roofline_reads_the_held_matrices_once_a_layer():
    reader, args = spec_of("moe_experts_roofline.decode")
    assert args == {"scope": "moe_experts", "program": DECODE,
                    "counts": "held_experts_step"}
    counts = INFO["held_experts_step"]
    least = counts["bytes"] / 819e9            # 1.16 ms a layer
    rows = [(LAYER + "mlp/moe_experts/ragged-dot-gmm", 2 * 4 * least / 0.4)]
    ctx = _ctx(rows=rows, modules=[(0, 26e6), (27e6, 53e6)], info=INFO)
    assert reader.read(ctx, **args) == pytest.approx(40.0)
    assert ctx.notes["scope_roofline:moe_experts"]["bound"] == "memory"
    assert ctx.notes["scope_roofline:moe_experts"]["calls"] == 8


def test_the_step_roofline_takes_the_larger_of_bytes_and_attention():
    reader, args = spec_of("decode_roofline.mla")
    steps = [(i * 27e6, i * 27e6 + 20e6) for i in range(5)]
    ctx = _ctx(modules=steps, info=INFO, series={"lanes_active": [256]},
               mean_live_positions=lambda: 224_000.0)
    by_bytes = (6.17e9 + 224_000 * 5760) / 819e9 * 1e3
    assert reader.read(ctx, **args) == pytest.approx(100 * by_bytes / 20.0)
    note = ctx.notes["decode_roofline_latent"]
    assert note["least_ms_by_bytes"] == pytest.approx(by_bytes)
    assert note["least_ms_by_attention_flops"] == pytest.approx(
        5 * (224_000 * 278_528 + 256 * 33_554_432) / 197e12 * 1e3)
    # the attention alone sits on the ridge (242 operations a byte for the
    # chip's 240.5), so with the weights' bytes beside it a step is bound
    # by bytes at any context; without them the operations bind
    far = _ctx(modules=steps, info=dict(INFO, weight_bytes=0.0),
               mean_live_positions=lambda: 256 * 2944.0)
    assert reader.read(far, **args) == pytest.approx(
        100 * far.notes["decode_roofline_latent"][
            "least_ms_by_attention_flops"] / 20.0)
    assert far.notes["decode_roofline_latent"]["least_ms_by_bytes"] \
        < far.notes["decode_roofline_latent"]["least_ms_by_attention_flops"]
    assert reader.read(_ctx(modules=steps, info=INFO), **args) is None
    assert reader.read(_ctx(info=INFO, mean_live_positions=lambda: 1.0),
                       **args) is None


def test_the_counter_files_read_the_programs_events():
    reader, args = spec_of("latent_share_of_lane_cache")
    plan = {"kind": "serve.cache_plan", "slots": 256,
            "latent_bytes_per_lane": 2944 * 5760, "kv_bytes_per_lane":
            2944 * 5760 + 2944 + 4, "bytes_per_lane": 2944 * 5760 + 2948}
    got = reader.read(_ctx(cache_plan=plan), **args)
    assert 99.98 < got < 100
    # the parent's event has no such field: nothing to read, nothing raised
    assert reader.read(_ctx(cache_plan=None), **args) is None
    reader, args = spec_of("moe_expert_load_max_over_mean.serve")
    assert reader.read(_ctx(expert_load=lambda: {"max_over_mean": 1.4}),
                       **args) == 1.4
    assert reader.read(_ctx(), **args) is None


# ---------------------------------------------------------------------------
# the cell through the harness, and the kind's verdict
# ---------------------------------------------------------------------------
def test_the_stand_in_is_registered_for_any_subset_of_the_tests():
    assert rehearsal.CONFIGS[TINY_DEEPSEEK["name"]] is TINY_DEEPSEEK
    assert rehearsal.TRAFFIC[TINY_CELL["traffic"]] is TINY_CLOSED_DECODED
    assert TINY_CELL in rehearsal.CELLS
    assert rehearsal.STAND_IN[CELL] == TINY_CELL["name"]
    # every published key of the real file is in the tiny one
    published = set(BODY) - {"assumed", "deployment", "published"}
    assert published <= set(TINY_DEEPSEEK), published - set(TINY_DEEPSEEK)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(tmp_path, trace):
    root = rehearsal.make_root(tmp_path)
    rc, last, err = rehearsal.run_cell(root, TINY_CELL["name"], trace=trace,
                                       seed=2 ** 31 + 39, seconds=1.5)
    assert rc == 0, err[-2000:]
    assert last["correct"] is True and last["rehearsal"] is True
    assert last["failed"] == 0 and last["attempted"] > 0
    if trace:
        # no device plane on the CPU: the trace's readers find nothing and
        # say nothing; the program's events are read
        assert last["metrics"]["compiles_in_window.serve"]["value"] == 0
        assert 99 < last["metrics"]["latent_share_of_lane_cache"]["value"] \
            <= 100
        assert last["metrics"]["moe_expert_load_max_over_mean.serve"][
            "value"] >= 1.0
        assert not (set(NEW_METRICS) - {
            "latent_share_of_lane_cache",
            "moe_expert_load_max_over_mean.serve"}) & set(last["metrics"])
    else:
        assert set(last["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}
    run = next(json.loads(ln) for ln in err.splitlines()
               if ln.startswith("{") and '"event": "run"' in ln)
    assert run["verdict"]["decode"]["ok"] is True
    assert run["verdict"]["decode"]["positions"] > 0
    # (how many of the two asked for still held a request at the close
    # depends on the machine's load)
    assert 1 <= run["verdict"]["decode"]["lanes"] <= 2


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
        t_close=float("inf"))


PLAN = types.SimpleNamespace(vocab=128)


def tiny_prompts(seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 128, size=n).tolist() for n in (9, 20, 5, 30)]


def test_check_fails_a_swapped_token_and_a_perturbed_latent():
    """The tiny system serves two requests to their end and is stopped
    with two more in their lanes. ``check`` over that record is correct
    and has read both live lanes' latents out of the scheduler's cache
    against the reference's. With a token after the first swapped in the
    record of the completed requests it is not, and no request counts as
    failed; nor with a swapped first token; nor where a live lane has
    taken in other tokens than its client was streamed; nor where a
    lane's first-layer latents, or its rotary keys, are off by a
    hundredth. Without live lanes there is no verdict."""
    env = tiny_env(TINY_DEEPSEEK, 11)
    system = deepseek_v2_serve.build(env, None)
    try:
        record = serve_until(system, tiny_prompts(0), (6, 30, 6, 30), 12)
    finally:
        system.unsubscribe(system.on_bus)
    assert system.cache_plan["slots"] == 4
    assert system.cache_plan["decode_attention"] == "einsum"
    assert system.cache_plan["latent_bytes_per_lane"] == 3 * 64 * 20 * 4
    assert system.mean_live_positions() > 0
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
            for lane in found:      # the first layer's rows
                lane[leaf] = lane[leaf].at[0].multiply(1.01)
            return found
        return lanes

    good = checked()
    assert good["correct"] is True and good["decode"]["positions"] == 10
    assert good["decode"]["lanes"] == 2
    assert system.scheduler.lanes_at_exit is None
    assert all(6 < lane["taken_in"] < 30 for lane in good["live_lanes"])
    # float32 against float32: the latents the timed steps left are the
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
    latent = checked(lanes=perturb("cached_latent"))
    assert latent["correct"] is False and latent["failed"] == 0
    assert latent["decode"]["first_layer_head_state_error"] \
        == pytest.approx(0.01, rel=1e-2)
    assert latent["decode"]["mean_tail_error"] < 2e-6
    assert latent["decode"]["mean_margin"] == 0.0      # tokens cannot tell
    keys = checked(lanes=perturb("cached_rope_key"))
    assert keys["correct"] is False
    assert keys["decode"]["mean_tail_error"] > 1e-3
    assert keys["decode"]["mean_state_error"] < 2e-6
    system.live_lanes = real_lanes
    system.scheduler.lanes_at_exit = None
    none = serve_closed_decoded.check(env, system, PLAN, record)
    assert none["correct"] is False and none["decode"]["lanes"] == 0


@pytest.mark.parametrize("control", ["bf16_compute", "int8_weights"])
def test_check_fails_a_lower_precision(control):
    """The two controls the cell's limits were set against, at the tiny
    size where the float32 system reads ~1e-6: the whole model computed in
    bfloat16 (softmax inputs, router inputs and latents with it), and
    weights rounded to 8 bits a column (served rounded, the reference
    reading the originals through the builder's ``reference_params``),
    are both outside the limits on the latents the run left."""
    import jax
    import jax.numpy as jnp

    config = copy.deepcopy(TINY_DEEPSEEK)
    if control == "bf16_compute":
        config["serve"].update(dtype="bf16", compute_dtype="bfloat16")
    env = tiny_env(config, 12)
    system = deepseek_v2_serve.build(env, None)
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
                "kernel", "lm_head", "wi", "wg", "wo") else leaf
            for path, leaf in flat])
        system.reference_params = lambda: original
    record = serve_until(system, tiny_prompts(1), (6, 30, 6, 30), 12)
    verdict = serve_closed_decoded.check(env, system, PLAN, record)
    decode = verdict["decode"]
    assert verdict["correct"] is False and verdict["failed"] == 0
    assert decode["lanes"] == 2
    assert decode["mean_state_error"] \
        > 10 * decode["limits"]["mean_state_error_max"]
    assert decode["mean_tail_error"] \
        > 10 * decode["limits"]["mean_tail_error_max"]
