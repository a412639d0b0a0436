"""The selected-attention serve cell's benchmark files: its configuration
against the catalog row, ``dsa_flops.py`` against a hand count and the
program's parameter tree, the nine new layer-metric files on a synthetic
context, the new traffic kind's generator, the tiny cell through the
harness, and the kind's ``check`` against a swapped token, a lane that took
in other tokens and a perturbed index key."""
import copy
import json
import os
import types

import numpy as np
import pytest

import rehearsal
from keye_vl_tiny import STAND_IN, TINY_CELL, TINY_KEYE, TINY_RESIDENT
from perfbench import dsa_flops, flops, stats
from perfbench import program_spans as ps
from perfbench import trace_reduce as tr
from perfbench.builders import keye_vl_serve
from perfbench.readers import (
    cache_plan,
    decode_roofline_selected,
    scope_share,
    selected_attention_roofline,
    selected_share,
)
from perfbench.traffic_kinds import serve_closed, serve_resident

ROOT = stats.repo_root()
BENCH = stats.load_json(os.path.join(ROOT, "BENCHMARK.json"))
PEAK = stats.load_json(os.path.join(ROOT, "perfbench", "peaks.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
(CELL,) = STAND_IN
ENTRY = next(c for c in BENCH["configs"] if c["file"].endswith(
    "keye-vl-2.0-ep8-6layer.json"))
BODY = stats.load_json(os.path.join(ROOT, ENTRY["file"]))
TRAFFIC = stats.load_json(os.path.join(
    ROOT, "perfbench", "traffic", "serve-resident-longctx-32.json"))
NEW_METRICS = ["dsa_share_of_decode", "dsa_index_share_of_decode",
               "dsa_select_share_of_decode", "dsa_attn_share_of_decode",
               "dsa_index_roofline", "dsa_attn_roofline",
               "decode_roofline.dsa", "dsa_selected_share",
               "index_key_share_of_lane_cache"]
# the accepted per-layer metrics that list the cell: those it can read.
# The window holds no admission, so nothing of an admission's (its idle
# share, its medians, the queue's wait, a time to first token) is read
ACCEPTED = ["compiles_in_window.serve", "sched_lane_occupancy",
            "decode_step_ms_p50", "decode_ahead_share",
            "device_idle_share.serve", "hbm_peak_gb.serve",
            "idle_share.step_host", "scope_unattributed_share.serve",
            "setup_trace_s", "setup_lower_s", "setup_compile_or_load_s",
            "setup_programs_built", "setup_cache_misses",
            "setup_first_dispatch_s.serve", "moe_share_of_decode",
            "moe_experts_roofline.decode",
            "moe_expert_load_max_over_mean.serve",
            # the review's: the cell runs those scopes (the index key's row
            # writes and ``chosen_rows`` under kv_cache_write, ``visible``
            # under kv_cache_read, a whole leaf moved under the carry tag)
            "kv_cache_share_of_decode"]
NOT_READ = ["idle_share.admit", "queue_wait_ms_p50", "ttft_p95_ms.closed",
            "admit_ms_p50", "ttft_p50_ms", "gap_p50_ms",
            "admit_dispatch_ms_p50", "admit_splice_ms_p50",
            "prefill_device_ms_p50", "kv_blocks_read_share",
            "decode_roofline"]
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
DECODE = ["deepspeed_tpu.inference.engine", "PROGRAM_DECODE_K"]
SIZES = keye_vl_serve.attention_sizes(BODY)
READERS = {"scope_share": scope_share, "cache_plan": cache_plan,
           "selected_attention_roofline": selected_attention_roofline,
           "decode_roofline_selected": decode_roofline_selected,
           "selected_share": selected_share}


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
    assert (BODY["num_hidden_layers"], BODY["num_experts"],
            BODY["vocab_size"]) == (6, 16, 18992)
    # six of 48 layers; 16 of the 128 experts the router scores; an eighth
    # of the vocabulary
    assert BODY["moe"]["routed_over"] == 128 == BODY["num_local_experts"] \
        == 8 * BODY["num_experts"]
    assert BODY["moe"]["experts_held"] == [0, 16]
    assert 8 * BODY["vocab_size"] == BODY["published"]["vocab_size"]
    assert "learned sparse attention" in row["mechanisms"]


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
        "decoding", "weights", "qk_norm", "indexer_inputs", "indexer_norm",
        "indexer_rotary", "indexer_weights_scale", "chunk_sizes",
        "selection", "cache", "text_positions", "prompt_bucket",
        "cache_positions"}
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


def test_the_file_reckons_its_own_bytes():
    b = BODY["bytes"]
    layer = (dsa_flops.attention_params(2048, 32, 4, 128)
             + dsa_flops.indexer_params(2048, 16, 64) + 2048 * 128
             + 16 * 3 * 2048 * 768 + 2 * 2048)
    assert b["parameters"] == 6 * layer + 2 * 18992 * 2048 + 2048
    assert round(b["parameters"] * 2 / 1e9, 2) == 1.32
    assert b["cache_bytes_per_position_and_layer"] == 2176 \
        == 2 * (2 * 4 * 128 + 64)
    assert b["lane_cache_bytes"] == 32 * 24576 * 6 * 2176
    assert 0.70 < (b["parameters"] * 2 + b["lane_cache_bytes"]) / 16e9 < 0.74


def test_serve_section_states_the_cache_and_the_limits():
    s = BODY["serve"]
    assert (s["dtype"], s["param_dtype"], s["compute_dtype"]) == (
        "bf16", "bfloat16", "bfloat16")
    assert s["cache_positions"] == 24576 == TRAFFIC["max_positions"]
    assert s["serving"] == {"slots": 32, "prompt_bucket": 2048}
    check = s["decode_check"]
    limits = {k: check[k] for k in (
        "mean_margin_max", "share_within_tolerance_min",
        "largest_margin_max", "mean_state_error_max",
        "first_layer_head_state_error_max", "mean_tail_error_max",
        "mean_selection_miss_max", "mean_choice_miss_max")}
    assert all(0 < v <= 1 for v in limits.values())
    assert len(check["why"]) > 200 and len(s["first_token_tolerance_why"]) > 50
    # each limit lies between what the system read over its seeds and what
    # a lower precision read, with room on both sides
    system, lower = check["system_readings"], check["lower_precision_readings"]
    assert system["runs"] >= 12 and system["selection_runs"] >= 10
    assert set(lower) == {"bf16_index_scores", "int8_weights"}
    stats_ = ("mean_margin", "largest_margin", "mean_state_error",
              "first_layer_head_state_error", "mean_tail_error",
              "mean_selection_miss", "mean_choice_miss")
    for stat in stats_:
        assert system[stat + "_largest"] < limits[stat + "_max"], stat
    assert system["share_within_tolerance_smallest"] \
        > limits["share_within_tolerance_min"]
    assert system["first_token_margin_largest"] < s["first_token_tolerance"]
    told = {}
    for name, read in lower.items():
        assert read["runs"] >= 2
        outside = [stat for stat in stats_
                   if read.get(stat + "_smallest", 0) > limits[stat + "_max"]]
        if read["share_within_tolerance_largest"] \
                < limits["share_within_tolerance_min"]:
            outside.append("share_within_tolerance")
        told[name] = outside
        assert read["refused"] is True and outside, name
    # 8-bit weights are refused in every one of their runs, by the first
    # layer's rows with room; the indexer's scores in bfloat16 by the one
    # statistic that reads the step's choice against its own query, in
    # both of the runs that read it, and by no other
    assert "first_layer_head_state_error" in told["int8_weights"]
    assert told["bf16_index_scores"] == ["mean_choice_miss"]
    assert lower["bf16_index_scores"]["choice_runs"] == 2
    assert limits["mean_choice_miss_max"] * 4 \
        < lower["bf16_index_scores"]["mean_choice_miss_smallest"]
    # a selection that is wrong, planted in the decode step alone: the
    # statistic reads one query a lane and layer, so its limit stands well
    # clear of both (at 0.03 it lay inside the system's own range: one run
    # of seven of the driver's seed 1710134797 read 0.0303)
    planted = check["planted_selection_faults"]
    assert system["selection_runs"] >= 17 and system["choice_runs"] >= 9
    assert 0.03 < system["mean_selection_miss_largest"] \
        < limits["mean_selection_miss_max"] / 4
    for fault in ("half_k", "newest_rows"):
        assert planted[fault]["refused"] is True
        assert planted[fault]["mean_selection_miss"] \
            > 5 * limits["mean_selection_miss_max"]
    assert "NOT MET" in check["why"]


def test_the_traffic_file_is_the_issues_mix():
    t = TRAFFIC
    assert t["kind"] == "serve_resident" and t["clients"] == 32
    lengths = t["prompt_lengths"]
    assert len(lengths) == 32 == len(set(lengths))
    want = [round(4097 * (16384 / 4097) ** ((i + 0.5) / 32))
            for i in range(32)]
    assert lengths == want and 4097 <= min(lengths) and max(lengths) <= 16384
    assert 8800 < sum(lengths) / 32 < 8900
    buckets = sorted({serve_closed.bucketed(n, 2048) for n in lengths})
    assert buckets == [6144, 8192, 10240, 12288, 14336, 16384]
    assert (t["output_tokens"], t["ramp_tokens"], t["prompt_bucket"],
            t["max_positions"], t["trace_seconds"],
            t["reference_samples"]) == (8192, 16, 2048, 24576, 8, 3)
    assert max(buckets) + t["output_tokens"] == t["max_positions"]
    assert len(t["why"]) > 100


def test_the_new_entries_are_appended_and_list_the_new_cell_alone():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-len(NEW_METRICS):] == NEW_METRICS
    for m in BENCH["per_layer"][-len(NEW_METRICS):]:
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["moves"] == "serve_out_tokens_per_s"
        assert m["layer"] == "decode step"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.exists(os.path.join(
            ROOT, "perfbench", "layer_metrics", m["name"] + ".json"))
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in ACCEPTED:
        assert by_name[name]["workloads"][-1] == CELL, name
    for name in NOT_READ:
        assert CELL not in by_name[name]["workloads"], name
    assert BENCH["configs"][-1] == ENTRY
    cell = BENCH["workloads"][-1]
    assert cell == {"name": CELL, "config": ENTRY["name"],
                    "traffic": "serve-resident-longctx-32", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "8x" in cell["why"]
    assert len(BENCH["workloads"]) == 10
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    end = {m["name"]: m for m in BENCH["end_to_end"]}
    assert end["serve_out_tokens_per_s"]["workloads"][-1] == CELL
    assert CELL not in end["gap_p95_ms"]["workloads"]
    assert CELL not in end["train_tokens_per_s_per_chip"]["workloads"]


# ---------------------------------------------------------------------------
# the counts
# ---------------------------------------------------------------------------
def test_counts_against_a_hand_count():
    assert dsa_flops.attention_params(2048, 32, 4, 128) \
        == 2048 * 5120 + 4096 * 2048 + 256 == 18_874_624
    assert dsa_flops.indexer_params(2048, 16, 64) \
        == 2048 * 1104 + 128 == 2_261_120
    # a live position's index key is 128 B a layer; 2 x 16 x 64 operations
    one = dsa_flops.index_step(1, 16, 64)
    assert one == {"flops": 2048.0, "bytes": 128.0}
    # a chosen position's k and v are 2,048 B a layer; 4 x 32 x 128
    row = dsa_flops.chosen_attention_step(1, 32, 4, 128)
    assert row == {"flops": 16384.0, "bytes": 2048.0}
    assert dsa_flops.kv_bytes_per_position(6, 4, 128) == 6 * 2048
    assert dsa_flops.index_key_bytes_per_position(6, 64) == 6 * 128
    weights = dsa_flops.decode_weight_bytes(
        6, 18992, 2048, 768, 16, 128, **SIZES)
    by_hand = 2 * (6 * (18_874_624 + 2_261_120 + 4096 + 16 * 3 * 2048 * 768)
                   + 18992 * 2048 + 2048) + 6 * 2048 * 128 * 4
    assert weights == by_hand and 1.24e9 < weights < 1.25e9
    # the issue's step: 32 lanes of ~13k live positions, 2,048 chosen each
    step = dsa_flops.decode_step_bytes(weights, 32 * 13000, 32 * 2048, 6,
                                       4, 128, 64)
    assert step == weights + 32 * 13000 * 768 + 32 * 2048 * 12288
    assert 2.8 < step / 819e9 * 1e3 < 3.0         # ms at the v5e's 819 GB/s


def test_counts_agree_with_the_programs_parameter_tree():
    """``decode_weight_bytes`` less the head it counts once, against the
    bytes of the program's own parameters (``jax.eval_shape`` at the
    published widths: nothing is allocated)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer_lm import GPT

    cfg = keye_vl_serve.model_config(BODY)
    shapes = jax.eval_shape(
        lambda: GPT(cfg).init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 8), jnp.int32)))["params"]
    total = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    assert total == BODY["bytes"]["parameters"]
    counted = dsa_flops.decode_weight_bytes(
        6, 18992, 2048, 768, 16, 128, **SIZES)
    # the step reads all but the embedding table (one row a lane), the
    # routers as float32
    assert counted == 2 * (total - 18992 * 2048) + 6 * 2048 * 128 * 2
    leaves = {leaf.name for leaf in cfg.cache_leaves}
    assert leaves == {"cached_key", "cached_value", "cached_index_key",
                      "chosen_rows", "choice_query", "choice_weights"}


# ---------------------------------------------------------------------------
# the readers, on a synthetic context
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


LAYER = "jit(decode_k)/while/body/GPT/h/while/body/block/"
ROWS = [(LAYER + "attn/indexer/dsa_index_proj/wq/dot", 1.0),
        (LAYER + "attn/dsa_index_scores/dot", 2.0),
        (LAYER + "attn/dsa_select/sort", 3.0),
        (LAYER + "attn/dsa_attn/gather", 10.0),
        (LAYER + "attn/c_attn/dot", 2.0),
        (LAYER + "mlp/moe_experts/experts/ragged-dot-gmm", 2.0)]
INFO = {"slots": 32, "decode_program": "jit_decode_k",
        "weight_bytes": 1.24e9, "kv_bytes_per_position": 12288.0,
        "selected_attention": dict(SIZES, layers=6, itemsize=2, topk=2048)}
LIVE, CHOSEN = 32 * 13000.0, 32 * 2048.0


@pytest.mark.parametrize("name,share", [
    ("dsa_share_of_decode", 80.0), ("dsa_index_share_of_decode", 15.0),
    ("dsa_select_share_of_decode", 15.0), ("dsa_attn_share_of_decode", 50.0)])
def test_the_share_files_read_their_scopes_of_the_decode_program(name, share):
    reader, args = spec_of(name)
    assert args["program"] == DECODE
    assert reader.read(_ctx(ROWS), **args) == pytest.approx(share)
    # a program without the scopes (the parent's): nothing, and no raise
    assert reader.read(_ctx(ROWS[4:]), **args) in (None, 0.0)
    assert reader.read(_ctx(), **args) is None


@pytest.mark.parametrize("name,which,scope_row", [
    ("dsa_index_roofline", "index", 1), ("dsa_attn_roofline", "attention", 3)])
def test_the_rooflines_count_what_the_mechanism_needs(name, which, scope_row):
    reader, args = spec_of(name)
    assert args["which"] == which and args["program"] == DECODE
    counts = dict(mean_live_positions=lambda: LIVE,
                  mean_selected_positions=lambda: CHOSEN)
    # one run of the program in the window, six layers
    ctx = _ctx(ROWS, modules=[(0.0, 1e8)], info=INFO, **counts)
    got = reader.read(ctx, **args)
    need = dsa_flops.index_step(LIVE, 16, 64) if which == "index" \
        else dsa_flops.chosen_attention_step(CHOSEN, 32, 4, 128)
    least, bound = flops.roofline_seconds(need["flops"], need["bytes"],
                                          PEAK["TPU v5 lite"])
    assert bound == "memory"
    assert got == pytest.approx(100.0 * least * 6 / ROWS[scope_row][1])
    note = ctx.notes["selected_attention_roofline:" + which]
    assert note["layers"] == 6 and note["runs"] == 1
    # a gather that reads more than the rows takes longer: a lower share,
    # and at the mechanism's own bytes and the chip's bandwidth exactly 100
    exact = _ctx([(ROWS[scope_row][0], least * 6)], modules=[(0.0, 1e8)],
                 info=INFO, **counts)
    assert reader.read(exact, **args) == pytest.approx(100.0)
    # nothing to read: no sizes, no counts, no scopes
    assert reader.read(_ctx(ROWS, modules=[(0.0, 1e8)], **counts),
                       **args) is None
    assert reader.read(_ctx(ROWS, modules=[(0.0, 1e8)], info=INFO),
                       **args) is None
    assert reader.read(_ctx(ROWS[4:], modules=[(0.0, 1e8)], info=INFO,
                            **counts), **args) is None


def test_the_step_roofline_and_the_chosen_share():
    counts = dict(mean_live_positions=lambda: LIVE,
                  mean_selected_positions=lambda: CHOSEN)
    reader, args = spec_of("decode_roofline.dsa")
    # two steps of 20 ms
    ctx = _ctx(modules=[(0.0, 2e7), (3e7, 5e7)], info=INFO, **counts)
    nbytes = 1.24e9 + LIVE * 768 + CHOSEN * 12288
    assert reader.read(ctx, **args) == pytest.approx(
        100.0 * (nbytes / 819e9 * 1e3) / 20.0)
    assert ctx.notes["decode_roofline_selected"]["bytes"] == nbytes
    assert reader.read(_ctx(modules=[(0.0, 2e7)], info=INFO), **args) is None
    reader, args = spec_of("dsa_selected_share")
    assert reader.read(_ctx(info=INFO, **counts), **args) \
        == pytest.approx(100.0 * 2048 / 13000)
    assert reader.read(_ctx(info=INFO), **args) is None


def test_the_counter_file_reads_the_lane_layouts_bytes():
    reader, args = spec_of("index_key_share_of_lane_cache")
    lane = 24576 * 6
    plan = {"kind": "serve.cache_plan", "slots": 32,
            "index_key_bytes_per_lane": lane * 128,
            "bytes_per_lane": lane * 2176 + lane + 6 * 4}
    got = reader.read(_ctx(cache_plan=plan), **args)
    assert got == pytest.approx(100 * 128 / 2177, rel=1e-4)
    assert reader.read(_ctx(cache_plan=None), **args) is None


# ---------------------------------------------------------------------------
# the new kind's generator
# ---------------------------------------------------------------------------
def plan_of(seed, traffic=TINY_RESIDENT):
    return serve_resident.plan(types.SimpleNamespace(
        traffic=traffic, config=TINY_KEYE, seed=seed))


def test_every_seed_offers_the_same_lengths_in_another_order():
    a, b = plan_of(2 ** 31 + 5), plan_of(2 ** 31 + 6)
    first_a = [a.next_request() for _ in range(4)]
    first_b = [b.next_request() for _ in range(4)]
    assert sorted(len(p) for p, _ in first_a) == [9, 14, 20, 30] \
        == sorted(len(p) for p, _ in first_b)
    assert [p for p, _ in first_a] != [p for p, _ in first_b]
    assert {n for _, n in first_a + first_b} == {32}
    again = plan_of(2 ** 31 + 5)
    assert [again.next_request() for _ in range(4)] == first_a
    # the next cycle is another order of the same lengths
    second = [a.next_request() for _ in range(4)]
    assert sorted(len(p) for p, _ in second) == [9, 14, 20, 30]
    assert all(0 <= t < 128 for p, _ in first_a + second for t in p)
    for bad in (dict(output_tokens=40), dict(ramp_tokens=32),
                dict(clients=3)):
        with pytest.raises(ValueError):
            plan_of(1, dict(TINY_RESIDENT, **bad))
    assert serve_resident.ROLE == "serve"
    assert serve_resident.series is serve_closed.series
    for fn in ("plan", "warm_up", "drive", "series", "end_to_end", "check"):
        assert callable(getattr(serve_resident, fn))


def test_end_to_end_needs_no_admission_in_the_window():
    s = {"tokens": 900, "window_s": 2.0, "ttft_ms": [], "admit_ms": [],
         "gap_ms": [10.0, 12.0, 11.0], "requests_completed": 0}
    got = serve_resident.end_to_end(s)
    assert got["serve_out_tokens_per_s"] == 450.0
    assert got["gap_p50_ms"] == 11.0 and got["n_ttft"] == 0
    assert not [k for k in got if k.startswith("ttft_")]
    closed = dict(s, ttft_ms=[5.0, 7.0])
    assert serve_resident.end_to_end(closed) \
        == serve_closed.end_to_end(closed)


class LockstepScheduler:
    """A scheduler's loop as ``drive`` sees it: ``poll_fn`` first, then
    every queued request takes a lane, then every lane streams one token;
    a clock that the test reads moves by one an iteration."""

    def __init__(self, clock):
        self.clock, self.queue, self.lanes, self.ids = clock, [], [], 0

    def submit(self, prompt, max_new_tokens, stream_callback):
        self.ids += 1
        self.queue.append([self.ids, max_new_tokens, stream_callback])
        return self.ids

    def run(self, poll_fn):
        while self.queue or self.lanes:
            self.clock.now += 0.5
            poll_fn()
            self.clock.now += 0.5
            self.lanes, self.queue = self.lanes + self.queue, []
            for lane in list(self.lanes):
                lane[1] -= 1
                if not lane[1]:
                    self.lanes.remove(lane)
                lane[2](lane[0], 1, not lane[1])


@pytest.mark.parametrize("seconds,streamed,after", [(10, 12, 0), (30, 1, 1)])
def test_the_run_ends_with_a_request_in_a_lane(monkeypatch, seconds,
                                               streamed, after):
    """The mix's requests ask for one number of tokens and are admitted
    together, so all end at one step (the 32nd), and at the poll after it
    their successors are queued and no lane holds a request. A window
    that closes at that very poll closes there, and the run goes on for the
    iteration that admits them: what it leaves is for ``check`` to read.
    Any other window ends the run where it closes."""
    import contextlib

    clock = types.SimpleNamespace(now=0.0, monotonic=lambda: clock.now)
    monkeypatch.setattr(serve_resident, "time", clock)
    env = types.SimpleNamespace(
        traffic=TINY_RESIDENT, seconds=seconds, trace=False, tracing=False,
        t_open=None, t_close=None, span=lambda name: contextlib.nullcontext())
    env.open_window = lambda **kw: setattr(env, "t_open", clock.now)
    env.close_window = lambda: setattr(env, "t_close", clock.now)
    system = types.SimpleNamespace(scheduler=LockstepScheduler(clock))
    record = serve_resident.drive(env, system, plan_of(5))
    # the ramp is two tokens a request: the window opens at the third poll
    assert (env.t_open, env.t_close) == (2.5, 2.5 + seconds)
    assert len(record["in_flight"]) == 4
    assert [len(r.tokens) for r in record["in_flight"]] == [streamed] * 4
    assert [sum(t > env.t_close for t in r.times)
            for r in record["in_flight"]] == [after] * 4
    assert len(record["done"]) == (4 if after else 0)


# ---------------------------------------------------------------------------
# the cell through the harness, and the kind's verdict
# ---------------------------------------------------------------------------
def test_the_stand_in_is_registered_for_any_subset_of_the_tests():
    assert rehearsal.CONFIGS[TINY_KEYE["name"]] is TINY_KEYE
    assert rehearsal.TRAFFIC[TINY_CELL["traffic"]] is TINY_RESIDENT
    assert TINY_CELL in rehearsal.CELLS
    assert rehearsal.STAND_IN[CELL] == TINY_CELL["name"]
    # every published key of the real file is in the tiny one
    published = set(BODY) - {"assumed", "deployment", "published", "bytes"}
    assert published <= set(TINY_KEYE), published - set(TINY_KEYE)
    assert set(TRAFFIC) - {"why", "grid"} <= set(TINY_RESIDENT)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(tmp_path, trace):
    root = rehearsal.make_root(tmp_path)
    rc, last, err = rehearsal.run_cell(root, TINY_CELL["name"], trace=trace,
                                       seed=2 ** 31 + 47, seconds=1.5)
    assert rc == 0, err[-2000:]
    assert last["correct"] is True and last["rehearsal"] is True
    assert last["failed"] == 0 and last["attempted"] >= 4
    if trace:
        # no device plane on the CPU: the trace's readers find nothing and
        # say nothing; the program's counters are read
        metrics = last["metrics"]
        assert metrics["compiles_in_window.serve"]["value"] == 0
        assert metrics["sched_lane_occupancy"]["value"] > 90
        assert 10 < metrics["index_key_share_of_lane_cache"]["value"] < 12
        # contexts of 9-62 positions against a topk of 8
        assert 10 < metrics["dsa_selected_share"]["value"] < 60
        assert metrics["moe_expert_load_max_over_mean.serve"]["value"] >= 1.0
        assert not (set(NEW_METRICS) - {
            "dsa_selected_share", "index_key_share_of_lane_cache"}) \
            & set(metrics)
    else:
        assert set(last["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}
    run = next(json.loads(ln) for ln in err.splitlines()
               if ln.startswith("{") and '"event": "run"' in ln)
    assert run["compiles_in_window"] == 0
    assert run["verdict"]["decode"]["ok"] is True
    assert run["verdict"]["decode"]["positions"] > 0
    assert run["verdict"]["in_flight_at_close"] == 4
    assert run["verdict"]["decode"]["lanes"] == 2


class WindowEnds(Exception):
    pass


def serve_until(system, prompts, want, polls):
    """What ``serve_resident.drive`` records, for requests submitted at
    once and a run that ``poll_fn`` ends at its ``polls``-th call with
    every request still in its lane."""
    sched, by_rid, count = system.scheduler, {}, []

    def on_token(rid, token, ended):
        req = by_rid[rid]
        req.times.append(2.0 + len(req.times))
        req.tokens.append(int(token))

    def poll():
        count.append(1)
        if len(count) == polls:
            raise WindowEnds

    for i, p in enumerate(prompts):
        rid = sched.submit(p, max_new_tokens=want, stream_callback=on_token)
        by_rid[rid] = serve_closed.Req(client=i, prompt=p, want=want,
                                       ramp=True, t_submit=1.0)
    with pytest.raises(WindowEnds):
        sched.run(poll_fn=poll)
    sched._pending.clear()
    return {"done": [], "by_rid": by_rid, "in_flight": list(by_rid.values()),
            "events": []}


def test_check_fails_a_swapped_token_and_a_perturbed_index_key():
    """The tiny system is stopped with its four requests in their lanes,
    contexts of 20-50 positions against a ``topk`` of 8. ``check`` over
    that record is correct and has read two live lanes' rows out of the
    scheduler's cache against the reference's. With a served token swapped
    in a sampled lane's record it is not (the lane took in another token
    than its client was streamed, and the reference's margin there is not
    0); nor with a token outside the vocabulary, which is a failed
    request; nor where a lane's first-layer keys, or its index keys, are
    off by a hundredth. Without live lanes there is no verdict."""
    env = types.SimpleNamespace(
        config=TINY_KEYE, traffic=TINY_RESIDENT, seed=11, t_open=0.0,
        t_close=float("inf"))
    plan = types.SimpleNamespace(vocab=128)
    system = keye_vl_serve.build(env, None)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 128, size=n).tolist() for n in (9, 20, 14, 30)]
    try:
        record = serve_until(system, prompts, 32, 22)
    finally:
        system.unsubscribe(system.on_bus)
    assert system.cache_plan["slots"] == 4
    # the scheduler's own word for a decode step that walks no blocks;
    # which rows it read is the decode program's to say (``chosen_rows``)
    assert system.cache_plan["decode_attention"] == "einsum"
    assert system.cache_plan["index_key_bytes_per_lane"] == 2 * 64 * 8 * 4
    assert system.mean_selected_positions() is None     # before ``check``
    kept = system.scheduler.lanes_at_exit
    assert len(kept.live) == 4
    real_lanes = system.live_lanes

    def checked(edit=None, lanes=None):
        rec = copy.deepcopy(record)
        rec["by_rid"] = {rid: next(
            x for x in rec["in_flight"] if x.client == r.client)
            for rid, r in record["by_rid"].items()}
        if edit:
            edit(rec)
        system.scheduler.lanes_at_exit = kept     # ``check`` lets it go
        system.live_lanes = (lambda n, rng: lanes(real_lanes(n, rng))) \
            if lanes else real_lanes
        return serve_resident.check(env, system, plan, rec)

    def swap(k, to=None):
        def edit(rec):
            for i, r in enumerate(rec["in_flight"]):
                r.tokens[k] = (r.tokens[k] + 1 + i) % 128 if to is None \
                    else to
        return edit

    def perturb(leaf):
        def lanes(found):
            for lane in found:      # the first layer's rows
                rows = np.array(lane[leaf])
                rows[0] = rows[0] * 1.01
                lane[leaf] = rows
            return found
        return lanes

    good = checked()
    assert good["correct"] is True and good["attempted"] == 4
    assert good["failed"] == 0 and good["decode"]["lanes"] == 2
    assert good["decode"]["positions"] == sum(
        lane["taken_in"] for lane in good["live_lanes"]) > 30
    assert system.scheduler.lanes_at_exit is None
    # float32 against float32: the rows the timed steps left are the
    # reference's, to the order of the sums
    assert good["decode"]["mean_state_error"] < 2e-6
    assert good["decode"]["first_layer_head_state_error"] < 2e-6
    assert good["decode"]["mean_tail_error"] < 2e-6
    assert len(good["decode"]["first_layer_state_error_by_head"]) == 2
    # every lane's last step read topk rows, by the program's own account,
    # and the sampled lanes' are the reference's choice of their own keys
    assert system.chosen_at_close == dict.fromkeys(range(4), 8.0)
    assert system.mean_selected_positions() == 32.0 \
        < system.mean_live_positions()
    assert good["decode"]["selection_miss_by_layer"] == [0.0, 0.0] \
        == good["decode"]["selection_choice_miss_by_layer"]
    assert good["decode"]["mean_choice_miss"] == 0.0
    assert good["decode"]["selection_miss_reference_keys_by_layer"] \
        == [0.0, 0.0]
    assert good["decode"]["limits"]["mean_selection_miss_max"] == 0.01
    assert max(good["decode"]["selection_median_row_error_by_layer"]) < 2e-6
    bad = checked(swap(5))
    assert bad["correct"] is False and bad["failed"] == 0
    assert bad["live_lanes_streamed_their_tokens"] is False
    first = checked(swap(0))
    assert first["correct"] is False
    outside = checked(swap(3, to=128))
    assert outside["correct"] is False and outside["failed"] == 4
    longer = checked(lambda rec: [r.tokens.extend([1] * 40)
                                  for r in rec["in_flight"]])
    assert longer["failed"] == 4
    keys = checked(lanes=perturb("cached_key"))
    assert keys["correct"] is False and keys["failed"] == 0
    assert keys["decode"]["first_layer_head_state_error"] > 5e-3
    assert keys["decode"]["mean_tail_error"] < 2e-6
    assert keys["decode"]["mean_margin"] == 0.0        # tokens cannot tell
    index = checked(lanes=perturb("cached_index_key"))
    assert index["correct"] is False
    assert index["decode"]["mean_tail_error"] == pytest.approx(
        0.005, rel=0.5)
    assert index["decode"]["mean_state_error"] < 2e-6

    # a lane whose last step read other rows than the indexer's choice:
    # the newest topk, then one row too few, then a row nobody wrote
    def rows_read(edit):
        def lanes(found):
            for lane in found:
                rows = np.array(lane["chosen_rows"])
                edit(rows, int(np.flatnonzero(lane["valid"][0])[-1]))
                lane["chosen_rows"] = rows
            return found
        return lanes

    def newest(rows, last):
        rows[0] = np.arange(last - 7, last + 1)

    def one_short(rows, last):
        rows[1, 0] = -1

    def unwritten(rows, last):
        rows[1, 0] = last + 1

    for edit, layer in ((newest, 0), (one_short, 1), (unwritten, 1)):
        other = checked(lanes=rows_read(edit))
        assert other["correct"] is False and other["failed"] == 0
        assert other["decode"]["selection_miss_by_layer"][layer] > 0.4
        assert other["decode"]["selection_miss_by_layer"][1 - layer] == 0.0
        assert other["decode"]["mean_state_error"] < 2e-6
    system.live_lanes = real_lanes

    system.scheduler.lanes_at_exit = None
    none = serve_resident.check(env, system, plan, record)
    assert none["correct"] is False and none["decode"]["lanes"] == 0


@pytest.mark.parametrize("fault", ["half_k", "newest_rows"])
def test_check_refuses_a_decode_program_that_chooses_otherwise(
        monkeypatch, fault):
    """The same stop, of a system whose DECODE step chooses half of
    ``topk``, or takes the newest rows whatever the scores (planted in
    ops/indexed_attention.py before the programs are traced; the prefill
    chooses as it should). The rows it read are the program's own account,
    and ``check`` holds them to the reference's choice. (Scores rounded to
    bfloat16 move no set of 8 among 50; that control runs on the chip, at
    2,048 of 20,000.)"""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops import indexed_attention as ia

    real_choose = ia.choose

    def half_k(scores, visible, topk):
        rows, ok = real_choose(scores, visible, topk)
        return rows, ok & (jnp.arange(topk) < topk // 2)

    def newest_rows(scores, visible, topk):
        at = jnp.arange(scores.shape[-1], dtype=jnp.float32)
        return real_choose(jnp.broadcast_to(at, scores.shape), visible, topk)

    monkeypatch.setattr(ia, "choose", locals()[fault])
    jax.clear_caches()
    env = types.SimpleNamespace(
        config=TINY_KEYE, traffic=TINY_RESIDENT, seed=11, t_open=0.0,
        t_close=float("inf"))
    system = keye_vl_serve.build(env, None)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 128, size=n).tolist() for n in (9, 20, 14, 30)]
    try:
        record = serve_until(system, prompts, 32, 22)
    finally:
        system.unsubscribe(system.on_bus)
    got = serve_resident.check(env, system, types.SimpleNamespace(vocab=128),
                               record)
    jax.clear_caches()
    assert got["failed"] == 0 and got["correct"] is False
    miss = got["decode"]["mean_selection_miss"]
    assert miss > got["decode"]["limits"]["mean_selection_miss_max"]
    assert miss == 1.0 if fault == "half_k" else miss > 0.4
    # by its own query's scores too: the step did not take their top_k
    assert got["decode"]["mean_choice_miss"] == pytest.approx(miss, abs=0.1)
