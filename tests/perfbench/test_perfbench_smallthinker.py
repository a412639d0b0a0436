"""The window-and-full TRAINING cell's benchmark files: its configuration
against the catalog row, ``swa_flops.py`` against hand counts and against
the causal counts at ``window >= t``, the five new layer-metric files on a
synthetic context, the new traffic file, the tiny cell through the harness
and the reference check's controls at the tiny size. Every entry of
``BENCHMARK.json`` is found BY NAME: nothing here says where in a list an
entry stands or how long a list is, so the next appended cell breaks none
of it."""
import importlib
import json
import os
import types

import pytest

import rehearsal
from perfbench import flops, stats, swa_flops
from perfbench import trace_reduce as tr
from perfbench.builders import smallthinker_train
from perfbench.readers import (
    expert_load_share,
    flash_roofline_named,
    named_op_share,
    window_flash_roofline,
)
from perfbench.reference import smallthinker_check
from perfbench.traffic_kinds import train_repeat
from smallthinker_tiny import (
    CELL,
    CONFIG,
    STAND_IN,
    TINY_CELL,
    TINY_SMALLTHINKER,
)

ROOT = stats.repo_root()
BENCH = stats.load_json(os.path.join(ROOT, "BENCHMARK.json"))
PEAK = stats.load_json(os.path.join(ROOT, "perfbench", "peaks.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
ENTRY = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
BODY = stats.load_json(os.path.join(ROOT, ENTRY["file"]))
TRAFFIC = stats.load_json(os.path.join(
    ROOT, "perfbench", "traffic", "train-seq16384-micro1.json"))
NEW_METRICS = {
    "window_flash_share_of_step": ("named_op_share", "device_trace"),
    "window_flash_roofline": ("window_flash_roofline", "device_trace"),
    "window_attn_share_of_step": ("scope_share", "device_trace"),
    "full_attn_share_of_step": ("scope_share", "device_trace"),
    "moe_routed_here_share": ("expert_load_share", "program_counter")}
# the accepted metrics of a training cell the new cell is appended to
LISTED = [
    "compiles_in_window.train", "train_step_ms_p50",
    "train_step_device_ms_p50", "train_mfu", "device_idle_share.train",
    "hbm_peak_gb.train", "idle_share.post_step.train",
    "optimizer_share_of_step", "lm_head_ce_share_of_step",
    "recompute_share_of_step", "scope_unattributed_share.train",
    "moe_share_of_step", "moe_dispatch_share_of_step",
    "moe_expert_load_max_over_mean", "moe_tokens_dropped", "setup_trace_s",
    "setup_lower_s", "setup_compile_or_load_s", "setup_programs_built",
    "setup_cache_misses", "flash_share_of_step.moe", "flash_roofline.moe"]


def catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog beside the model-configs guide is not here")
    with open(CATALOG, encoding="utf-8") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "SmallThinker-21BA3B-Instruct")


def spec_of(name):
    spec = stats.load_json(os.path.join(
        ROOT, "perfbench", "layer_metrics", name + ".json"))
    return importlib.import_module(
        "perfbench.readers." + spec["reader"]), spec.get("args", {})


# --- the configuration ------------------------------------------------------
def test_every_published_key_is_in_the_file_under_its_key():
    row = catalog_row()
    assert ENTRY["source"] == BODY["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in ("num_hidden_layers", "vocab_size"):
            assert key in BODY and BODY[key] == value, key
    # the router keeps its 64 outputs: the held share is the cut
    assert BODY["moe_num_primary_experts"] == 64
    assert BODY["moe"] == {"routed_over": 64, "experts_held": [0, 16],
                           "expert_activation": "relu",
                           "router_input": "block"}
    assert BODY["published"] == {k: row["config"][k] for k in REDUCED}
    assert BODY["vocab_size"] * 4 == row["config"]["vocab_size"]
    assert BODY["model"]["vocab_size"] == BODY["vocab_size"] == 37984
    # whole periods of the published layout
    n = BODY["num_hidden_layers"]
    assert n % 4 == 0 and n >= 4
    assert BODY["sliding_window_layout"][:n] == [0, 1, 1, 1] * (n // 4)
    assert smallthinker_train.layer_types(BODY) == (
        "attention", "window", "window", "window") * (n // 4)
    assert {"mixture of experts", "window and full attention mixed"} \
        <= set(row["mechanisms"])


def test_reduced_is_exactly_what_differs_from_the_catalog():
    """What ``test_configuration_entry_and_file`` checks, for a
    configuration that is cut: entry and file agree, ``reduced`` names keys
    of the file and no width, the file says what it assumed and which
    deployment it stands for, one cell runs it, its builder exists; and,
    where the catalog has the row, the cut keys are all that differ."""
    assert BODY["name"] == ENTRY["name"]
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
        "router_input", "rotary", "router_weights", "auxiliary_losses",
        "secondary_experts", "expert_activation", "optimizer", "precision",
        "weights"}
    assert all(len(why) > 10 for why in BODY["assumed"].values())
    assert "expert parallel 4" in BODY["deployment"]
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == CONFIG] == [CELL]
    row = catalog_row()
    differs = sorted(k for k, v in row["config"].items()
                     if BODY.get(k, "missing") != v)
    assert differs == ["num_hidden_layers", "vocab_size"]


def test_the_train_section_is_the_accepted_moe_cells_but_for_the_head():
    olmoe = stats.load_json(os.path.join(
        ROOT, "perfbench", "configs", "olmoe-1b-7b-3layer.json"))["train"]
    mine = dict(BODY["train"])
    assert mine.pop("fused_head_ce") == 2048
    assert mine == olmoe
    ref = BODY["reference"]
    assert ref["module"] == "smallthinker" and ref["batch"] == [1, 8192]
    assert set(ref["limits"]) in (set(), {
        "loss", "agreement", *smallthinker_check.GROUPS})


def test_the_traffic_file():
    assert TRAFFIC["kind"] == "train_repeat" == train_repeat.__name__.rsplit(
        ".", 1)[-1]
    assert {k: TRAFFIC[k] for k in TRAFFIC if k != "why"} == {
        "kind": "train_repeat", "seq": 16384, "micro_batch_per_chip": 1,
        "labels": "next_token", "warm_up_steps": 3, "trace_seconds": 6,
        "loss_margin": 0.1}
    assert TRAFFIC["seq"] == BODY["max_position_embeddings"]


def test_the_entries_list_the_cell_by_name():
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": CONFIG,
                    "traffic": "train-seq16384-micro1", "chips": 1,
                    "why": cell["why"]}
    assert 1 <= len(cell["why"]) <= 200
    end_to_end = {m["name"]: m for m in BENCH["end_to_end"]}
    assert CELL in end_to_end["train_tokens_per_s_per_chip"]["workloads"]
    assert "workloads" not in end_to_end["setup_s"]
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    for name, (reader, source) in NEW_METRICS.items():
        entry = per_layer[name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_tokens_per_s_per_chip"
        assert entry["source"] == source and entry["unit"] == "%"
        assert spec_of(name)[0].__name__.endswith("." + reader)
    listed = [m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", []) and m["name"] not in
              NEW_METRICS]
    assert set(listed) <= set(LISTED) | {"moe_experts_roofline"}
    for name in listed:
        assert per_layer[name]["moves"] in (
            "train_tokens_per_s_per_chip", "setup_s")


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_each_new_metric_has_a_file_and_an_entry(name):
    spec = stats.load_json(os.path.join(
        ROOT, "perfbench", "layer_metrics", name + ".json"))
    assert set(spec) == {"reader", "args", "how"} and len(spec["how"]) > 40
    assert spec["reader"] == NEW_METRICS[name][0]
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"]
                              if m["name"] not in NEW_METRICS}


# --- the counts -------------------------------------------------------------
def test_window_pairs_by_hand_and_at_the_causal_limit():
    assert swa_flops.window_pairs(8, 3) == 1 + 2 + 3 * 6
    assert swa_flops.window_pairs(16384, 4096) == sum(
        min(i + 1, 4096) for i in range(16384))
    for t in (8, 1024):
        assert swa_flops.window_pairs(t, t) == swa_flops.window_pairs(
            t, 10 * t) == t * (t + 1) / 2


@pytest.mark.parametrize("kind", sorted(flops.FLASH_MATMULS))
def test_a_window_calls_counts(kind):
    bh, kv, t, d, w = 28, 4, 16384, 128, 4096
    pairs = w * t - w * (w - 1) / 2
    assert swa_flops.window_flash_call_flops(kind, bh, t, d, w) == \
        2 * bh * pairs * d * flops.FLASH_MATMULS[kind]
    # at window >= t: the causal count, whose triangle leaves half the
    # diagonal out (t * t / 2 pairs where the exact count has t more halves)
    causal = flops.flash_call_flops(kind, bh, t, d, True)
    assert swa_flops.window_flash_call_flops(kind, bh, t, d, t) == \
        pytest.approx(causal * (1 + 1 / t))
    # a window of a quarter: 7/16 of the causal triangle, less an edge
    assert swa_flops.window_flash_call_flops(kind, bh, t, d, w) / causal == \
        pytest.approx(7 / 16, rel=1e-3)
    # bytes: K and V at their own heads; with one KV head a query head and
    # no o or dk/dv beside, no more than the causal call's
    tensors = {"fwd": 2, "bwd_dq": 3, "bwd_dkv": 4}[kind]
    assert swa_flops.window_flash_call_bytes(kind, bh, kv, t, d) == \
        (tensors * bh + 2 * kv) * t * d * 2
    assert swa_flops.window_flash_call_bytes(kind, bh, bh, t, d) <= \
        flops.flash_call_bytes(kind, bh, t, d)


def test_the_models_flops_per_token_by_hand():
    kinds = smallthinker_train.layer_types(BODY)
    got = swa_flops.train_flops_per_token(
        kinds, 2560, 28, 4, 128, 768, 64, 6 * 16 / 64, 37984, 16384, 4096)
    attention = 2560 * 128 * (28 + 4 + 4 + 28)      # q, k, v, o
    layer = attention + 2560 * 64 + 1.5 * 3 * 2560 * 768
    full = 16385 / 2
    band = 4096 - 4096 * 4095 / 2 / 16384
    pairs = sum(band if k == "window" else full for k in kinds)
    assert got == pytest.approx(
        6 * len(kinds) * layer + 12 * 28 * 128 * pairs + 6 * 37984 * 2560)
    # ISSUE 63's arithmetic: scores 117 and 51 MFLOP a token forward in a
    # full and a window layer, matrices 42, held experts 18
    assert 4 * 28 * 128 * full / 1e6 == pytest.approx(117, abs=1)
    assert 4 * 28 * 128 * band / 1e6 == pytest.approx(51, abs=1)
    assert 2 * attention / 1e6 == pytest.approx(42, abs=1)
    assert 2 * 1.5 * 3 * 2560 * 768 / 1e6 == pytest.approx(18, abs=1)
    # a stack without a window would count the full layer's pairs four times
    assert swa_flops.attention_pairs_per_token(
        ("attention",) * 4, 16384, 4096) / pairs * (len(kinds) // 4) > 1.7


# --- the readers on a synthetic context ------------------------------------
def _ctx(ops, info, **more):
    red = tr.Reduced(devices={0: tr.Device(ops=ops)}, window=(0.0, 1e12))
    return types.SimpleNamespace(
        red=red, system=types.SimpleNamespace(info=info, **more),
        env=types.SimpleNamespace(peak=PEAK["TPU v5 lite"]), notes={},
        series={})


CALL = ' custom-call(%q), custom_call_target="tpu_custom_call"'
FWD = "%w = (bf16[28,16384,128], f32[28,16384,8])" + CALL
DQ = "%w = bf16[28,16384,128]" + CALL
DKV = "%w = (bf16[28,16384,128], bf16[28,16384,128])" + CALL
INFO = {"flash": {"bh": 28, "t": 16384, "d": 128, "causal": True,
                  "itemsize": 2},
        "window_flash": {"window": 4096, "kv_heads": 4, "itemsize": 2}}


def _least(kind):
    return flops.roofline_seconds(
        swa_flops.window_flash_call_flops(kind, 28, 16384, 128, 4096),
        swa_flops.window_flash_call_bytes(kind, 28, 4, 16384, 128),
        PEAK["TPU v5 lite"])[0]


def test_the_window_readers_read_the_window_kernels_alone():
    """A program with both families of flash kernel: the readers by the
    prefix ``window_flash_`` count the window layers' calls under their
    window, and the accepted readers by ``flash_`` the full layers' alone
    (a window call counted as a causal one would read above 100)."""
    full = flops.roofline_seconds(
        flops.flash_call_flops("fwd", 28, 16384, 128),
        flops.flash_call_bytes("fwd", 28, 16384, 128), PEAK["TPU v5 lite"])[0]
    at, ops = 0.0, []
    for name, text, secs in (
            ("window_flash_fwd.1", FWD, 2 * _least("fwd")),
            ("window_flash_bwd_dq.2", DQ, 2 * _least("bwd_dq")),
            ("window_flash_bwd_dkv.3", DKV, 2 * _least("bwd_dkv")),
            ("flash_fwd.4", FWD, 4 * full),
            ("fusion.9", "%fusion.9 = f32[8] fusion(", 0.5)):
        ops.append(tr.Op(name, "custom-call", at, at + secs * 1e9, text))
        at += secs * 1e9
    ctx = _ctx(ops, INFO)
    reader, args = spec_of("window_flash_roofline")
    assert reader is window_flash_roofline and args == {
        "prefix": "window_flash_"}
    assert reader.read(ctx, **args) == pytest.approx(50.0, rel=1e-6)
    assert ctx.notes["window_flash_roofline_bound"] == {"compute": 3}
    assert flash_roofline_named.read(ctx, "flash_") == pytest.approx(25.0)
    windowed = 2 * sum(_least(k) for k in ("fwd", "bwd_dq", "bwd_dkv"))
    share, args = spec_of("window_flash_share_of_step")
    assert share is named_op_share
    assert share.read(ctx, **args) == pytest.approx(
        100 * windowed / (windowed + 4 * full + 0.5), rel=1e-6)
    assert named_op_share.read(ctx, "flash_") == pytest.approx(
        100 * 4 * full / (windowed + 4 * full + 0.5), rel=1e-6)
    # a program from before the kernels existed, a builder that says
    # nothing of a window: nothing to read, and no error
    assert reader.read(_ctx(ops[3:], INFO), "window_flash_") is None
    assert reader.read(_ctx(ops, {"flash": INFO["flash"]}),
                       "window_flash_") is None
    assert share.read(_ctx(ops[3:], INFO), "window_flash_") is None


def test_a_window_call_under_the_causal_count_would_read_past_its_peak():
    """Why the cell is NOT in the accepted ``flash_*`` readers through the
    window kernels: the same call at its roofline, counted as a causal
    call, reads 16/7 of it."""
    secs = _least("fwd")
    ops = [tr.Op("flash_fwd.1", "custom-call", 0.0, secs * 1e9, FWD)]
    assert flash_roofline_named.read(_ctx(ops, INFO), "flash_") > 200


@pytest.mark.parametrize("name,scope", [
    ("window_attn_share_of_step", "window_attn"),
    ("full_attn_share_of_step", "full_attn")])
def test_the_scope_files_name_the_kinds_scopes_of_the_whole_step(name, scope):
    from deepspeed_tpu.telemetry import scopes

    reader, args = spec_of(name)
    assert args == {"scopes": [scope]}      # no program: every instruction
    assert scope in (scopes.SCOPE_WINDOW_ATTN, scopes.SCOPE_FULL_ATTN)
    # a trace without a device plane has nothing under any scope
    ctx = _ctx([], {})
    ctx.red = tr.Reduced(devices={}, window=(0.0, 1e12))
    assert reader.read(ctx, **args) is None


def test_the_counter_file_reads_the_programs_event():
    reader, args = spec_of("moe_routed_here_share")
    assert reader is expert_load_share
    event = {"routed": 4 * 98304, "routed_here": 98000, "held": 16}
    ctx = _ctx([], {}, expert_load=lambda: event)
    assert reader.read(ctx, **args) == pytest.approx(100 * 98000 / 393216)
    assert reader.read(_ctx([], {}), **args) is None
    assert reader.read(_ctx([], {}, expert_load=lambda: {"routed": 8}),
                       **args) is None


# --- the builder ------------------------------------------------------------
def test_the_builder_declares_the_model_in_fields():
    cfg = smallthinker_train.model_config(BODY, BODY["train"], 16384)
    assert (cfg.n_embd, cfg.n_head, cfg.kv_heads, cfg.head_dim) == (
        2560, 28, 4, 128)
    assert cfg.sliding_window == 4096 and cfg.rotary_kinds == ("window",)
    assert cfg.rope_theta == 1.5e6 and cfg.layer_norm_epsilon == 1e-6
    assert (cfg.moe_num_experts, cfg.moe_top_k, cfg.moe_ffn_dim,
            cfg.moe_experts_held) == (64, 6, 768, (0, 16))
    assert (cfg.moe_router_input, cfg.moe_expert_activation,
            cfg.moe_norm_topk_prob, cfg.moe_aux_loss_coef,
            cfg.moe_drop_tokens) == ("block", "relu", True, 0.0, False)
    assert cfg.fused_head_ce == 2048 and cfg.remat and cfg.scan_layers
    assert not cfg.tie_word_embeddings and not cfg.use_bias
    with pytest.raises(ValueError, match="another model"):
        smallthinker_train.layer_types(dict(BODY, rope_layout=[1] * 52))


# --- the rehearsal ----------------------------------------------------------
def test_the_stand_in_is_registered_for_any_subset_of_the_tests():
    assert rehearsal.CONFIGS[TINY_SMALLTHINKER["name"]] is TINY_SMALLTHINKER
    assert TINY_CELL in rehearsal.CELLS
    assert rehearsal.STAND_IN[CELL] == STAND_IN[CELL] == TINY_CELL["name"]
    published = set(BODY) - {"assumed", "deployment", "published"}
    assert published <= set(TINY_SMALLTHINKER), \
        published - set(TINY_SMALLTHINKER)
    assert set(TINY_SMALLTHINKER["moe"]) == set(BODY["moe"])
    assert set(TINY_SMALLTHINKER["train"]) == set(BODY["train"])
    assert rehearsal.TRAFFIC[TINY_CELL["traffic"]]["kind"] == TRAFFIC["kind"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(tmp_path, trace):
    root = rehearsal.make_root(tmp_path)
    rc, last, err = rehearsal.run_cell(root, TINY_CELL["name"], trace=trace,
                                       seed=2 ** 31 + 63, seconds=1.5)
    assert rc == 0, err[-2000:]
    assert last["correct"] is True and last["rehearsal"] is True
    assert last["failed"] == 0 and last["attempted"] > 0
    if not trace:
        assert set(last["metrics"]) == {"train_tokens_per_s_per_chip",
                                        "setup_s"}
    else:
        # no device plane on the CPU: the trace's readers find nothing and
        # leave their metric out; the counters are read
        assert last["metrics"]["moe_tokens_dropped"]["value"] == 0
        assert last["metrics"]["compiles_in_window.train"]["value"] == 0
        assert 0 < last["metrics"]["moe_routed_here_share"]["value"] < 100


def test_the_checks_controls_at_the_tiny_size(tmp_path, capsys):
    """System inside every limit on two seeds; 8-bit weights, a router on
    the normed stream and a reference without its window each outside."""
    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(TINY_SMALLTHINKER))
    assert smallthinker_check.main(
        ["--config", str(path), "--seeds", "3", "4", "--controls"]) == 0
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    sides = {(r["seed"], r["side"]): r for r in rows if "side" in r}
    assert sides[3, "system"]["inside_all_limits"]
    assert sides[4, "system"]["inside_all_limits"]
    assert not sides[3, "reference_8bit_weights"]["inside_all_limits"]
    normed = sides[3, "reference_router_reads_normed_stream"]
    assert not normed["within"]["agreement"]
    unwindowed = sides[3, "reference_without_the_window"]
    assert not unwindowed["within"]["grad_attention_window"]
    assert not unwindowed["within"]["loss"]
    assert rows[-1]["ok"] is True
