"""The per-layer metrics that read the serve loop's own account of a trace
(``deepspeed_tpu.telemetry.serve_account``, reader ``serve_account``) and
``idle_share.post_step.train``: their entries, their files, the reader on
the recorded v5e trace, on a trace without a device plane and in a checkout
whose program has no such module. Every entry of ``BENCHMARK.json`` is
found BY NAME, and "appended" means "after every entry the benchmark had",
so the next PR's appended entry breaks nothing here."""
import os
import types

import pytest

from deepspeed_tpu.telemetry import serve_account as account
from deepspeed_tpu.telemetry import spans
from perfbench import program_spans as ps
from perfbench import stats
from perfbench import trace_reduce as tr
from perfbench.readers import serve_account, span_idle

ROOT = stats.repo_root()
DATA = os.path.join(ROOT, "perfbench", "testdata")
BENCH = stats.load_json(os.path.join(ROOT, "BENCHMARK.json"))
NAMES = [m["name"] for m in BENCH["per_layer"]]
LAST_ACCEPTED = "moe_bias_changed_share"        # PR 52's last entry
GAP_CELLS = ["gpt-1.3b-serve-closed", "gpt-1.3b-serve-open-08",
             "falcon-h1-34b-serve-closed", "brumby-14b-serve-closed"]
# The two 256-lane cells are listed by none of these: each has an accepted
# test that holds the set of metrics listing its cell to what its PR saw
# (test_perfbench_deepseek_v2.py, test_perfbench_lfm2.py), and a PR may
# not edit an accepted test (PERF.md, section 7). The reader reads them
# there all the same, once a benchmark PR lists them.
SERVE_CELLS = GAP_CELLS + ["keye-vl-2.0-serve-resident-16k"]
ADMITTING = GAP_CELLS
TRAIN_CELLS = ["gpt-1.3b-train", "bert-large-train", "gpt-1.3b-zero3-4chip",
               "olmoe-1b-7b-train-4k"]
# name -> (unit, moves, cells, layer)
NEW = {
    "decode_stall_device_ms_p95": ("ms", "gap_p95_ms", GAP_CELLS),
    "admit_stall_device_ms_p50": ("ms", "gap_p95_ms", GAP_CELLS),
    "prefill_device_ms_p50.own": ("ms", "gap_p95_ms", GAP_CELLS),
    "admissions_per_decode_step": ("count", "serve_out_tokens_per_s",
                                   ADMITTING),
    "iteration_host_ms_p50": ("ms", "serve_out_tokens_per_s", SERVE_CELLS),
    "idle_gap_max_ms.serve": ("ms", "serve_out_tokens_per_s", SERVE_CELLS),
    "idle_share.post_step.train": ("%", "train_tokens_per_s_per_chip",
                                   TRAIN_CELLS),
}
ACCOUNT_METRICS = [n for n in NEW if n != "idle_share.post_step.train"]


def entry_of(name):
    return next(m for m in BENCH["per_layer"] if m["name"] == name)


def spec_of(name):
    return stats.load_json(os.path.join(
        ROOT, "perfbench", "layer_metrics", name + ".json"))


def recorded_ctx(name="serve_spans", window_span="window"):
    """A reader's context over a recorded trace, its account made from the
    loaded profile (the harness finds the file under its trace directory)."""
    profile = tr.load(os.path.join(DATA, name + ".xplane.pb.gz"))
    red = tr.reduce_trace(profile, window_span=window_span)
    ctx = types.SimpleNamespace(red=red, series={}, notes={})
    setattr(ctx, "_serve_account",
            account.account(profile, window=red.window))
    setattr(ctx, "_program_spans", ps.build(profile, red, None))
    return ctx


@pytest.mark.parametrize("name", sorted(NEW))
def test_entry_is_appended_and_names_its_cells(name):
    unit, moves, cells = NEW[name]
    entry = entry_of(name)
    assert NAMES.index(name) > NAMES.index(LAST_ACCEPTED)
    assert NAMES.count(name) == 1
    assert entry["unit"] == unit and entry["better"] == "lower"
    assert entry["source"] == "program_span" and entry["moves"] == moves
    # a later PR may append cells (the two 256-lane cells wait for one)
    assert entry["workloads"][:len(cells)] == cells
    assert entry["layer"] == ("train step" if name.endswith(".train")
                              else "scheduler")
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    moved = next(e for e in BENCH["end_to_end"] if e["name"] == moves)
    assert set(cells) <= set(moved["workloads"])
    assert {w["name"] for w in BENCH["workloads"]} >= set(cells)


@pytest.mark.parametrize("name", ACCOUNT_METRICS)
def test_file_names_a_table_and_a_field_of_the_account(name):
    spec = spec_of(name)
    assert spec["reader"] == "serve_account" and spec["how"]
    args = spec["args"]
    empty = account.Account(window=(0, 1))
    assert isinstance(getattr(empty, args["table"]), list)
    assert ("q" in args) != ("stat" in args)
    assert args.get("stat") in (None, "max", "mean")
    # the field and the fields of ``where`` are what the rows carry
    ctx = recorded_ctx()
    rows = getattr(serve_account.of(ctx), args["table"])
    assert rows and all(
        k in rows[0] for k in [args["field"], *args.get("where", {})])


def test_post_step_file_reads_a_span_the_engine_writes():
    spec = spec_of("idle_share.post_step.train")
    assert spec["reader"] == "span_idle" and spec["how"]
    assert spec["args"] == {
        "inside": spans.TRAIN_PHASE + "post_step_bookkeeping"}
    profile = tr.load(os.path.join(DATA, "train_spans.xplane.pb.gz"))
    red = tr.reduce_trace(profile, window_span="window")
    ctx = types.SimpleNamespace(red=red, series={}, notes={})
    setattr(ctx, "_program_spans", ps.build(profile, red, None))
    share = span_idle.read(ctx, **spec["args"])
    assert 0 <= share <= 100


@pytest.mark.parametrize("name,want", [
    # three admissions before the first of eight steps
    ("admissions_per_decode_step", 3 / 8),
    # the recorded loop's step has no admission between two decode runs
    ("admit_stall_device_ms_p50", None),
])
def test_reader_on_the_recorded_trace(name, want):
    got = serve_account.read(recorded_ctx(), **spec_of(name)["args"])
    assert got == (pytest.approx(want) if want is not None else None)


def test_reader_agrees_with_the_account_and_the_benchmarks_idle_time():
    ctx = recorded_ctx()
    acc = serve_account.of(ctx)
    own = serve_account.read(ctx, **spec_of(
        "prefill_device_ms_p50.own")["args"])
    assert own == pytest.approx(stats.percentile(
        [a["prefill_device_ms"] for a in acc.admissions], 50))
    # alone in their spans here, so enclosure reads the same
    assert own == pytest.approx(stats.percentile(ps.module_ms_by_span(
        ps.of(ctx), spans.SERVE_ADMIT, account.PROGRAM_PREFILL), 50))
    stall = serve_account.read(ctx, **spec_of(
        "decode_stall_device_ms_p95")["args"])
    assert stall == pytest.approx(account.percentile(
        account.select(acc.steps, "stall_ms"), 95))
    longest = serve_account.read(ctx, **spec_of(
        "idle_gap_max_ms.serve")["args"])
    assert longest == pytest.approx(max(g["ms"] for g in acc.gaps))
    host = serve_account.read(ctx, **spec_of(
        "iteration_host_ms_p50")["args"])
    assert 0 < host < stats.percentile(
        [i["ms"] for i in acc.iterations], 50)
    assert acc.totals[0]["idle_ms"] / 1e3 == pytest.approx(
        ps.idle_seconds(ps.of(ctx)))


def test_the_account_and_its_summaries_go_to_the_runs_log(monkeypatch):
    profile = tr.load(os.path.join(DATA, "serve_spans.xplane.pb.gz"))
    red = tr.reduce_trace(profile, window_span="window")
    monkeypatch.setattr(tr, "find_xplane", lambda d: d)
    monkeypatch.setattr(tr, "load", lambda path: profile)
    ctx = types.SimpleNamespace(
        red=red, notes={}, series={"gap_ms": [1.0, 2.0, 3.0]},
        env=types.SimpleNamespace(trace_dir="x"))
    assert serve_account.read(ctx, "steps", "admissions", stat="mean") \
        == pytest.approx(3 / 8)
    note = ctx.notes["serve_account"]
    assert note["joins"][account.PROGRAM_PREFILL][account.JOIN_CHAIN] == 3
    assert note["gaps_by_cause"] and note["stall_by_buckets"]
    assert note["gap_ms_p95"] == pytest.approx(2.9)
    assert serve_account.of(ctx) is getattr(ctx, "_serve_account")


@pytest.mark.parametrize("name", ACCOUNT_METRICS)
def test_no_device_plane_is_none(name):
    ctx = types.SimpleNamespace(red=tr.Reduced(), notes={})
    assert serve_account.read(ctx, **spec_of(name)["args"]) is None


@pytest.mark.parametrize("name", ACCOUNT_METRICS)
def test_a_program_without_the_module_reads_nothing(name, monkeypatch):
    """The parent's checkout: no ``serve_account`` in its program, so the
    reader returns None and its line leaves the metric out."""
    monkeypatch.setattr(serve_account, "program_module", lambda: None)
    assert serve_account.read(recorded_ctx(), **spec_of(name)["args"]) \
        is None


def test_a_trace_without_the_loops_spans_reads_nothing():
    """``serve`` was recorded from a program with no spans at all: no row,
    no metric but the longest gap, which is the device's alone."""
    ctx = recorded_ctx("serve", window_span=None)
    for name in ACCOUNT_METRICS:
        got = serve_account.read(ctx, **spec_of(name)["args"])
        assert (got is None) == (name != "idle_gap_max_ms.serve"), name
