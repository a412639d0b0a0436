"""``decode_ahead_share``: the mean of the ``ahead`` attribute of the
program's ``ds:serve.decode_step`` spans, times 100 (reader
``span_attr_mean``). On a synthetic span list, on the trace recorded from
the program as it stood before the attribute existed (the parent of the PR
that brought it: nothing to read, nothing raised), and as an entry of
``BENCHMARK.json`` that names what the program exports."""
import os
import types

import pytest

from deepspeed_tpu.telemetry import spans
from perfbench import program_spans as ps
from perfbench import stats
from perfbench import trace_reduce as tr
from perfbench.readers import span_attr_mean

ROOT = stats.repo_root()
DATA = os.path.join(ROOT, "perfbench", "testdata")
BENCH = stats.load_json(os.path.join(ROOT, "BENCHMARK.json"))
METRIC = "decode_ahead_share"
SPEC = stats.load_json(os.path.join(
    ROOT, "perfbench", "layer_metrics", METRIC + ".json"))


def _ctx(span_list, window=(0, 1000)):
    """A reader's context over hand-made spans; one device, so that the
    spans count as measured on a chip."""
    red = types.SimpleNamespace(devices={0: None}, window=window)
    ctx = types.SimpleNamespace(red=red, series={}, notes={})
    setattr(ctx, "_program_spans",
            ps.Program(red=red, spans=span_list, rows=None))
    return ctx


def _steps(aheads, start=10, every=10):
    return [ps.Span(spans.SERVE_DECODE_STEP, start + i * every,
                    start + i * every + 5,
                    {"lanes_active": 2} if a is None
                    else {"lanes_active": 2, "ahead": a})
            for i, a in enumerate(aheads)]


@pytest.mark.parametrize("aheads,want", [
    ([1, 1, 1, 0], 75.0),
    ([0, 0], 0.0),
    ([1] * 9 + [0], 90.0),
    ([1], 100.0),
    # a span from before the attribute is left out, not read as 0
    ([None, 1, 0, None], 50.0),
])
def test_share_is_the_mean_of_zeros_and_ones_times_100(aheads, want):
    assert span_attr_mean.read(_ctx(_steps(aheads)), **SPEC["args"]) \
        == pytest.approx(want)


def test_only_the_named_span_inside_the_window_counts():
    inside = _steps([1, 0])
    other = [ps.Span(spans.SERVE_ADMIT, 12, 14, {"ahead": 1}),
             ps.Span(spans.SERVE_DECODE_STEP, 990, 1010, {"ahead": 1}),
             ps.Span(spans.SERVE_DECODE_STEP, -5, 3, {"ahead": 1})]
    assert span_attr_mean.read(_ctx(inside + other), **SPEC["args"]) \
        == pytest.approx(50.0)
    assert span_attr_mean.read(
        _ctx(inside), spans.SERVE_DECODE_STEP, "lanes_active") \
        == pytest.approx(2.0)


@pytest.mark.parametrize("span_list", [
    [], _steps([None, None, None]),
    [ps.Span(spans.SERVE_EMIT, 10, 12, {"request_id": 1})]],
    ids=["no_spans", "spans_without_the_attribute", "other_spans"])
def test_nothing_to_read_is_none(span_list):
    assert span_attr_mean.read(_ctx(span_list), **SPEC["args"]) is None


def test_no_device_plane_is_none():
    ctx = types.SimpleNamespace(red=tr.Reduced(), notes={})
    assert span_attr_mean.read(ctx, **SPEC["args"]) is None


def test_the_parents_recorded_spans_read_as_nothing():
    """``serve_spans`` was recorded on the v5e from a program whose decode
    steps carried ``lanes_active`` only; ``serve`` from one with no spans
    at all. Both: None, and no exception."""
    for name, table in (("serve_spans", "serve_spans.scopes.json"),
                        ("serve", None)):
        profile = tr.load(os.path.join(DATA, name + ".xplane.pb.gz"))
        red = tr.reduce_trace(profile, window_span="window") \
            if table else tr.reduce_trace(profile)
        ctx = types.SimpleNamespace(red=red, series={}, notes={},
                                    system=types.SimpleNamespace(info={}))
        setattr(ctx, "_program_spans", ps.build(profile, red, None))
        if table:
            steps = ps.named(ps.of(ctx), spans.SERVE_DECODE_STEP)
            assert steps and all("ahead" not in s.attrs for s in steps)
        assert span_attr_mean.read(ctx, **SPEC["args"]) is None


def test_entry_and_file_name_what_the_program_exports():
    entry = next(m for m in BENCH["per_layer"] if m["name"] == METRIC)
    assert entry == BENCH["per_layer"][-1]      # appended, nothing moved
    assert entry["source"] == "program_span" and entry["unit"] == "%"
    assert entry["better"] == "higher" and entry["layer"] == "scheduler"
    moved = next(e for e in BENCH["end_to_end"]
                 if e["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])
    serve_cells = {m2 for m in BENCH["per_layer"]
                   if m["name"] == "idle_share.step_host"
                   for m2 in m["workloads"]}
    assert set(entry["workloads"]) == serve_cells
    assert SPEC["reader"] == "span_attr_mean" and SPEC["how"]
    span_names = {v for k, v in vars(spans).items()
                  if k.startswith("SERVE_")}
    assert SPEC["args"]["span"] in span_names
    assert SPEC["args"]["span"] == spans.SERVE_DECODE_STEP
