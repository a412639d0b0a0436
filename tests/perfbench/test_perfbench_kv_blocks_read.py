"""``kv_blocks_read_share``: the mean of the ``kv_blocks_read_share``
attribute of the program's ``ds:serve.decode_step`` spans, times 100
(reader ``span_attr_mean``, a data file alone): of the position blocks the
lanes' KV cache holds, the share a decode step's attention read. On a
synthetic span list, on traces recorded from programs that had no such
attribute (nothing to read, nothing raised), and as an entry of
``BENCHMARK.json`` that names what the program exports."""
import os
import types

import pytest

from deepspeed_tpu.inference import scheduler as scheduler_mod
from deepspeed_tpu.telemetry import spans
from perfbench import program_spans as ps
from perfbench import stats
from perfbench import trace_reduce as tr
from perfbench.readers import span_attr_mean

ROOT = stats.repo_root()
DATA = os.path.join(ROOT, "perfbench", "testdata")
BENCH = stats.load_json(os.path.join(ROOT, "BENCHMARK.json"))
METRIC = "kv_blocks_read_share"
SPEC = stats.load_json(os.path.join(
    ROOT, "perfbench", "layer_metrics", METRIC + ".json"))


def _ctx(span_list, window=(0, 1000)):
    red = types.SimpleNamespace(devices={0: None}, window=window)
    ctx = types.SimpleNamespace(red=red, series={}, notes={})
    setattr(ctx, "_program_spans",
            ps.Program(red=red, spans=span_list, rows=None))
    return ctx


def _steps(shares, start=10, every=10):
    return [ps.Span(spans.SERVE_DECODE_STEP, start + i * every,
                    start + i * every + 5,
                    {"lanes_active": 2, "ahead": 1} if share is None
                    else {"lanes_active": 2, "ahead": 1, METRIC: share})
            for i, share in enumerate(shares)]


@pytest.mark.parametrize("shares,want", [
    ([0.5, 0.25, 0.75], 50.0),
    ([1.0, 1.0], 100.0),            # attention on the einsums reads it all
    ([0.4609375], 46.09375),
    # a span from before the attribute is left out, not read as 0
    ([None, 0.25, 0.5, None], 37.5),
])
def test_share_is_the_mean_times_100(shares, want):
    assert span_attr_mean.read(_ctx(_steps(shares)), **SPEC["args"]) \
        == pytest.approx(want)


@pytest.mark.parametrize("span_list", [
    [], _steps([None, None]),
    [ps.Span(spans.SERVE_ADMIT, 10, 12, {METRIC: 0.5})]],
    ids=["no_spans", "spans_without_the_attribute", "another_span"])
def test_nothing_to_read_is_none(span_list):
    assert span_attr_mean.read(_ctx(span_list), **SPEC["args"]) is None


def test_recorded_traces_from_before_the_attribute_read_as_nothing():
    """The parent's decode steps carry ``lanes_active`` (and ``ahead``)
    only; the benchmark's files are laid over the parent too, where this
    metric has to be absent from the line and raise nothing."""
    for name, table in (("serve_spans", "serve_spans.scopes.json"),
                        ("serve", None)):
        profile = tr.load(os.path.join(DATA, name + ".xplane.pb.gz"))
        red = tr.reduce_trace(profile, window_span="window") \
            if table else tr.reduce_trace(profile)
        ctx = types.SimpleNamespace(red=red, series={}, notes={},
                                    system=types.SimpleNamespace(info={}))
        setattr(ctx, "_program_spans", ps.build(profile, red, None))
        assert span_attr_mean.read(ctx, **SPEC["args"]) is None


def test_entry_and_file_name_what_the_program_exports():
    entry = next(m for m in BENCH["per_layer"] if m["name"] == METRIC)
    assert entry["source"] == "program_span" and entry["unit"] == "%"
    assert entry["better"] == "lower" and entry["layer"] == "decode step"
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"]
                              if m["name"] != METRIC}
    moved = next(e for e in BENCH["end_to_end"]
                 if e["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])
    serve_cells = {m2 for m in BENCH["per_layer"]
                   if m["name"] == "decode_step_ms_p50"
                   for m2 in m["workloads"]}
    assert set(entry["workloads"]) == serve_cells
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert SPEC["reader"] == "span_attr_mean" and SPEC["how"]
    assert SPEC["args"] == {"span": spans.SERVE_DECODE_STEP,
                            "attr": METRIC, "scale": 100.0}
    # the attribute is the one the scheduler's loop writes
    assert 'kv_blocks_read_share=' in open(
        scheduler_mod.__file__, encoding="utf-8").read()
