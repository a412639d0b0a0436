"""The reduction from a profiler trace to metrics, on three small traces
recorded on the v5e (``perfbench/testdata``): three steps of a tiny GPT on
one chip, the same under ZeRO-3 on four chips, and a tiny server."""
import os
import types

import pytest

from perfbench import stats
from perfbench import trace_reduce as tr
from perfbench.readers import (collective_exposed, collective_share,
                               device_idle, flash_roofline, module_time,
                               mosaic_share)

DATA = os.path.join(stats.repo_root(), "perfbench", "testdata")
PEAK = stats.load_json(os.path.join(
    stats.repo_root(), "perfbench", "peaks.json"))["TPU v5 lite"]


@pytest.fixture(scope="module")
def traces():
    return {name: tr.reduce_trace(tr.load(os.path.join(
        DATA, name + ".xplane.pb.gz")))
        for name in ("train1", "train4", "serve")}


def _ctx(red, info):
    return types.SimpleNamespace(
        red=red, system=types.SimpleNamespace(info=info), series={},
        env=types.SimpleNamespace(peak=PEAK), notes={})


@pytest.mark.parametrize("text,want", [
    ("%attn.33 = (bf16[4,1024,128]{2,1,0:T(8,128)(2,1)S(1)}, "
     "f32[4,1024,8]{2,1,0:T(8,128)}) custom-call(bf16[4,1024,128]{2,1,0} "
     "%bitcast.289), custom_call_target=\"tpu_custom_call\"",
     ("attn.33", "custom-call")),
    ("%fusion.325 = bf16[256]{0:T(256)(128)(2,1)S(1)} fusion(bf16[2,256]"
     "{1,0} %get-tuple-element.1378), kind=kLoop", ("fusion.325", "fusion")),
    ("%all-gather.129 = bf16[1024,256]{1,0:T(8,128)(2,1)S(1)} all-gather("
     "bf16[256,256]{1,0} %fusion.1), channel_id=129",
     ("all-gather.129", "all-gather")),
    ("%collective-permute-done.2 = bf16[1,64,256]{2,1,0} "
     "collective-permute-done((bf16[1,64,256]{2,1,0}, u32[]{:S(2)}) "
     "%collective-permute-start.2)",
     ("collective-permute-done.2", "collective-permute-done")),
    ("%while.5 = (s32[]{:T(128)}, bf16[2,1024,256]{1,2,0}) while((s32[], "
     "bf16[2,1024,256]) %tuple.1), condition=%cond", ("while.5", "while")),
    ("not an instruction", ("not an instruction", "?")),
])
def test_parse_op(text, want):
    assert tr.parse_op(text) == want


def test_collective_opcodes():
    assert tr.is_collective("all-to-all")
    assert tr.is_collective("collective-permute-start")
    assert tr.is_collective("all-reduce-done")
    assert not tr.is_collective("fusion")
    assert not tr.is_collective("copy-start")


def test_interval_arithmetic():
    u = tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert u == [(0, 3), (5, 8)]
    assert tr.total(u) == 6
    assert tr.clip(u, 2, 6) == [(2, 3), (5, 6)]
    assert tr.complement(u, -1, 10) == [(-1, 0), (3, 5), (8, 10)]
    assert tr.intersect(u, [(2, 6), (7, 20)]) == [(2, 3), (5, 6), (7, 8)]
    assert tr.subtract(u, [(1, 2), (6, 9)]) == [(0, 1), (2, 3), (5, 6)]
    assert tr.subtract([], u) == []


def test_host_timeline_labels_by_innermost_span():
    spans = [("run", 0, 100), ("poll", 10, 20), ("callback", 30, 50),
             ("inner", 35, 40), ("after", 120, 130)]
    assert tr.host_timeline(spans) == [
        (0, 10, "run"), (10, 20, "poll"), (20, 30, "run"),
        (30, 35, "callback"), (35, 40, "inner"), (40, 50, "callback"),
        (50, 100, "run"), (120, 130, "after")]


def test_containers_are_not_work(traces):
    for red in traces.values():
        for dev in red.devices.values():
            assert not [o for o in dev.ops if o.opcode in tr.CONTAINERS]
    # the raw trace does hold them, spanning their bodies
    raw = tr.load(os.path.join(DATA, "train1.xplane.pb.gz"))
    names = [e.name for p in raw.planes if p.name == "/device:TPU:0"
             for ln in p.lines if ln.name == "XLA Ops" for e in ln.events]
    assert sum(1 for n in names if tr.parse_op(n)[1] == "while") == 6


def test_busy_idle_union_on_one_chip(traces):
    red = traces["train1"]
    assert list(red.devices) == [0]
    window, busy = tr.window_seconds(red), tr.busy_seconds(red)
    assert 0 < busy < window
    # a tiny model: the chip waits for the host most of the time
    assert device_idle.read(_ctx(red, {})) == pytest.approx(
        100 * (1 - busy / window))
    dev = red.devices[0]
    by_sum = sum(o.dur for o in dev.ops) / 1e9
    assert busy <= by_sum * (1 + 1e-9)     # the union never exceeds the sum
    gaps = tr.idle_by_span(red)
    assert sum(s for _, s in gaps) == pytest.approx(window - busy, rel=1e-6)
    assert {name for name, _ in gaps} <= {"train_batch", "fence",
                                          "unattributed"}
    assert gaps[0][0] == "train_batch"     # dispatching, not waiting


def test_window_span_and_default_host_label(traces):
    raw = tr.load(os.path.join(DATA, "serve.xplane.pb.gz"))
    red = tr.reduce_trace(raw, window_span="run")
    span = [s for s in red.spans if s[0] == "run"][0]
    assert red.window == (span[1], span[2])
    gaps = tr.idle_by_span(red, window_span="run", host="scheduler")
    assert [g[0] for g in gaps] == ["scheduler"]
    assert gaps[0][1] == pytest.approx(
        tr.window_seconds(red) - tr.busy_seconds(red), rel=1e-6)


def test_per_op_sums(traces):
    red = traces["train1"]
    top = tr.top_ops(red, 10)
    assert len(top) == 10 and top[0][0] == "attn.36"
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    sums = tr.op_seconds(red)
    assert sum(sums.values()) == pytest.approx(
        sum(o.dur for o in red.devices[0].ops) / 1e9)


def test_flash_calls_are_found_and_classified(traces):
    red = traces["train1"]
    calls = {}
    for o in red.devices[0].ops:
        if tr.MOSAIC_TARGET in o.text:
            calls.setdefault(flash_roofline.classify(o.text), []).append(o)
    # forward (twice per layer and step under full recomputation), dQ, dK+dV
    assert set(calls) == {("fwd", 4, 1024, 128), ("bwd_dq", 4, 1024, 128),
                          ("bwd_dkv", 4, 1024, 128)}
    assert len(calls[("fwd", 4, 1024, 128)]) == 2 * \
        len(calls[("bwd_dq", 4, 1024, 128)])
    info = {"flash": {"bh": 4, "t": 1024, "d": 128, "causal": True,
                      "itemsize": 2}}
    ctx = _ctx(red, info)
    share = flash_roofline.read(ctx)
    assert 0 < share < 100
    assert set(ctx.notes["flash_roofline_bound"]) <= {"compute", "memory"}
    assert 0 < mosaic_share.read(ctx) < 100
    assert flash_roofline.read(_ctx(red, {})) is None      # nothing to read
    assert mosaic_share.read(_ctx(traces["serve"], {})) is None


def test_collectives_on_four_chips(traces):
    red = traces["train4"]
    assert sorted(red.devices) == [0, 1, 2, 3]
    flight, exposed = tr.collective_seconds(red)
    assert 0 < exposed <= flight < tr.window_seconds(red)
    # all-gather / all-to-all / all-reduce run as synchronous operations on
    # the op line, so most of the collective time is exposed
    assert exposed / flight > 0.5
    ctx = _ctx(red, {"step_program": "jit_train_step"})
    assert tr.module_runs(red, "jit_train_step") == 3
    assert collective_exposed.read(ctx) == pytest.approx(exposed * 1e3 / 3)
    assert collective_share.read(ctx) == pytest.approx(
        100 * flight / tr.window_seconds(red))
    one = _ctx(traces["train1"], {"step_program": "jit_train_step"})
    assert collective_share.read(one) is None
    assert collective_exposed.read(one) is None


def test_program_times_from_the_modules_line(traces):
    red = traces["serve"]
    steps = tr.module_durations_ms(red, "jit_decode_k")
    assert len(steps) == 22 and all(s > 0 for s in steps)
    ctx = _ctx(red, {"decode_program": "jit_decode_k"})
    assert module_time.read(ctx, "decode_program", 50) == pytest.approx(
        stats.percentile(steps, 50))
    assert module_time.read(ctx, "no_such_key", 50) is None
