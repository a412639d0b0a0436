"""The readers of the program's own spans and scopes, on two small traces
recorded on the v5e with their scope tables (``perfbench/testdata``:
``serve_spans``: a tiny server, three admissions and a few decode steps;
``train_spans``: three steps of a tiny GPT under full recomputation), and
the program's join (``deepspeed_tpu.telemetry.scopes``) on small HLO
texts."""
import os
import types

import pytest

from deepspeed_tpu.telemetry import scopes, spans
from perfbench import program_spans as ps
from perfbench import stats
from perfbench import trace_reduce as tr
from perfbench.readers import (module_time, scope_share, span_attr, span_idle,
                               span_module_time, span_time)

ROOT = stats.repo_root()
DATA = os.path.join(ROOT, "perfbench", "testdata")
BENCH = stats.load_json(os.path.join(ROOT, "BENCHMARK.json"))
NEW = ["idle_share.admit", "idle_share.step_host", "admit_dispatch_ms_p50",
       "admit_first_token_read_ms_p50", "admit_splice_ms_p50",
       "prefill_device_ms_p50", "queue_wait_ms_p50",
       "kv_cache_share_of_decode", "optimizer_share_of_step",
       "lm_head_ce_share_of_step", "recompute_share_of_step",
       "train_step_device_ms_p50", "scope_unattributed_share.train",
       "scope_unattributed_share.serve"]
ENGINE = "deepspeed_tpu.inference.engine"


def _ctx(name, info=None):
    profile = tr.load(os.path.join(DATA, name + ".xplane.pb.gz"))
    red = tr.reduce_trace(profile, window_span="window")
    table = stats.load_json(os.path.join(DATA, name + ".scopes.json"))
    ctx = types.SimpleNamespace(
        red=red, series={}, notes={},
        system=types.SimpleNamespace(info=info or {}))
    setattr(ctx, "_program_spans", ps.build(profile, red, table))
    return ctx


def _children(prog, parent):
    """The spans that lie inside ``parent``, itself left out."""
    return [s for s in prog.spans if s is not parent
            and parent.start <= s.start and s.end <= parent.end]


@pytest.fixture(scope="module")
def serve():
    return _ctx("serve_spans")


@pytest.fixture(scope="module")
def train():
    return _ctx("train_spans", {"step_program": "jit_train_step"})


# ---------------------------------------------------------------------------
# the join, on HLO text
# ---------------------------------------------------------------------------
HLO = '''HloModule jit_step, is_scheduled=true

%fused_a (p0: bf16[2,4,64,2,8]) -> bf16[2,4,64,2,8] {
  %p0 = bf16[2,4,64,2,8]{4,3,2,1,0} parameter(0)
  %m.1 = bf16[2,4,64,2,8]{4,3,2,1,0} multiply(%p0, %p0), metadata={op_name="jit(step)/jit(main)/GPT/h/attn/kv_cache_write/mul"}
  %m.2 = bf16[2,4,64,2,8]{4,3,2,1,0} add(%m.1, %p0), metadata={op_name="jit(step)/jit(main)/GPT/h/attn/kv_cache_write/add"}
  ROOT %m.3 = bf16[2,4,64,2,8]{4,3,2,1,0} add(%m.2, %p0), metadata={op_name="jit(step)/jit(main)/GPT/h/attn/kv_cache_write/add"}
}

%body (t: (s32[], bf16[2,4,64,2,8])) -> (s32[], bf16[2,4,64,2,8]) {
  %t = (s32[], bf16[2,4,64,2,8]{4,3,2,1,0}) parameter(0)
  %g = bf16[2,4,64,2,8]{4,3,2,1,0} get-tuple-element(%t), index=1
  %copy.7 = bf16[2,4,64,2,8]{4,3,2,1,0} copy(%g)
  %slice.1 = bf16[4,64,2,8]{3,2,1,0} fusion(%copy.7), kind=kLoop, calls=%fused_b, metadata={op_name="jit(step)/jit(main)/while/body/GPT/h/dynamic_slice"}
  %fusion.1 = bf16[2,4,64,2,8]{4,3,2,1,0} fusion(%copy.7), kind=kLoop, calls=%fused_a
  ROOT %tuple = (s32[], bf16[2,4,64,2,8]{4,3,2,1,0}) tuple(%i, %fusion.1)
}

%fused_b (p0: bf16[2,4,64,2,8]) -> bf16[4,64,2,8] {
  %p0.1 = bf16[2,4,64,2,8]{4,3,2,1,0} parameter(0)
  ROOT %ds = bf16[4,64,2,8]{3,2,1,0} dynamic-slice(%p0.1), dynamic_slice_sizes={1,4,64,2,8}
}

ENTRY %main (x: bf16[2,4,64,2,8]) -> bf16[2,4,64,2,8] {
  %x = bf16[2,4,64,2,8]{4,3,2,1,0} parameter(0), metadata={op_name="x"}
  %while.1 = (s32[], bf16[2,4,64,2,8]{4,3,2,1,0}) while(%init), condition=%cond, body=%body
  %opt.1 = f32[8]{0} multiply(%a, %b), metadata={op_name="jit(step)/jit(main)/optimizer/mul"}
  %bare.1 = f32[8]{0} add(%a, %b), metadata={op_name="jit(step)/jit(main)/add"}
  ROOT %out = bf16[2,4,64,2,8]{4,3,2,1,0} get-tuple-element(%while.1), index=1
}
'''
KV = [(2, 4, 64, 2, 8), (4, 64, 2, 8)]


@pytest.mark.parametrize("path,want", [
    ("jit(train_step)/jit(main)/mul", []),
    ("x", []),
    (None, []),
    ("jit(train_step)/jit(main)/optimizer/mul", ["optimizer"]),
    ("jit(step)/jit(main)/while/body/closed_call/GPT/h/dynamic_slice",
     ["GPT", "h"]),
    ("jit(f)/transpose(jvp(attn_core))/flash_bwd_dq/pallas_call",
     ["transpose(jvp(attn_core))", "flash_bwd_dq"]),
    ("jit(s)/jit(main)/cond/branch_1_fun/optimizer/jit(a/b)/mul",
     ["optimizer"]),
])
def test_components_drop_structure_and_the_primitive(path, want):
    assert scopes.components(path) == want


def test_has_scope_sees_through_jaxs_transform_wrappers():
    path = "jit(f)/transpose(jvp(GPT))/h/checkpoint/rematted_computation" \
           "/block/attn/attn_core/dot_general"
    assert scopes.has_scope(path, scopes.SCOPE_ATTN_CORE)
    assert scopes.has_scope(path, scopes.SCOPE_REMAT)
    assert scopes.has_scope(path, "GPT") and not scopes.has_scope(path, "mlp")
    assert scopes.has_scope("jit(f)/jvp(attn_core)/flash_fwd/pallas_call",
                            scopes.SCOPE_ATTN_CORE, "nothing")
    assert not scopes.has_scope(None, scopes.SCOPE_ATTN_CORE)


def test_instruction_scopes_fusions_carry_and_bare_instructions():
    program, table = scopes.instruction_scopes(HLO, KV)
    assert program == "jit_step"
    # a fusion without its own op_name takes its computation's commonest
    assert table["fusion.1"].endswith("kv_cache_write/add")
    # instructions inside a fused computation are not in the table
    assert "m.1" not in table and "ds" not in table
    # no scope of the program's own, a whole KV leaf as result: carry
    assert scopes.has_scope(table["copy.7"], scopes.SCOPE_KV_CACHE_CARRY)
    assert scopes.components(table["copy.7"]) == [
        scopes.SCOPE_KV_CACHE_CARRY]
    assert scopes.components(table["slice.1"]) == [
        "GPT", "h", scopes.SCOPE_KV_CACHE_CARRY]
    # a named scope owns it: never carry, whatever the shape
    assert not scopes.has_scope(table["fusion.1"],
                                scopes.SCOPE_KV_CACHE_CARRY)
    assert scopes.components(table["opt.1"]) == [scopes.SCOPE_OPTIMIZER]
    assert scopes.components(table["bare.1"]) == []
    # containers and tuples of the loop are no carry
    assert not scopes.has_scope(table["while.1"],
                                scopes.SCOPE_KV_CACHE_CARRY)
    without = scopes.instruction_scopes(HLO)[1]
    assert without["copy.7"] is None


def test_executables_of_one_name_keep_what_they_agree_on():
    other = HLO.replace("optimizer/mul", "grad_cast/mul").replace(
        "kv_cache_write/add", "kv_cache_write/sub")
    table = scopes.scope_table([HLO, other], KV)["jit_step"]
    assert scopes.components(table["opt.1"]) == []          # they disagree
    assert scopes.components(table["fusion.1"])[-1] == \
        scopes.SCOPE_KV_CACHE_WRITE                         # same scope
    assert scopes.components(table["slice.1"]) == [
        "GPT", "h", scopes.SCOPE_KV_CACHE_CARRY]            # identical


# ---------------------------------------------------------------------------
# the recorded traces
# ---------------------------------------------------------------------------
def test_serve_trace_has_every_span_of_the_table(serve):
    prog = ps.of(serve)
    names = {s.name for s in prog.spans}
    assert {spans.SERVE_ITERATION, spans.SERVE_ADMIT, spans.SERVE_PREFILL,
            spans.SERVE_FIRST_TOKEN_READ, spans.SERVE_SPLICE,
            spans.SERVE_EMIT, spans.SERVE_STATS, spans.SERVE_DECODE_STEP,
            spans.SERVE_DECODE_READ} <= names
    admits = ps.named(prog, spans.SERVE_ADMIT)
    assert len(admits) == 3
    assert sorted(a.attrs["bucket"] for a in admits) == [64, 128, 128]
    assert all(a.attrs["queue_wait_us"] >= 0 for a in admits)


def test_idle_under_admit_plus_idle_outside_it_is_all_idle(serve):
    prog = ps.of(serve)
    whole = ps.idle_seconds(prog)
    admit = ps.idle_seconds(prog, spans.SERVE_ADMIT)
    step = ps.idle_seconds(prog, spans.SERVE_ITERATION, spans.SERVE_ADMIT)
    outside = ps.idle_seconds(prog, None, spans.SERVE_ITERATION)
    assert admit > 0 and step > 0
    assert admit + step + outside == pytest.approx(whole, rel=1e-9)
    window = tr.window_seconds(serve.red)
    assert span_idle.read(serve, spans.SERVE_ADMIT) == pytest.approx(
        100 * admit / window)
    assert span_idle.read(serve, spans.SERVE_ITERATION, spans.SERVE_ADMIT) \
        == pytest.approx(100 * step / window)
    # the device's whole idle share, as the older reader computes it
    assert 100 * whole / window == pytest.approx(
        100 * (1 - tr.busy_seconds(serve.red) / window))


def test_admission_children_sum_to_their_parent_less_its_self_time(serve):
    prog = ps.of(serve)
    three = (spans.SERVE_PREFILL, spans.SERVE_FIRST_TOKEN_READ,
             spans.SERVE_SPLICE)
    for admit in ps.named(prog, spans.SERVE_ADMIT):
        kids = _children(prog, admit)
        assert [k.name for k in kids if k.name in three] == list(three)
        inner = sum(k.dur_ms for k in kids if k.name in three)
        emit = sum(k.dur_ms for k in kids if k.name == spans.SERVE_EMIT)
        # the parent's own time: the bus event, building the lane
        assert 0 <= admit.dur_ms - inner - emit < 0.5
    for name in three:
        assert span_time.read(serve, name, 50) > 0
    assert span_time.read(serve, "serve.no_such_span", 50) is None


def test_queue_wait_and_prefill_device_time_per_admission(serve):
    prog = ps.of(serve)
    waits = span_attr.read(serve, spans.SERVE_ADMIT, "queue_wait_us", 50,
                           scale=0.001)
    assert waits is not None and waits >= 0
    per = ps.module_ms_by_span(prog, spans.SERVE_ADMIT, "jit_prefill")
    assert len(per) == 3 and all(ms > 0 for ms in per)
    got = span_module_time.read(serve, spans.SERVE_ADMIT,
                                [ENGINE, "PROGRAM_PREFILL"], 50)
    assert got == pytest.approx(stats.percentile(per, 50))
    # the prefill ran while the host was blocked on its token
    for admit in ps.named(prog, spans.SERVE_ADMIT):
        read = [k for k in _children(prog, admit)
                if k.name == spans.SERVE_FIRST_TOKEN_READ][0]
        assert read.dur_ms > 0
    assert span_module_time.read(
        serve, spans.SERVE_ADMIT, [ENGINE, "NO_SUCH_PROGRAM"], 50) is None


def test_decode_program_time_by_kv_cache_scope(serve):
    prog = ps.of(serve)
    names = [scopes.SCOPE_KV_CACHE_WRITE, scopes.SCOPE_KV_CACHE_READ,
             scopes.SCOPE_KV_CACHE_CARRY]
    value = scope_share.read(serve, names, [ENGINE, "PROGRAM_DECODE_K"])
    apart = serve.notes["scope_share:" + "+".join(names)]
    assert 0 < value < 100 and value == pytest.approx(sum(apart.values()))
    assert apart[scopes.SCOPE_KV_CACHE_WRITE] > 0
    assert apart[scopes.SCOPE_KV_CACHE_CARRY] > 0
    rest = prog.scopes.share(
        prog.rows, lambda r: not scopes.has_scope(r["path"], *names),
        lambda r: r["program"] == "jit_decode_k")
    assert value + rest == pytest.approx(100.0)


@pytest.mark.parametrize("which", ["serve", "train"])
def test_scope_shares_and_the_unattributed_share_make_100(which, request):
    ctx = request.getfixturevalue(which)
    prog = ps.of(ctx)
    un = scope_share.read(ctx, unattributed=True)
    at = prog.scopes.share(prog.rows, prog.scopes.attributed)
    assert un + at == pytest.approx(100.0) and 0 <= un < 25
    by = dict(scopes.by_scope(prog.rows))
    whole = sum(by.values())
    assert 100 * by.get("(unattributed)", 0.0) / whole == pytest.approx(un)
    # instructions of one core do not overlap: their sum is the busy time
    assert whole == pytest.approx(tr.busy_seconds(ctx.red), rel=1e-3)
    assert ctx.notes["scope_top_ops"][0][3] >= ctx.notes["scope_top_ops"][
        -1][3]
    assert all(row[2] is None for row in ctx.notes["scope_top_unattributed"])


def test_train_step_by_scope(train):
    opt = scope_share.read(train, [scopes.SCOPE_OPTIMIZER,
                                   scopes.SCOPE_GRAD_NORM_CLIP,
                                   scopes.SCOPE_GRAD_CAST])
    head = scope_share.read(train, [scopes.SCOPE_LM_HEAD_CE,
                                    scopes.SCOPE_MLM_HEAD])
    remat = scope_share.read(train, [scopes.SCOPE_REMAT])
    assert 0 < opt < 100 and 0 < head < 100 and 0 < remat < 50
    assert train.notes["scope_share:lm_head_ce+mlm_head"]["mlm_head"] == 0
    prog = ps.of(train)
    kernels = {r["instruction"].split(".")[0] for r in prog.rows
               if r["program"] == "jit_train_step"}
    assert {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"} <= kernels
    # the forward kernel runs twice under full recomputation
    fwd = [r for r in prog.rows if r["instruction"].startswith("flash_fwd")]
    assert any(scopes.has_scope(r["path"], scopes.SCOPE_REMAT) for r in fwd)
    assert any(not scopes.has_scope(r["path"], scopes.SCOPE_REMAT)
               for r in fwd)
    assert module_time.read(train, "step_program", 50) > 0
    spans_ = {s.name for s in prog.spans}
    assert {"train.h2d", "train.compiled_step",
            "train.post_step_bookkeeping"} <= spans_


def test_a_program_without_spans_or_scopes_reads_as_nothing():
    """The parent commit: no ``ds:`` span in the trace, no
    ``program_scopes``. Every reader returns None and raises nothing."""
    profile = tr.load(os.path.join(DATA, "serve.xplane.pb.gz"))
    red = tr.reduce_trace(profile)
    ctx = types.SimpleNamespace(red=red, series={}, notes={},
                                system=types.SimpleNamespace(info={}))
    setattr(ctx, "_program_spans", ps.build(profile, red, None))
    assert span_idle.read(ctx, spans.SERVE_ADMIT) is None
    assert span_time.read(ctx, spans.SERVE_PREFILL, 50) is None
    assert span_attr.read(ctx, spans.SERVE_ADMIT, "queue_wait_us", 50) is None
    assert span_module_time.read(ctx, spans.SERVE_ADMIT,
                                 [ENGINE, "PROGRAM_PREFILL"], 50) is None
    assert scope_share.read(ctx, unattributed=True) is None
    assert scope_share.read(ctx, [scopes.SCOPE_OPTIMIZER]) is None
    empty = types.SimpleNamespace(red=tr.Reduced(), notes={})
    assert ps.of(empty) is None         # no device plane: a CPU rehearsal


def test_the_operators_command_prints_time_per_scope(capsys):
    rc = scopes.main([os.path.join(DATA, "train_spans.xplane.pb.gz"),
                      os.path.join(DATA, "train_spans.scopes.json")])
    out = capsys.readouterr().out.splitlines()
    assert rc == 0 and out[0].split() == ["seconds", "share", "scope"]
    shares = [float(ln.split()[1].rstrip("%")) for ln in out[1:]]
    assert sum(shares) == pytest.approx(100.0, abs=0.5)
    assert any(ln.split()[2] == scopes.SCOPE_OPTIMIZER for ln in out[1:])
    assert scopes.main([]) == 2


@pytest.mark.parametrize("name", NEW)
def test_new_metric_reads_names_the_program_exports(name):
    """A metric's file spells span and scope names as data; each must be
    one the program exports, so that a rename in the program fails here
    and not silently on the chip."""
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    spec = stats.load_json(os.path.join(
        ROOT, "perfbench", "layer_metrics", name + ".json"))
    args = spec["args"]
    span_names = {v for k, v in vars(spans).items()
                  if k.startswith(("SERVE_", "TRAIN_"))}
    scope_names = {v for k, v in vars(scopes).items()
                   if k.startswith("SCOPE_")}
    for key in ("inside", "outside", "span"):
        if key in args:
            assert args[key] in span_names, (name, args[key])
    assert set(args.get("scopes", ())) <= scope_names
    if isinstance(args.get("program"), list):
        assert ps.program_constant(*args["program"])
    want = "program_span" if spec["reader"].startswith("span_") \
        else "device_trace"
    assert entry["source"] == want and entry["better"] == "lower"
