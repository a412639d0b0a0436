"""Set-up by the program's own account: the six per-layer metrics of the
entry layer that read the program's build log (reader ``program_builds``).
As entries of ``BENCHMARK.json`` and files that agree with each other and
with what the program exports; the reader on a hand-made log (the cut at the
window's opening, the union, what a checkout without the log reads); and a
rehearsal of one training and one serve cell whose line carries them."""
import json
import os
import types

import pytest

import rehearsal
from deepspeed_tpu.telemetry import builds
from perfbench import stats
from perfbench.readers import program_builds as reader

ROOT = stats.repo_root()
BENCH = stats.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]
SERVE = [c for c in CELLS if c in next(
    m for m in BENCH["end_to_end"]
    if m["name"] == "serve_out_tokens_per_s")["workloads"]]
# metric -> (unit, cells, the reader's arguments), in the entries' order
METRICS = {
    "setup_trace_s": ("s", CELLS, {"what": "seconds", "stage": "trace"}),
    "setup_lower_s": ("s", CELLS, {"what": "seconds", "stage": "lower"}),
    "setup_compile_or_load_s": (
        "s", CELLS, {"what": "seconds", "stage": "compile_or_load"}),
    "setup_programs_built": ("count", CELLS, {"what": "programs"}),
    "setup_cache_misses": ("count", CELLS, {"what": "cache_misses"}),
    "setup_first_dispatch_s.serve": ("s", SERVE,
                                     {"what": "first_dispatch"}),
}


def _spec(name):
    return stats.load_json(os.path.join(
        ROOT, "perfbench", "layer_metrics", name + ".json"))


# ---------------------------------------------------------------------------
# entries and files
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", list(METRICS))
def test_entry_and_file_agree(name):
    unit, cells, args = METRICS[name]
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": "program_counter", "layer": "entry",
                     "moves": "setup_s", "workloads": cells}
    # every cell reports setup_s: the metric that has no list of its own
    moved = next(e for e in BENCH["end_to_end"] if e["name"] == "setup_s")
    assert "workloads" not in moved
    spec = _spec(name)
    assert set(spec) == {"reader", "args", "how"} and spec["how"]
    assert spec["reader"] == "program_builds" and spec["args"] == args
    # the stage is one the program's log keeps
    assert args.get("stage", builds.TRACE) in builds.STAGES
    assert reader.COMPILE_OR_LOAD == builds.COMPILE_OR_LOAD


def test_the_six_are_appended_in_order_and_list_cells_in_the_files_order():
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index("setup_trace_s")       # a later PR appends after them
    assert names[at:at + len(METRICS)] == list(METRICS)
    assert at > names.index("retention_state_roofline")
    assert len(CELLS) == 8 and len(SERVE) == 4
    assert SERVE == [c for c in CELLS if "serve" in c]
    # the entry layer had the compile counter alone before
    entry_layer = [m["name"] for m in BENCH["per_layer"]
                   if m["layer"] == "entry"]
    assert entry_layer == ["compiles_in_window.train",
                           "compiles_in_window.serve"] + list(METRICS)


# ---------------------------------------------------------------------------
# the reader, on a hand-made log
# ---------------------------------------------------------------------------
def _log():
    """A real ``BuildLog`` with rows put in by hand: an eager program, a
    dispatched program whose nested jit is traced inside its own tracing,
    and a build after time 100."""
    log = builds.BuildLog()
    log.entered = 1.0
    rows = [
        ("add", "trace", 2.0, 2.5), ("jit(add)", "lower", 2.5, 3.0),
        ("jit(add)", "compile_or_load", 3.0, 4.0, None, True),
        ("inner", "trace", 11.0, 12.0, "(1, 64)"),
        ("prefill", "trace", 10.0, 13.0, "(1, 64)"),
        ("jit(prefill)", "lower", 13.0, 15.0, "(1, 64)"),
        ("jit(prefill)", "compile_or_load", 15.0, 19.0, "(1, 64)", False),
        ("mul", "trace", 20.0, 20.25),
        ("jit(mul)", "compile_or_load", 20.5, 21.0, None, None),
        ("jit(late)", "compile_or_load", 101.0, 105.0, None, False),
    ]
    for program, stage, start, end, *more in rows:
        row = {"program": program, "stage": stage, "start": start,
               "end": end, "nth": 1}
        if more and more[0]:
            row.update(dispatch="jit(prefill)", key=more[0])
        if stage == "compile_or_load":
            row["cache_hit"] = more[1]
        log.rows.append(row)
    log.dispatches += [
        {"program": "jit(prefill)", "key": "(1, 64)", "start": 9.5,
         "end": 19.75, "first_dispatch_s": 10.25},
        {"program": "jit(late)", "key": "(1, 128)", "start": 100.0,
         "end": 106.0, "first_dispatch_s": 6.0}]
    return log


def _ctx(owner, t_open=50.0, devices=True, serve=True):
    red = types.SimpleNamespace(devices={0: None} if devices else {})
    system = types.SimpleNamespace(engine=owner)
    if serve:
        system.scheduler = owner
    return types.SimpleNamespace(
        red=red, system=system, notes={}, series={},
        env=types.SimpleNamespace(t_open=t_open))


def _owner(log):
    return types.SimpleNamespace(program_builds=log.snapshot)


@pytest.mark.parametrize("name,want", [
    ("setup_trace_s", 0.5 + 3.0 + 0.25),    # inner lies inside prefill
    ("setup_lower_s", 0.5 + 2.0),
    ("setup_compile_or_load_s", 1.0 + 4.0 + 0.5),
    ("setup_programs_built", 3),
    ("setup_cache_misses", 1),      # asked and missed; not "never asked"
    ("setup_first_dispatch_s.serve", 10.25),
])
def test_reads_what_ended_before_the_window_opened(name, want):
    ctx = _ctx(_owner(_log()))
    assert reader.read(ctx, **_spec(name)["args"]) == pytest.approx(want)
    note = ctx.notes["program_builds"]
    assert note["since_entry_s"] == pytest.approx(49.0)
    assert note["seconds_in_first_calls"] == {
        "trace": 3.0, "lower": 2.0, "compile_or_load": 4.0}
    assert note["first_calls"]["rows"] == [
        ["jit(prefill)", "(1, 64)", 10.25, 4.0, 2.0, 3.0]]
    assert note["first_calls"]["columns"][3:] == [
        "compile_or_load", "lower", "trace"]
    assert note["not_from_cache"] == ["jit(prefill)"]
    assert note["cache_not_asked"] == 1


def test_the_cut_is_the_windows_opening():
    log = _log()
    late = _ctx(_owner(log), t_open=200.0)
    assert reader.read(late, what="programs") == 4
    assert reader.read(late, what="cache_misses") == 2
    assert reader.read(late, what="first_dispatch") == pytest.approx(16.25)
    assert reader.read(late, what="seconds", stage="compile_or_load") \
        == pytest.approx(9.5)
    early = _ctx(_owner(log), t_open=14.0)      # mid-build: stages that ended
    assert reader.read(early, what="programs") == 1
    assert reader.read(early, what="seconds", stage="trace") \
        == pytest.approx(3.5)
    assert reader.read(early, what="first_dispatch") is None


@pytest.mark.parametrize("name", list(METRICS))
def test_a_checkout_without_the_log_reads_nothing(name):
    """The benchmark's files are laid over the parent too: its engines and
    scheduler have no ``program_builds``, the metric is left out of the
    line and nothing is raised."""
    ctx = _ctx(types.SimpleNamespace())
    assert reader.read(ctx, **_spec(name)["args"]) is None
    assert ctx.notes == {}
    train = _ctx(types.SimpleNamespace(), serve=False)
    assert reader.read(train, **_spec(name)["args"]) is None


def test_a_training_system_has_no_first_calls_and_is_read_from_its_engine():
    log = _log()
    del log.dispatches[:]
    ctx = _ctx(_owner(log), serve=False)
    assert reader.read(ctx, what="first_dispatch") is None
    assert reader.read(ctx, what="programs") == 3


def test_a_trace_without_a_device_reads_nothing_unless_told():
    """A rehearsal on the CPU: times of a machine nobody measures are left
    out like the rest, and the accepted rehearsal tests hold the line to
    the metrics it had."""
    ctx = _ctx(_owner(_log()), devices=False)
    assert reader.read(ctx, what="programs") is None
    assert reader.read(ctx, what="programs", without_device=True) == 3
    with pytest.raises(ValueError):
        reader.read(_ctx(_owner(_log())), what="nothing")


# ---------------------------------------------------------------------------
# a rehearsal on the CPU: nothing here is a measurement
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A rehearsal checkout whose six files tell the reader to read without
    a device plane."""
    root = rehearsal.make_root(tmp_path_factory.mktemp("checkout"))
    for name in METRICS:
        path = os.path.join(root, "perfbench", "layer_metrics",
                            name + ".json")
        spec = stats.load_json(path)
        spec["args"]["without_device"] = True
        with open(path, "w", encoding="utf-8") as f:
            json.dump(spec, f)
    return root


@pytest.mark.parametrize("cell,serve", [("tiny-train", False),
                                        ("tiny-serve", True)])
def test_a_rehearsed_cell_prints_them(root, cell, serve):
    rc, last, err = rehearsal.run_cell(root, cell, trace=1,
                                       seed=2 ** 31 + 23)
    assert rc == 0, err[-2000:]
    assert last["correct"] is True and last["rehearsal"] is True
    got = {n: last["metrics"][n] for n in METRICS if n in last["metrics"]}
    want = [n for n in METRICS if serve or not n.endswith(".serve")]
    assert list(got) == want and len(want) == (6 if serve else 5)
    for name, m in got.items():
        assert m["unit"] == METRICS[name][0]
    run = next(json.loads(ln) for ln in err.splitlines()
               if ln.startswith("{") and '"event": "run"' in ln)
    stages = sum(got[n]["value"] for n in (
        "setup_trace_s", "setup_lower_s", "setup_compile_or_load_s"))
    assert 0 < stages <= run["setup_s"]
    assert got["setup_programs_built"]["value"] >= 1
    # the harness counts from before the entry point, the log from it on
    assert got["setup_programs_built"]["value"] <= run["compiles_total"]
    # no persistent cache on the CPU: asked and not served, every one
    assert got["setup_cache_misses"]["value"] \
        == got["setup_programs_built"]["value"]
    assert last["metrics"]["compiles_in_window." + (
        "serve" if serve else "train")]["value"] == 0
    note = next(json.loads(ln) for ln in err.splitlines()
                if ln.startswith("{") and '"event": "trace"' in ln)[
        "notes"]["program_builds"]
    calls = note["first_calls"]["rows"]
    if serve:
        first = got["setup_first_dispatch_s.serve"]["value"]
        # the three stages inside the first calls are part of them (a
        # helper that lowering traces counts in both stages: milliseconds)
        assert sum(note["seconds_in_first_calls"].values()) <= 1.02 * first
        assert first == pytest.approx(sum(c[2] for c in calls), abs=1e-3)
        programs = {c[0] for c in calls}
        assert {"jit(prefill)", "jit(decode_k)", "jit(splice)"} <= programs
        # one prefill program per prompt bucket of the tiny grid: 16, 32
        assert sorted(c[1] for c in calls if c[0] == "jit(prefill)") \
            == ["(1, 16)", "(1, 32)"]
    else:
        assert calls == []


def test_with_the_files_as_committed_a_rehearsal_leaves_them_out(tmp_path):
    root = rehearsal.make_root(tmp_path)
    rc, last, err = rehearsal.run_cell(root, "tiny-train", trace=1)
    assert rc == 0, err[-2000:]
    assert not set(METRICS) & set(last["metrics"])
