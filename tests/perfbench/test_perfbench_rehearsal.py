"""The command as the driver runs it, rehearsed: each builder at a tiny
size on the CPU, in a new process, from a checkout made of files (see
``rehearsal.py``). Nothing here is a measurement; what is checked is the
last line's shape and the control flow that leads to it."""
import json
import os
import subprocess
import sys

import pytest

import rehearsal

NEEDED = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearsal.make_root(tmp_path_factory.mktemp("checkout"))


def _shape(last, bench, cell, trace):
    assert last is not None and NEEDED <= set(last)
    assert set(last) <= NEEDED | {"breakdown", "rehearsal"}
    assert last["rehearsal"] is True            # never read as a measurement
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    dev = last["device"]
    assert dev["platform"] == "cpu" and dev["count"] >= 1
    assert "memory_peak_bytes" in dev
    wanted = bench["per_layer"] if trace else bench["end_to_end"]
    names = {m["name"]: m["unit"] for m in wanted
             if "workloads" not in m or cell in m["workloads"]}
    assert set(last["metrics"]) <= set(names)
    for name, m in last["metrics"].items():
        assert set(m) == {"value", "unit"} and m["unit"] == names[name]
        assert isinstance(m["value"], float)
    return names


@pytest.mark.parametrize("cell,devices", [
    ("tiny-train", 1), ("tiny-serve", 1), ("tiny-bert-train", 1),
    ("tiny-zero3", 4)])
def test_untraced_run_prints_the_end_to_end_metrics(root, cell, devices):
    rc, last, err = rehearsal.run_cell(root, cell, trace=0, devices=devices,
                                       seed=2 ** 31 + 11)
    assert rc == 0, err[-2000:]
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    names = _shape(last, bench, cell, trace=0)
    assert set(last["metrics"]) == set(names)     # every one, setup_s too
    assert all(m["value"] > 0 for m in last["metrics"].values())
    assert "breakdown" not in last
    assert '"compiles_in_window": 0' in err


@pytest.mark.parametrize("cell,expect", [
    ("tiny-train", {"compiles_in_window.train", "train_step_ms_p50",
                    "train_mfu"}),
    ("tiny-serve", {"compiles_in_window.serve", "sched_lane_occupancy",
                    "admit_ms_p50", "ttft_p50_ms", "ttft_p95_ms.closed",
                    "gap_p50_ms"})])
def test_traced_run_prints_the_per_layer_metrics_it_can_read(root, cell,
                                                             expect):
    rc, last, err = rehearsal.run_cell(root, cell, trace=1)
    assert rc == 0, err[-2000:]
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    _shape(last, bench, cell, trace=1)
    # no TPU plane in a CPU trace: the device readers find nothing to read
    # and are left out, the host-side readers report. The tail needs 200
    # requests in the rehearsal's one second; on a loaded machine fewer
    # complete, and its reader then leaves it out as it should
    assert expect - {"ttft_p95_ms.closed"} <= set(last["metrics"]) <= expect
    assert last["metrics"]["compiles_in_window." + (
        "serve" if "serve" in cell else "train")]["value"] == 0.0
    assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
    assert last["device"]["window_s"] > 0 and "busy_s" in last["device"]


def test_same_seed_same_inputs_and_verdict(root):
    a = rehearsal.run_cell(root, "tiny-train", seed=5)
    b = rehearsal.run_cell(root, "tiny-train", seed=5)
    c = rehearsal.run_cell(root, "tiny-train", seed=6)

    def first_loss(err):
        line = [ln for ln in err.splitlines() if '"event": "run"' in ln][-1]
        return json.loads(line)["verdict"]["first_loss"]

    assert first_loss(a[2]) == first_loss(b[2]) != first_loss(c[2])


def _no_result(stdout):
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "metrics" in obj:
            return False
    return True


def test_the_real_command_fails_without_a_tpu_and_prints_no_metric():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    p = subprocess.run(
        [sys.executable, "-m", "perfbench", "--workload", "gpt-1.3b-train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=rehearsal.REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "not 'tpu'" in p.stderr


def test_a_checkout_with_only_the_benchmark_fails_without_a_result(root):
    """Only ``BENCHMARK.json`` and the files under ``paths``: the program is
    not there, so the builder cannot import it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "-m", "perfbench", "--workload", "tiny-train",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearsal"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "deepspeed_tpu" in p.stderr


def test_an_unknown_workload_fails_without_a_result(root):
    rc, last, err = rehearsal.run_cell(root, "no-such-cell")
    assert rc != 0 and last is None and "no-such-cell" in err
