"""Additions are files: a later PR brings a cell, a configuration, a traffic
mix and a per-layer metric with its reader by adding files and
``BENCHMARK.json`` entries, and edits no file that is there."""
import hashlib
import json
import os

import rehearsal

NEW_READER = '''"""Requests completed in the window: a count."""


def read(ctx, scale=1):
    done = ctx.series.get("requests_completed")
    return None if done is None else done * scale
'''


def _digest(root):
    out = {}
    for base, _, files in os.walk(os.path.join(root, "perfbench")):
        for f in files:
            if "__pycache__" in base:
                continue
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = \
                    hashlib.sha256(fh.read()).hexdigest()
    return out


def test_a_new_cell_arrives_as_files_and_entries_only(tmp_path):
    root = rehearsal.make_root(tmp_path)
    before = _digest(root)

    def write(rel, obj):
        path = os.path.join(root, rel)
        assert not os.path.exists(path), rel
        with open(path, "w", encoding="utf-8") as f:
            f.write(obj if isinstance(obj, str) else json.dumps(obj))

    cfg = json.loads(json.dumps(rehearsal.CONFIGS["tiny-gpt"]))
    cfg["name"] = "later-gpt"
    cfg["model"]["n_layer"] = 1
    cfg["serve"]["serving"]["slots"] = 2
    write("perfbench/configs/later-gpt.json", cfg)
    traffic = dict(rehearsal.TRAFFIC["tiny-closed"], clients=2,
                   prompt_lengths=[4, 18], output_lengths=[2, 7])
    write("perfbench/traffic/later-closed.json", traffic)
    write("perfbench/readers/later_count.py", NEW_READER)
    write("perfbench/layer_metrics/later_requests.json",
          {"reader": "later_count", "args": {"scale": 1},
           "how": "requests completed in the window"})

    bench_path = os.path.join(root, "BENCHMARK.json")
    with open(bench_path, encoding="utf-8") as f:
        bench = json.load(f)
    old = json.loads(json.dumps(bench))
    bench["configs"].append({"name": "later-gpt", "source": "test",
                             "file": "perfbench/configs/later-gpt.json",
                             "reduced": [], "why": "added later"})
    bench["workloads"].append({"name": "later-serve", "config": "later-gpt",
                               "traffic": "later-closed", "chips": 1,
                               "why": "added later"})
    bench["per_layer"].append({
        "name": "later_requests", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "serve_out_tokens_per_s", "workloads": ["later-serve"]})
    for m in bench["end_to_end"] + bench["per_layer"]:
        # entries gain the new cell; none is changed otherwise
        if "workloads" in m and "tiny-serve" in m["workloads"] \
                and "later-serve" not in m["workloads"]:
            m["workloads"] = m["workloads"] + ["later-serve"]
    with open(bench_path, "w", encoding="utf-8") as f:
        json.dump(bench, f)
    for key in ("configs", "workloads", "per_layer"):
        assert bench[key][:len(old[key])] == [
            dict(o, workloads=n["workloads"]) if "workloads" in o else o
            for o, n in zip(old[key], bench[key])]

    rc, last, err = rehearsal.run_cell(root, "later-serve", trace=1)
    assert rc == 0, err[-2000:]
    assert last["correct"] is True
    assert last["metrics"]["later_requests"]["unit"] == "count"
    assert last["metrics"]["later_requests"]["value"] > 0
    assert "sched_lane_occupancy" in last["metrics"]   # the old ones too
    rc, last, err = rehearsal.run_cell(root, "later-serve", trace=0)
    assert rc == 0, err[-2000:]
    assert last["metrics"]["serve_out_tokens_per_s"]["value"] > 0

    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before} == before
    assert sorted(set(after) - set(before)) == [
        "perfbench/configs/later-gpt.json",
        "perfbench/layer_metrics/later_requests.json",
        "perfbench/readers/later_count.py",
        "perfbench/traffic/later-closed.json"]
