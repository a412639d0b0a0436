"""A rehearsal checkout for the tests: the benchmark's own code, copied into
a temporary directory, with tiny configurations and traffic files and a
``BENCHMARK.json`` that names them. ``run_cell`` runs one cell of it as the
driver would, in a new process, with ``--rehearsal`` (CPU, nothing
measured)."""
import json
import os
import re
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_GPT = {"family": "gpt2", "vocab_size": 128, "n_positions": 64,
            "n_embd": 64, "n_layer": 2, "n_head": 2, "head_dim": 32,
            "mlp_ratio": 4, "activation": "gelu_tanh",
            "tie_word_embeddings": True, "layer_norm_epsilon": 1e-5}
ADAM = {"type": "FusedAdam", "params": {"lr": 1e-3}}


def _ds(stage):
    return {"bf16": {"enabled": True}, "gradient_clipping": 1.0,
            "optimizer": ADAM, "zero_optimization": {"stage": stage},
            "steps_per_print": 10 ** 9}


CONFIGS = {
    "tiny-gpt": {
        "name": "tiny-gpt", "source": "test", "model": TINY_GPT,
        "builders": {"train": "gpt_train", "serve": "gpt_serve"},
        "train": {"param_dtype": "float32", "compute_dtype": "float32",
                  "remat": True, "remat_policy": "full",
                  "use_flash_attention": False, "ds_config": _ds(1)},
        "serve": {"dtype": "fp32", "param_dtype": "float32",
                  "compute_dtype": "float32", "use_flash_attention": False,
                  "serving": {"slots": 4, "prompt_bucket": 16},
                  "first_token_tolerance": 0.01},
        "reduced": []},
    "tiny-gpt-zero3": {
        "name": "tiny-gpt-zero3", "source": "test", "model": TINY_GPT,
        "builders": {"train": "gpt_train"},
        "train": {"param_dtype": "float32", "compute_dtype": "float32",
                  "remat": True, "remat_policy": "full",
                  "use_flash_attention": False, "ds_config": _ds(3)},
        "reduced": []},
    "tiny-bert": {
        "name": "tiny-bert", "source": "test",
        "builders": {"train": "bert_train"},
        "model": {"family": "bert", "vocab_size": 128, "hidden_size": 64,
                  "num_hidden_layers": 2, "num_attention_heads": 2,
                  "intermediate_size": 128, "max_position_embeddings": 64,
                  "type_vocab_size": 2, "layer_norm_eps": 1e-12,
                  "hidden_act": "gelu"},
        "train": {"param_dtype": "float32", "compute_dtype": "float32",
                  "remat": True, "remat_policy": "selective", "dropout": 0.0,
                  "ds_config": _ds(0)},
        "reduced": []},
}
TRAFFIC = {
    "tiny-train": {"kind": "train_repeat", "seq": 32,
                   "micro_batch_per_chip": 2, "labels": "next_token",
                   "warm_up_steps": 2, "trace_seconds": 1,
                   "loss_margin": 0.01},
    "tiny-mlm": {"kind": "train_repeat", "seq": 32,
                 "micro_batch_per_chip": 4, "labels": "masked",
                 "label_share": 0.15, "warm_up_steps": 2, "trace_seconds": 1,
                 "loss_margin": 0.01},
    "tiny-closed": {"kind": "serve_closed", "clients": 4,
                    "prompt_lengths": [5, 9, 20, 30],
                    "output_lengths": [3, 4, 5, 6], "prompt_bucket": 16,
                    "max_positions": 64, "ramp_output_step": 1,
                    "pregenerate_requests": 40, "trace_seconds": 1,
                    "reference_samples": 2},
}
CELLS = [
    {"name": "tiny-train", "config": "tiny-gpt", "traffic": "tiny-train",
     "chips": 1, "why": "rehearsal"},
    {"name": "tiny-serve", "config": "tiny-gpt", "traffic": "tiny-closed",
     "chips": 1, "why": "rehearsal"},
    {"name": "tiny-bert-train", "config": "tiny-bert", "traffic": "tiny-mlm",
     "chips": 1, "why": "rehearsal"},
    {"name": "tiny-zero3", "config": "tiny-gpt-zero3",
     "traffic": "tiny-train", "chips": 4, "why": "rehearsal"},
]
# which tiny cell stands in for which real one in a metric's `workloads`
STAND_IN = {"gpt-1.3b-train": "tiny-train",
            "gpt-1.3b-serve-closed": "tiny-serve",
            "bert-large-train": "tiny-bert-train",
            "gpt-1.3b-zero3-4chip": "tiny-zero3"}


def make_root(tmp):
    """Copy ``perfbench/`` to ``tmp`` and add the tiny cells as files."""
    root = str(tmp)
    shutil.copytree(os.path.join(REPO, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    for name, cfg in CONFIGS.items():
        _write(root, f"perfbench/configs/{name}.json", cfg)
    for name, tr in TRAFFIC.items():
        _write(root, f"perfbench/traffic/{name}.json", tr)
    bench["configs"] = [{"name": n, "source": "test",
                         "file": f"perfbench/configs/{n}.json",
                         "reduced": [], "why": "rehearsal"} for n in CONFIGS]
    bench["workloads"] = CELLS
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [STAND_IN[w] for w in m["workloads"]]
    _write(root, "BENCHMARK.json", bench)
    return root


def _write(root, rel, obj):
    with open(os.path.join(root, rel), "w", encoding="utf-8") as f:
        json.dump(obj, f, indent=1)


def run_cell(root, workload, trace=0, seed=7, seconds=1.0, rehearsal=True,
             devices=1, timeout=600):
    """``(returncode, last stdout line parsed or None, stderr)``."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={devices}").strip()
    cmd = [sys.executable, "-m", "perfbench", "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds), "--trace",
           str(trace)] + (["--rehearsal"] if rehearsal else [])
    p = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                       text=True, timeout=timeout)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    last = None
    if lines:
        try:
            last = json.loads(lines[-1])
        except ValueError:
            last = None
    return p.returncode, last, p.stderr
