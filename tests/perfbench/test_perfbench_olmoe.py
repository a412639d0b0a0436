"""The OLMoE configuration, its counts and its readers."""
import json
import os
import types

import pytest

import rehearsal
from perfbench import flops, moe_flops, stats
from perfbench import trace_reduce as tr
from perfbench.readers import expert_load, flash_roofline, \
    flash_roofline_named, grouped_matmul_roofline, mosaic_share, \
    named_op_share, series_value

ROOT = stats.repo_root()
BENCH = stats.load_json(os.path.join(ROOT, "BENCHMARK.json"))
PEAK = stats.load_json(os.path.join(ROOT, "perfbench", "peaks.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = [c for c in BENCH["configs"] if c["reduced"]]


@pytest.mark.parametrize("cfg", REDUCED, ids=lambda c: c["name"])
def test_reduced_configuration_says_what_it_cut(cfg):
    """What ``test_configuration_entry_and_file`` checks, for a
    configuration that is cut: entry and file agree on ``reduced``, every
    key in it is a key of the file and none of them is a width, and the
    file says what it assumed and which deployment it stands for."""
    body = stats.load_json(os.path.join(ROOT, cfg["file"]))
    assert body["name"] == cfg["name"] and body["source"] == cfg["source"]
    assert body["reduced"] == cfg["reduced"] and len(cfg["reduced"]) <= 16
    for key in cfg["reduced"]:
        assert key in body
        assert not key.endswith(("_dim", "_rank", "_size")), key
    assert body["assumed"] and body["deployment"]
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])


def test_every_published_number_is_in_the_file_under_its_key():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    cfg = next(c for c in BENCH["configs"] if c["name"].startswith("olmoe"))
    body = stats.load_json(os.path.join(ROOT, cfg["file"]))
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == cfg["source"])
    differs = sorted(k for k, v in row["config"].items()
                     if body.get(k, "missing") != v)
    assert differs == cfg["reduced"] == ["num_hidden_layers"]
    assert body["num_hidden_layers"] == 3 < row["config"]["num_hidden_layers"]


def test_active_parameters_agree_with_the_programs_tree():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer_lm import GPT
    from perfbench.builders import olmoe_train

    import conftest

    c = conftest.TINY_OLMOE
    cfg = olmoe_train.model_config(c, c["train"], 64)
    shapes = jax.eval_shape(
        lambda: GPT(cfg).init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 8), jnp.int32)))["params"]
    block = shapes["h"]["block"]
    count = lambda t: sum(x.size for x in jax.tree.leaves(t))
    dense = count(block["attn"]["c_attn"]) + count(block["attn"]["c_proj"]) \
        + count(block["mlp"]["gate"])
    active = count(block["mlp"]["experts"]) * c["num_experts_per_tok"] \
        // c["num_experts"]
    assert moe_flops.moe_params_active(
        c["num_hidden_layers"], c["hidden_size"], c["intermediate_size"],
        c["num_experts"], c["num_experts_per_tok"]) == dense + active


def test_counts_at_the_cell():
    fpt = moe_flops.moe_train_flops_per_token(3, 2048, 1024, 64, 8, 50304,
                                              4096)
    # 6 x 3 x (16.78 M attention + 0.13 M router + 8 x 6.29 M experts)
    # + 0.151e9 attention scores + 0.618e9 head
    assert fpt == pytest.approx(1.980e9, rel=2e-3)
    rows = 2 * 4096 * 8
    f = moe_flops.grouped_matmul_flops(rows, 2048, 1024)
    b = moe_flops.grouped_matmul_bytes(rows, 2048, 1024, 64)
    assert f == 2.0 * 65536 * 2048 * 1024 and b == pytest.approx(6.71e8,
                                                                 rel=1e-3)
    secs, bound = flops.roofline_seconds(f, b, PEAK["TPU v5 lite"])
    assert bound == "compute" and secs == pytest.approx(1.395e-3, rel=1e-3)


def _ctx(ops, info, **more):
    red = tr.Reduced(devices={0: tr.Device(ops=ops)}, window=(0.0, 1e9))
    return types.SimpleNamespace(
        red=red, system=types.SimpleNamespace(info=info, **more),
        env=types.SimpleNamespace(peak=PEAK["TPU v5 lite"]), notes={},
        series={"queue_depth_at_close": 7})


def test_grouped_matmul_roofline_reads_the_ragged_dots():
    shape = {"rows": 65536, "d_model": 2048, "d_hidden": 1024, "groups": 64,
             "itemsize": 2}
    ops = [tr.Op("ragged-dot-none.3", "ragged-dot", 0.0, 2.79e6),
           tr.Op("ragged-dot-none.4", "ragged-dot", 3e6, 5.79e6),
           tr.Op("ragged-dot-metadata.1", "custom-call", 5.8e6, 5.9e6),
           tr.Op("fusion.1", "fusion", 6e6, 9e6)]
    ctx = _ctx(ops, {"grouped_matmul": shape})
    assert grouped_matmul_roofline.read(ctx, "ragged-dot", "ragged-dot-metadata") \
        == pytest.approx(50.0, rel=2e-3)
    assert ctx.notes["grouped_matmul_roofline"]["calls"] == 2
    assert grouped_matmul_roofline.read(_ctx(ops[2:], {
        "grouped_matmul": shape}), "ragged-dot", "ragged-dot-metadata") is None
    assert grouped_matmul_roofline.read(_ctx(ops, {}), "ragged-dot", "ragged-dot-metadata") is None


def test_the_flash_readers_by_name_leave_the_ragged_dots_out():
    """A program with the flash kernels and the compiler's ragged-dot
    kernels, all Mosaic calls: the readers by name count the first only,
    where the accepted ones count both."""
    call = ' custom-call(%q), custom_call_target="tpu_custom_call"'
    fwd = "%flash_fwd.1 = (bf16[32,4096,128]{2,1,0}, f32[32,4096,1]{2,1,0})"
    dq = "%flash_bwd_dq.2 = bf16[32,4096,128]{2,1,0}"
    # the matrices' gradient: one 3-D result, which reads as a dQ call
    ragged = "%ragged-dot-none.3 = bf16[64,2048,1024]{2,1,0}"
    shape = {"bh": 32, "t": 4096, "d": 128, "causal": True, "itemsize": 2}
    least = [flops.roofline_seconds(
        flops.flash_call_flops(kind, 32, 4096, 128),
        flops.flash_call_bytes(kind, 32, 4096, 128),
        PEAK["TPU v5 lite"])[0] for kind in ("fwd", "bwd_dq")]
    ops = [tr.Op("flash_fwd.1", "custom-call", 0.0, 4e9 * least[0],
                 fwd + call),
           tr.Op("flash_bwd_dq.2", "custom-call", 1e8, 1e8 + 4e9 * least[1],
                 dq + call),
           tr.Op("ragged-dot-none.3", "custom-call", 2e8, 3e8, ragged + call),
           tr.Op("fusion.1", "fusion", 3e8, 4e8, "%fusion.1 = f32[8] fusion(")]
    ctx = _ctx(ops, {"flash": shape})
    assert flash_roofline_named.read(ctx, "flash_") \
        == pytest.approx(25.0, rel=1e-6)
    assert ctx.notes["flash_roofline_bound"] == {"compute": 2}
    flash_s = 4 * sum(least)
    assert named_op_share.read(ctx, "flash_") \
        == pytest.approx(100 * flash_s / (flash_s + 0.2), rel=1e-6)
    assert mosaic_share.read(ctx) > named_op_share.read(ctx, "flash_")
    assert flash_roofline.read(ctx) != pytest.approx(25.0, rel=0.1)
    # nothing to read: no such shape, no such operation
    assert flash_roofline_named.read(_ctx(ops, {}), "flash_") is None
    assert flash_roofline_named.read(_ctx(ops[2:], {"flash": shape}),
                                     "flash_") is None
    assert named_op_share.read(_ctx(ops[2:], {}), "flash_") is None


def test_load_and_value_readers_return_none_where_there_is_nothing():
    assert expert_load.read(_ctx([], {}), "max_over_mean") is None
    ctx = _ctx([], {}, expert_load=lambda: {"max_over_mean": 2.5,
                                            "tokens_dropped": 0})
    assert expert_load.read(ctx, "max_over_mean") == 2.5
    assert expert_load.read(ctx, "tokens_dropped") == 0
    assert series_value.read(ctx, "queue_depth_at_close") == 7
    assert series_value.read(ctx, "no_such") is None


@pytest.mark.parametrize("trace", [0, 1])
def test_the_olmoe_cell_rehearses(tmp_path, trace):
    root = rehearsal.make_root(tmp_path)
    rc, last, err = rehearsal.run_cell(root, "tiny-olmoe-train", trace=trace)
    assert rc == 0, err[-2000:]
    assert last["correct"] is True and last["rehearsal"] is True
    if trace:
        assert last["metrics"]["moe_tokens_dropped"]["value"] == 0.0
        assert last["metrics"]["moe_expert_load_max_over_mean"]["value"] >= 1
        assert "train_mfu" in last["metrics"]
    else:
        assert last["metrics"]["train_tokens_per_s_per_chip"]["value"] > 0


def test_the_comparison_passes_on_the_system_and_fails_both_controls(
        tmp_path, capsys):
    """``olmoe_check`` at a tiny size in float32: the system is inside
    limits a thousand times tighter than the chip's, weights rounded to 8
    bits and renormalised top-k weights are outside."""
    import conftest
    from perfbench.reference import olmoe_check

    cfg = dict(conftest.TINY_OLMOE, reference={
        "module": "olmoe", "batch": [2, 32], "limits": {
            "loss": 1e-5, "agreement": 0.999, "grad_experts": 1e-4,
            "grad_attention": 1e-4, "grad_router": 1e-4}})
    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(cfg))
    assert olmoe_check.main(["--config", str(path), "--seeds", "5",
                             "--controls"]) == 0
    rows = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    sides = {r["side"]: r["inside_all_limits"] for r in rows if "side" in r}
    assert sides == {"system": True, "reference_8bit_weights": False,
                     "reference_renormalised_topk": False}
    assert rows[-1]["ok"] is True
