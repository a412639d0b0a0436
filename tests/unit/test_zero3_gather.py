"""ZeRO-3's gather at the point of use (runtime/zero/gather.py), on the
virtual 8-device mesh: what the compiled stage-3 step contains, that it
trains as stage 0 does, what composes with it, what the plan event says,
and that the mechanism is absent wherever there is nothing to gather."""

import contextlib
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.bert import BertConfig, BertForPreTraining
from deepspeed_tpu.models.transformer_lm import (
    GPT, GPTConfig, gpt_tp_rules, quantize_block_params)
from deepspeed_tpu.parallel.mesh import (
    MeshTopology, reset_default_topology, set_default_topology)
from deepspeed_tpu.runtime import engine as engine_mod
from deepspeed_tpu.runtime import step as step_mod
from deepspeed_tpu.runtime.zero import gather as zero3
from deepspeed_tpu.runtime.zero.sharding import ZeroShardingRules
from deepspeed_tpu.telemetry.bus import KIND_ZERO3_GATHER_PLAN, telemetry_bus
from deepspeed_tpu.utils.tree import flatten_with_paths

from unit.simple_model import tiny_gpt_config

F32, BF16 = jnp.float32, jnp.bfloat16
PRECISIONS = {"fp32": (F32, F32), "fp32-bf16": (F32, BF16),
              "bf16": (BF16, BF16)}
SGD = {"type": "SGD", "params": {"lr": 0.05, "momentum": 0.9}}


def gpt(precision="fp32", **kw):
    param_dtype, dtype = PRECISIONS[precision]
    base = dict(n_embd=64, n_layer=3, vocab_size=256, dtype=dtype,
                param_dtype=param_dtype, scan_layers=True, remat=True)
    base.update(kw)
    return GPT(tiny_gpt_config(**base))


def moe(precision="fp32"):
    """OLMoE's shape in small: dropless top-k of SwiGLU experts, RMSNorm,
    rotary, qk-norm, untied head (tests/unit/test_moe_dropless.py)."""
    param_dtype, dtype = PRECISIONS[precision]
    return GPT(GPTConfig(
        vocab_size=256, n_positions=64, n_embd=64, n_layer=2, n_head=4,
        intermediate_size=32, norm="rmsnorm", activation="silu",
        use_bias=False, rotary=True, learned_positions=False,
        tie_word_embeddings=False, qk_norm=True, dtype=dtype,
        param_dtype=param_dtype, remat=True, scan_layers=True,
        use_flash_attention=False, moe_num_experts=8, moe_top_k=3,
        moe_drop_tokens=False, moe_gated_experts=True,
        moe_aux_loss_coef=0.01, moe_z_loss_coef=0.001))


def bert(precision="fp32"):
    param_dtype, dtype = PRECISIONS[precision]
    return BertForPreTraining(BertConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=128,
        max_position_embeddings=64, dtype=dtype, param_dtype=param_dtype,
        remat=True, remat_policy="selective"))


MODELS = {"gpt-scan": gpt, "gpt-loop": lambda p: gpt(p, scan_layers=False),
          "bert": bert, "moe": moe}


def ahead(n_layer):
    """What the tiny model's loop gathers ahead of use: the first matrix
    a layer reads (``c_attn``'s 64 x 192 kernel) for the layer after a
    turn's own, and every layer's vectors (832 elements) before the loop.
    A bucket of k - 1 times that lets a turn gather for k layers
    (``zero3.turn_length``); ``zero3.layers_ahead`` hands on by one turn,
    so 2 is the most, which DeepSpeed's default of 5e7 gives too."""
    return 64 * 192 + n_layer * (192 + 256 + 6 * 64)



def engine_for(model, stage, topo, precision="fp32", threshold=0,
               layers_per_turn=None, **extra):
    reset_default_topology()
    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": SGD,
        "zero_optimization": {"stage": stage,
                              "stage3_param_persistence_threshold": threshold},
        "steps_per_print": 10 ** 9,
    }
    if layers_per_turn is not None:
        cfg["zero_optimization"]["stage3_prefetch_bucket_size"] = (
            (layers_per_turn - 1) * ahead(model.config.n_layer))
    if PRECISIONS[precision][1] == BF16:
        cfg["bf16"] = {"enabled": True}
    cfg.update(extra)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=cfg, topology=topo, seed=3)
    return engine


def batches(engine, n=3, seq=64, vocab=256, seed=11):
    rng = np.random.RandomState(seed)
    gb = (engine.train_micro_batch_size_per_gpu
          * engine.topology.data_parallel_size)
    out = []
    for _ in range(n):
        ids = rng.randint(0, vocab, size=(gb, seq)).astype(np.int32)
        out.append({"input_ids": ids, "labels": ids})
    return out


def train(engine, data):
    return [float(engine.train_batch(iter([b]))) for b in data]


def fsdp4():
    return MeshTopology(fsdp=4, dp=1, devices=jax.devices()[:4])


@contextlib.contextmanager
def plan_events():
    events = []

    def on_event(ev):
        if ev["kind"] == KIND_ZERO3_GATHER_PLAN:
            events.append(ev)

    telemetry_bus.subscribe(on_event)
    try:
        yield events
    finally:
        telemetry_bus.unsubscribe(on_event)


def instructions(text, opcode):
    """HLO instructions of one opcode as (result type, operands, op_name)."""
    out = []
    for line in text.splitlines():
        m = re.search(r" = (\(.*?\)|\S+) %s\((.*?)\)[,\s]" % opcode, line)
        if m:
            name = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), m.group(2), name.group(1) if name else ""))
    return out


# ---------------------------------------------------------------------------
# what the compiled stage-3 step contains
# ---------------------------------------------------------------------------
def computations(text):
    """HLO computations by name, each the text of its instructions."""
    out, name = {}, None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if m:
            name = m.group(1)
            out[name] = []
        elif name is not None:
            out[name].append(line)
    return {name: "\n".join(lines) for name, lines in out.items()}


def dims_of(result):
    return [int(d) for d in re.search(r"\[([\d,]*)\]", result).group(1)
            .split(",") if d]


LAYERS, TURN, SEQ = 4, 2, 32


@pytest.fixture(scope="module")
def stage3_step():
    """The stage-3 step of a small scanned GPT with remat over fsdp=4,
    float32 parameters and bf16 compute (the four-chip cell in small): four
    layers, two a turn of the layer loop. 32 positions, so that no
    activation has a weight's shape."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    with plan_events() as events:
        engine = engine_for(gpt("fp32-bf16", n_layer=LAYERS), 3, fsdp4(),
                            "fp32-bf16", layers_per_turn=TURN)
        data = batches(engine, seq=SEQ)
        losses = train(engine, data)
    compiled = engine.compiled_step_programs()["train_step"]
    lowered = engine._train_step_fn.lower(
        *[engine_mod._avals_like(x) for x in (
            engine._params, engine._opt_state, engine._ls_state)],
        engine._last_batch_aval, engine_mod._avals_like(engine._rng),
        engine.micro_steps, jnp.float32(1.0)).as_text()
    yield engine, losses, events, compiled.as_text(), lowered
    reset_default_topology()


def test_no_activation_is_resharded(stage3_step):
    """An all-to-all moves activations: the partitioner read the stored
    shard as tensor parallelism. With the use site constrained none is
    left."""
    text = stage3_step[3]
    assert not instructions(text, "all-to-all")
    assert "all-to-all" not in text


def test_each_layer_gathers_its_weights_inside_the_loop(stage3_step):
    text = stage3_step[3]
    gathers = [g for g in instructions(text, "all-gather")
               if "/while/body/" in g[2] and zero3.SCOPE_ZERO3_GATHER in g[2]]
    assert gathers
    # forward and, under remat, once more in the backward pass
    assert any("rematted_computation" in g[2] for g in gathers)
    assert any("rematted_computation" not in g[2] for g in gathers)
    # one layer at a time: no gathered result carries the layer axis
    for result, _, _ in gathers:
        dims = dims_of(result)
        assert len(dims) <= 2 or dims[0] == 1, result


def test_a_turn_gathers_the_next_layers_first_weight(stage3_step):
    """Each loop, forward and backward, gathers ``c_attn``'s kernel (the
    first weight a layer reads) once before its first turn and once in
    its body: turn i issues layer i + 1's (i - 1's, walking down) and
    carries the result to the turn that reads it. The layer's own gathers
    of a turn, forward and recomputed, are the other three kernels."""
    text = stage3_step[3]
    comps = computations(text)
    c_attn = [64, 3 * 64]

    def heads(text):
        return [g for g in instructions(text, "all-gather")
                if "/%s/" % zero3.SCOPE_ZERO3_GATHER in g[2]
                and dims_of(g[0])[-2:] == c_attn]

    bodies = {body: heads(comps[body])
              for body in set(re.findall(r"body=%?([\w.\-]+)", text))}
    bodies = {body: found for body, found in bodies.items() if found}
    assert len(bodies) == 2, list(bodies)          # forward, backward
    assert all(len(found) == 1 for found in bodies.values())
    assert len(heads(text)) == 4                   # two more ahead of them
    for result, _, name in heads(text):
        dims = dims_of(result)
        assert len(dims) <= 2 or set(dims[:-2]) == {1}, result
        # not one of a layer's own: those are gathered where it recomputes
        assert "rematted_computation" not in name
    # and each loop carries ONE whole c_attn kernel from turn to turn
    for result, _, name in instructions(text, "while"):
        if name.endswith("/h/while"):
            carried = [e for e in re.findall(r"\w+\[[\d,]*\]", result)
                       if dims_of(e)[-2:] == c_attn]
            assert len(carried) == 1 and len(dims_of(carried[0])) == 2


def test_no_gathered_weight_is_saved_for_the_backward_pass(stage3_step):
    """What the forward loop hands the backward loop is each layer's input
    and nothing gathered: no loop carries or stacks an array of a whole
    matrix's shape for more than one layer (ZeRO-3 keeps a quarter)."""
    text = stage3_step[3]
    whole = ([64, 3 * 64], [64, 4 * 64], [4 * 64, 64])
    loops = [r for r in instructions(text, "while")]
    assert len(loops) >= 2
    saved_inputs = 0
    for result, _, _ in loops:
        for element in re.findall(r"\w+\[[\d,]*\]", result):
            dims = dims_of(element)
            # the inputs of every layer but the first, which is the
            # stack's own input
            if dims[-3:] == [2, SEQ, 64] \
                    and np.prod(dims[:-3]) == LAYERS - 1:
                saved_inputs += 1
            if dims[-2:] in whole:
                assert np.prod(dims[:-2]) == 1, element
    assert saved_inputs >= 2               # saved by one loop, read by the other


def test_the_gather_moves_the_compute_dtype(stage3_step):
    """The CPU backend has no bf16 collectives: its compiled text gathers
    float32 and converts after (the TPU's gathers bf16, PERF.md PR 29). So
    this reads the lowering: every sharding constraint under the scope is
    on a bf16 matrix or on float32 vectors (one, or a leaf's for every
    layer)."""
    lowered = stage3_step[4]
    seen = set()
    for line in lowered.splitlines():
        if "sharding_constraint" not in line and "@Sharding" not in line:
            continue
        m = re.search(r"tensor<([\dx]*)x(\w+)>", line)
        dims, dtype = m.group(1).split("x"), m.group(2)
        if len(dims) == 2 and dims[0] == str(LAYERS):
            dims = dims[1:]   # every layer's vectors, whole ahead of a loop
        seen.add((len(dims), dtype))
    assert (2, "bf16") in seen
    assert (2, "f32") not in seen and (3, "f32") not in seen, seen


def test_layer_gradients_are_reduce_scattered(stage3_step):
    """On the TPU: a reduce-scatter (or its windowed-einsum form). The CPU
    backend lowers one as an all-reduce of the layer's gradients followed
    by each chip's dynamic-slice; this matches either, inside the backward
    loop, and holds the parameter's gradient to the stored sharding."""
    engine, _, _, text, _ = stage3_step
    body = [r for op in ("reduce-scatter", "all-reduce")
            for r in instructions(text, op)
            if "transpose(jvp(GPT))/h/while/body" in r[2]]
    assert body
    if not instructions(text, "reduce-scatter"):
        assert "dynamic-slice" in text
    # the whole gradient of no layer's matrix is an output of the loop:
    # the updated parameters leave the step as they are stored
    for path, leaf in flatten_with_paths(engine.params).items():
        if path.endswith("kernel"):
            assert "fsdp" in str(leaf.sharding.spec), (path,
                                                       leaf.sharding.spec)


def test_the_plan_event_carries_what_the_plan_implies(stage3_step):
    engine, _, events, _, _ = stage3_step
    assert len(events) == 1            # once per program, not per step
    ev = events[0]
    assert ev["program"] == "train_step" and ev["fsdp"] == 4
    # a turn gathers for two layers, its own and the next one's first
    # weight: only the gather ahead of each loop, forward and backward,
    # has nothing of its loop to run under
    assert ev["layers_per_turn"] == TURN
    assert ev["gathers_at_turn_head_per_step"] == 2
    rules, n = engine.sharding_rules, 4
    gathered = persistent = g_bytes = s_bytes = 0
    for path, leaf in flatten_with_paths(engine.params).items():
        if path.startswith("ln_f"):
            continue                   # a vector outside the loop: GSPMD's
        in_loop = path.startswith("h/")     # stacked: a layer axis first
        spec = tuple(rules.param_spec(path, leaf.shape))[int(in_loop):]
        if "fsdp" not in spec:
            persistent += 1
            continue
        gathered += 1
        matrix = leaf.ndim - int(in_loop) >= 2
        wire = leaf.size * (2 if matrix else 4) * (n - 1) // n
        # the layers' leaves are gathered again by the rematerialised
        # backward pass; the tied table is read twice (lookup and head)
        g_bytes += wire * (2 if path.startswith(("h/", "wte")) else 1)
        s_bytes += wire * (2 if path.startswith("wte") else 1)
    assert ev["leaves_gathered"] == gathered
    assert ev["leaves_persistent"] == persistent
    assert ev["bytes_gathered_per_step"] == g_bytes
    assert ev["bytes_reduce_scattered_per_step"] == s_bytes
    # the same collectives in another order: with nothing gathered ahead
    # every layer's first gather, forward and recomputed, waits at a
    # turn's head; a larger bucket hands on by one turn all the same; and
    # all of them gather and scatter the same bytes
    for turn, heads in ((1, 2 * LAYERS), (3, 2)):
        with plan_events() as other:
            one = engine_for(gpt("fp32-bf16", n_layer=LAYERS), 3, fsdp4(),
                             "fp32-bf16", layers_per_turn=turn)
            place(one, batches(one, n=1, seq=SEQ)[0])
            step_lowering(one)
        assert len(other) == 1
        assert other[0]["layers_per_turn"] == min(turn, 2)
        assert other[0]["gathers_at_turn_head_per_step"] == heads
        for key in ("bytes_gathered_per_step",
                    "bytes_reduce_scattered_per_step", "leaves_gathered"):
            assert other[0][key] == ev[key], (turn, key)


def test_every_matrix_gather_carries_the_scope(stage3_step):
    """``program_scopes()`` attributes a collective by its op_name: the
    four kernels of a layer, forward and recomputed, sit under the scope
    (a vector's gather may be emitted at the scan's slice instead)."""
    text = stage3_step[3]
    named = [g for g in instructions(text, "all-gather")
             if "/while/body/" in g[2] and "/%s/" % zero3.SCOPE_ZERO3_GATHER
             in g[2]]
    assert len(named) >= 4 * 2


# ---------------------------------------------------------------------------
# it trains as stage 0 does
# ---------------------------------------------------------------------------
# float32: the tolerances of test_zero.py::test_zero_matches_stage0. With
# bf16 compute both programs sum bf16 gradients over the chips in another
# order, one bf16 ulp (2^-8) apart on a few elements.
TOLERANCE = {"fp32": dict(rtol=2e-5, atol=2e-6),
             "fp32-bf16": dict(rtol=2e-2, atol=2e-3),
             "bf16": dict(rtol=4e-2, atol=2e-2)}


# how the layers run: (scanned, layers a turn gathers for, layers). Over
# two layers the loop ahead has one turn and the layer after it; with
# DeepSpeed's own bucket (None: the key left alone) a turn gathers for two.
LAYER_LOOPS = {"scan-k1": (True, 1, 3), "scan-k2": (True, 2, 4),
               "scan-k2-odd": (True, 2, 3), "scan-k2-two": (True, 2, 2),
               "scan-default": (True, None, 3), "loop": (False, None, 3)}


@pytest.mark.parametrize("loop", list(LAYER_LOOPS))
@pytest.mark.parametrize("precision", list(PRECISIONS))
def test_stage3_matches_stage0(eight_devices, precision, loop):
    scan, turn, layers = LAYER_LOOPS[loop]
    base = engine_for(gpt(precision, scan_layers=scan, n_layer=layers), 0,
                      MeshTopology(dp=4, devices=jax.devices()[:4]),
                      precision)
    data = batches(base)
    ref_losses = train(base, data)
    ref = {k: np.asarray(v, np.float32)
           for k, v in flatten_with_paths(base.params).items()}

    with plan_events() as events:
        engine = engine_for(gpt(precision, scan_layers=scan, n_layer=layers),
                            3, fsdp4(), precision, layers_per_turn=turn)
        losses = train(engine, data)
    assert events[0]["layers_per_turn"] == ((turn or 2) if scan else 1)
    tol = TOLERANCE[precision]
    np.testing.assert_allclose(losses, ref_losses, **tol)
    for path, leaf in flatten_with_paths(engine.params).items():
        np.testing.assert_allclose(np.asarray(leaf, np.float32), ref[path],
                                   err_msg=path, **tol)


def test_split_forward_backward_path_gathers_too(eight_devices):
    """gas > 1 runs ``fwd_bwd`` + ``apply``: the same context, its own
    program name."""
    with plan_events() as events:
        engine = engine_for(gpt(), 3, fsdp4(),
                            gradient_accumulation_steps=2)
        data = batches(engine, n=4)
        losses = [float(engine.train_batch(iter(data[i:i + 2])))
                  for i in (0, 2)]
    assert np.isfinite(losses).all()
    assert [e["program"] for e in events] == ["fwd_bwd"]
    text = engine.compiled_step_programs()["fwd_bwd"].as_text()
    assert "all-to-all" not in text
    assert "/%s/" % zero3.SCOPE_ZERO3_GATHER in text


def test_eval_gathers_and_scatters_nothing(eight_devices):
    engine = engine_for(gpt(), 3, fsdp4())
    data = batches(engine, n=1)
    train(engine, data)
    with plan_events() as events:
        logits = engine.eval_batch({"input_ids": data[0]["input_ids"]})
    assert np.isfinite(np.asarray(logits)).all()
    assert [e["program"] for e in events] == ["eval"]
    assert events[0]["leaves_gathered"] > 0


# ---------------------------------------------------------------------------
# what composes with it
# ---------------------------------------------------------------------------
def test_tp_shard_stays_through_the_gather(eight_devices):
    topo = MeshTopology(fsdp=2, tp=2, dp=1, devices=jax.devices()[:4])
    set_default_topology(topo)
    rules = ZeroShardingRules(topo, stage=3, tp_rules=gpt_tp_rules)
    shape = (3, 64, 256)                         # stacked c_fc kernel
    path = ("h", "block", "mlp", "c_fc", "kernel")
    stored = rules.param_spec("/".join(path), shape)
    assert "tp" in str(stored) and "fsdp" in str(stored)
    sharding = jax.sharding.NamedSharding(topo.mesh, stored)
    w = jax.device_put(jnp.ones(shape, F32), sharding)

    def use(w):  # one layer's slice, as the scan's body sees it
        with zero3.gather_context(rules, "train_step"):
            return zero3.gather_tree(w[0], path, BF16, stacked=3)

    def loss(w):
        return jnp.sum(use(w).astype(F32) ** 2)

    out = jax.jit(use)(w)
    assert out.dtype == BF16
    assert tuple(out.sharding.spec) in ((None, "tp"), (None, ("tp",)))
    grad = jax.jit(jax.grad(loss), out_shardings=sharding)(w)
    assert grad.dtype == F32 and grad.shape == shape
    np.testing.assert_array_equal(np.asarray(grad[0]), 2.0)
    np.testing.assert_array_equal(np.asarray(grad[1:]), 0.0)


def test_tp_by_fsdp_engine_step(eight_devices):
    topo = MeshTopology(fsdp=2, tp=2, dp=1, devices=jax.devices()[:4])
    base = engine_for(gpt(), 0, MeshTopology(dp=2, devices=jax.devices()[:2]))
    data = batches(base)
    ref_losses = train(base, data)
    with plan_events() as events:
        engine = engine_for(gpt(), 3, topo)
        losses = train(engine, data)
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-5, atol=2e-6)
    assert events and events[0]["fsdp"] == 2
    spec = engine.params["h"]["block"]["mlp"]["c_fc"]["kernel"].sharding.spec
    assert "tp" in str(spec) and "fsdp" in str(spec)


def test_a_leaf_under_the_persistence_threshold_is_not_gathered(
        eight_devices):
    # 3 x 64 x 64 = 12,288 (attn/c_proj) stays whole; 3 x 64 x 192 and the
    # MLP's 3 x 64 x 256 are sharded and gathered
    with plan_events() as events:
        engine = engine_for(gpt(), 3, fsdp4(), threshold=20_000)
        train(engine, batches(engine, n=1))
    flat = flatten_with_paths(engine.params)
    whole = [p for p, x in flat.items() if "fsdp" not in str(x.sharding.spec)]
    assert "h/block/attn/c_proj/kernel" in whole
    assert "fsdp" in str(flat["h/block/mlp/c_fc/kernel"].sharding.spec)
    ev = events[0]
    assert ev["leaves_gathered"] == len(flat) - len(whole)
    assert ev["leaves_persistent"] == len(
        [p for p in whole if not p.startswith("ln_f")])
    text = engine.compiled_step_programs()["train_step"].as_text()
    assert not [g for g in instructions(text, "all-gather")
                if "c_proj" in g[2] and "attn" in g[2]]


def test_everything_persistent_gathers_nothing(eight_devices):
    with plan_events() as events:
        engine = engine_for(gpt(), 3, fsdp4(), threshold=10 ** 9)
        train(engine, batches(engine, n=1))
    assert events[0]["leaves_gathered"] == 0
    assert events[0]["bytes_gathered_per_step"] == 0
    text = engine.compiled_step_programs()["train_step"].as_text()
    assert "/%s/" % zero3.SCOPE_ZERO3_GATHER not in text


def test_quantized_weights_compose(eight_devices):
    """int8-at-rest leaves are gathered as int8 (and their scales as they
    are) before the dequantisation inside the loop."""
    topo = fsdp4()
    set_default_topology(topo)
    dense = gpt(remat=False)
    ids = np.random.RandomState(0).randint(0, 256, (4, 64)).astype(np.int32)
    params = dense.init(jax.random.PRNGKey(0), ids)["params"]
    qparams = dict(params, h=quantize_block_params(params["h"]))
    quantized = gpt(remat=False, quantized_weights=True)
    want = quantized.apply({"params": qparams}, ids)
    rules = ZeroShardingRules(topo, stage=3)

    def forward(p, ids):
        with zero3.gather_context(rules, "eval") as plan:
            out = quantized.apply({"params": p}, ids)
        forward.plan = plan
        return out

    shardings = rules.param_sharding_tree(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                     qparams))
    placed = jax.device_put(qparams, shardings)
    got = jax.jit(forward)(placed, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    q = [p for p in forward.plan.gathered if p[0].endswith("/q")]
    assert q and all(forward.plan.gathered[p][1] == 0 for p in q)


def test_param_offload_composes(eight_devices):
    """Host-streamed stacks (ops/streaming.py; on the CPU the placement is
    structure only): stream, then gather, then use."""
    with plan_events() as events:
        engine = engine_for(
            gpt("fp32-bf16", param_offload=True), 3, fsdp4(), "fp32-bf16",
            optimizer={"type": "Adam", "params": {"lr": 1e-3}},
            zero_optimization={
                "stage": 3, "stage3_param_persistence_threshold": 0,
                "offload_param": {"device": "cpu"},
                "offload_optimizer": {"device": "cpu"}})
        data = batches(engine, n=1) * 4
        losses = train(engine, data)
    assert losses[-1] < losses[0], losses
    assert events and events[0]["leaves_gathered"] > 0


# ---------------------------------------------------------------------------
# absent wherever there is nothing to gather
# ---------------------------------------------------------------------------
def place(engine, batch):
    """The engine's state and one placed batch, without compiling a step
    (``step_lowering`` only lowers)."""
    set_default_topology(engine.topology)
    engine._init_state(dict(batch))
    engine._put_batch(dict(batch))


def step_lowering(engine):
    """Hash of the lowered text of a freshly built ``train_step``."""
    fn = engine._build_train_step()
    avals = engine_mod._avals_like
    text = fn.lower(
        avals(engine._params), avals(engine._opt_state),
        avals(engine._ls_state), engine._last_batch_aval,
        avals(engine._rng), engine.micro_steps, jnp.float32(1.0)).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


def model_batches(name, engine):
    data = batches(engine, n=1)
    if name == "bert":
        labels = np.where(np.arange(64)[None, :] % 7 == 0,
                          data[0]["input_ids"], -100).astype(np.int32)
        data = [{"input_ids": data[0]["input_ids"], "labels": labels}]
    return data


INERT = {"stage0-dp8": (0, dict(dp=8)), "stage1-fsdp8": (1, dict(fsdp=8)),
         "stage2-fsdp4-dp2": (2, dict(fsdp=4, dp=2)),
         "stage3-one-chip": (3, dict(n=1))}


@pytest.mark.parametrize("where", list(INERT))
@pytest.mark.parametrize("precision", ["fp32-bf16", "bf16"])
@pytest.mark.parametrize("name", list(MODELS))
def test_inert_wherever_fsdp_is_one_or_the_stage_below_three(
        eight_devices, monkeypatch, name, precision, where):
    """The lowered step is, to the byte, what it is when the engine never
    enters the context, and no plan is published."""
    stage, mesh = INERT[where]
    mesh = dict(mesh)
    n = mesh.pop("n", 8)
    topo = MeshTopology(devices=jax.devices()[:n], **mesh)
    with plan_events() as events:
        engine = engine_for(MODELS[name](precision), stage, topo, precision)
        place(engine, model_batches(name, engine)[0])
        with_context = step_lowering(engine)
    assert not events

    @contextlib.contextmanager
    def never_entered(rules, program):
        yield None

    monkeypatch.setattr(step_mod, "gather_context", never_entered)
    assert step_lowering(engine) == with_context


def test_the_hash_does_tell_programs_apart(eight_devices, monkeypatch):
    """The control of the test above: under stage 3 over fsdp=4 the same
    comparison differs."""
    engine = engine_for(gpt(), 3, fsdp4())
    place(engine, batches(engine, n=1)[0])
    with_context = step_lowering(engine)

    @contextlib.contextmanager
    def never_entered(rules, program):
        yield None

    monkeypatch.setattr(step_mod, "gather_context", never_entered)
    assert step_lowering(engine) != with_context


def pinned_step(precision, layers_per_turn):
    """Hash of the lowered stage-3 step of the small scanned GPT over
    fsdp=4 (``python tests/unit/test_zero3_gather.py`` prints what
    ``data/zero3_step_hashes.json`` holds: recorded on 9107503, the parent
    of the PR that made a turn hold more than one layer, where the two
    keys were parsed and read by nothing)."""
    engine = engine_for(gpt(precision), 3, fsdp4(), precision,
                        layers_per_turn=layers_per_turn)
    place(engine, batches(engine, n=1)[0])
    return step_lowering(engine)


@pytest.mark.parametrize("precision", ["fp32-bf16", "bf16"])
def test_a_prefetch_bucket_of_zero_is_the_one_layer_turn_as_it_was(
        eight_devices, precision):
    """``stage3_prefetch_bucket_size: 0``: nothing may be gathered ahead
    of use, a turn holds one layer, and the step lowers to the byte as it
    did before a turn could hold more. The control: two layers a turn do
    not."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "zero3_step_hashes.json")) as fh:
        recorded = json.load(fh)
    assert pinned_step(precision, 1) == recorded[precision]
    assert pinned_step(precision, 2) != recorded[precision]


# ---------------------------------------------------------------------------
# how many layers a turn holds
# ---------------------------------------------------------------------------
# GPT-2 1.3B's layer as the four-chip cell gathers it: c_attn 2048 x 6144
# (the first matrix a layer reads), c_proj 2048 x 2048, c_fc 2048 x 8192
# and its mirror; the vectors are a few thousand elements
LAYER_1P3B = 2048 * (6144 + 2048 + 8192 + 8192)
AHEAD_1P3B = 2048 * 6144
BUCKET, LIVE = 50_000_000, 1_000_000_000        # DeepSpeed's defaults


@pytest.mark.parametrize("case, args, want", [
    ("defaults-1.3b", (24, LAYER_1P3B, AHEAD_1P3B, BUCKET, LIVE), 4),
    ("bucket-0", (24, LAYER_1P3B, AHEAD_1P3B, 0, LIVE), 1),
    ("bucket-below-one-leaf", (24, LAYER_1P3B, AHEAD_1P3B,
                               AHEAD_1P3B - 1, LIVE), 1),
    ("bucket-of-one-leaf", (24, LAYER_1P3B, AHEAD_1P3B, AHEAD_1P3B, LIVE), 2),
    ("live-below-two-layers", (24, LAYER_1P3B, AHEAD_1P3B, BUCKET,
                               2 * LAYER_1P3B - 1), 1),
    ("live-of-two-layers", (24, LAYER_1P3B, AHEAD_1P3B, BUCKET,
                            2 * LAYER_1P3B), 2),
    ("live-0", (24, LAYER_1P3B, AHEAD_1P3B, BUCKET, 0), 1),
    ("never-more-than-the-loop", (3, 64 * 64 * 12, 64 * 256, BUCKET, LIVE),
     3),
    ("one-layer", (1, LAYER_1P3B, AHEAD_1P3B, BUCKET, LIVE), 1),
    ("nothing-gathered", (24, 0, 0, BUCKET, LIVE), 1),
])
def test_turn_length(case, args, want):
    assert zero3.turn_length(*args) == want
    n_layers = args[0]
    assert 1 <= zero3.turn_length(*args) <= n_layers


def test_the_engine_hands_the_two_keys_to_the_rules(eight_devices):
    engine = engine_for(gpt(), 3, fsdp4())
    assert engine.sharding_rules.prefetch_bucket_size == BUCKET
    assert engine.sharding_rules.max_live_parameters == LIVE
    engine = engine_for(gpt(), 3, fsdp4(), zero_optimization={
        "stage": 3, "stage3_prefetch_bucket_size": 7,
        "stage3_max_live_parameters": 11})
    assert engine.sharding_rules.prefetch_bucket_size == 7
    assert engine.sharding_rules.max_live_parameters == 11
    # rules built by hand, with no ZeRO section, prefetch nothing
    assert ZeroShardingRules(fsdp4(), stage=3).prefetch_bucket_size == 0


@pytest.mark.parametrize("layers_whole, want", [(2, 2), (1, 1)])
def test_max_live_parameters_caps_the_turn(eight_devices, layers_whole,
                                           want):
    """A tiny layer's gathered elements (the four kernels and the vectors:
    no persistence threshold here) twice over may be whole at once, or not
    quite: a turn gathers for two layers or for one, whatever the bucket
    allows."""
    layer = 64 * (192 + 64 + 256 + 256) + 192 + 256 + 6 * 64
    live = 2 * layer - (0 if layers_whole == 2 else 1)
    with plan_events() as events:
        engine = engine_for(gpt(n_layer=4), 3, fsdp4(), zero_optimization={
            "stage": 3, "stage3_param_persistence_threshold": 0,
            "stage3_max_live_parameters": live})
        place(engine, batches(engine, n=1)[0])
        step_lowering(engine)
    assert events[0]["layers_per_turn"] == want


def test_a_loop_the_hand_on_cannot_run_keeps_the_scan(eight_devices):
    """Selective remat saves a layer's dots for the backward pass and the
    loop ahead recomputes whole layers: under that policy the scan stays
    what it was, one layer a turn, and says so."""
    with plan_events() as events:
        engine = engine_for(gpt(remat_policy="selective"), 3, fsdp4())
        losses = train(engine, batches(engine))
    assert np.isfinite(losses).all()
    assert events[0]["layers_per_turn"] == 1
    assert events[0]["gathers_at_turn_head_per_step"] == 2 * 3


def test_dropout_and_a_routers_loss_run_through_the_loop_ahead(
        eight_devices):
    """The loop ahead gives each layer its own dropout and gating keys and
    sums the layers' auxiliary losses: a dropless MoE with dropout trains
    as stage 0 does at the first step (where no rounding has compounded)
    and goes on falling."""
    base = engine_for(moe(), 0, MeshTopology(dp=4, devices=jax.devices()[:4]))
    data = batches(base, n=1) * 4
    ref = train(base, data)
    with plan_events() as events:
        engine = engine_for(moe(), 3, fsdp4())
        losses = train(engine, data)
    assert events[0]["layers_per_turn"] == 2
    np.testing.assert_allclose(losses, ref, rtol=2e-4, atol=2e-5)
    dropped = engine_for(gpt(dropout=0.1, n_layer=4), 3, fsdp4())
    losses = train(dropped, batches(dropped, n=1) * 4)
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_serving_enters_no_context(eight_devices):
    """``init_inference`` traces the same modules with no context: the
    class comes back as it went in."""
    assert zero3.current_plan() is None
    assert zero3.gathered_on_use(GPT, ("x",), BF16) is GPT
    tree = {"kernel": jnp.ones((4, 4))}
    assert zero3.gather_tree(tree, ("x",), BF16) is tree


if __name__ == "__main__":
    print(json.dumps({precision: pinned_step(precision, 1)
                      for precision in ("fp32-bf16", "bf16")}, indent=1))
