#!/usr/bin/env python
"""HLO-level overlap analysis for the bucketed gradient exchange.

The bucketing claim (docs/performance.md) is a SCHEDULING claim: the
per-bucket collectives form mutually independent dataflow chains, so
XLA's latency-hiding scheduler is free to start bucket N+1's compute
phases (quantize / dequant-sum / weight update) while bucket N's
collective is still on the wire. This script makes that checkable from
the compiled artifact instead of asserted: it compiles the engine's real
optimizer-boundary step for each exchange mode on the virtual 8-device
CPU mesh, parses the scheduled HLO's def-use graph, and reports

- how many collectives the exchange issues (by op kind),
- how many of them are INDEPENDENT ROOTS — collectives with no other
  collective among their transitive operands, i.e. ready to launch the
  moment their local inputs exist (a latency-hiding scheduler can run
  all roots concurrently with unrelated compute),
- the longest collective-to-collective dependency chain (phases that
  CANNOT overlap each other — e.g. the int8 path's all_to_all feeding
  its all_gather).

Interpretation: the monolithic (one-bucket) exchange has 1 root — every
byte crosses the wire before any dependent compute starts. A k-bucket
plan has k roots: bucket boundaries are exactly the points where the
scheduler may interleave compute. The chain depth stays the per-bucket
phase count (bucketing never lengthens the critical phase chain).

  python benchmarks/communication/overlap_hlo.py        # prints + JSON

Results are committed to overlap_hlo_results.json; the CPU backend
promotes bf16 collectives to f32 (no bf16 all-reduce support), so dtype
rows show the TRACED wire dtype from comm accounting, while op counts and
dependence structure are backend-independent (same HLO graph shape).
"""

import argparse
import json
import os
import re
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

if "JAX_PLATFORMS" not in os.environ:
    os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8")

import flax.linen as nn  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

COLLECTIVE_OPS = ("all-reduce", "all-to-all", "all-gather",
                  "reduce-scatter", "collective-permute")

# ---------------------------------------------------------------------------
# HLO def-use parsing (computation-scoped)
# ---------------------------------------------------------------------------
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*")
# the opcode is the token glued to the operand list's "(": result TYPES can
# be multi-token tuples ("(s8[1,512]{1,0}, ...) all-to-all(...)"), so
# "first word after the type" parsing misreads tuple-returning collectives
_OPCODE = re.compile(r"([\w\-]+)\(")


def parse_computations(hlo_text):
    """{computation -> [(instr_name, op_kind, [operand_names])]} from an
    HLO text dump. Operands are the %refs inside the op's argument list;
    computation refs (to_apply=/calls=/body=...) are excluded by only
    reading the first balanced parenthesized group."""
    comps, cur, cur_name = {}, None, None
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.endswith("{") and ("(" in stripped) and ("=" not in
                                                             stripped.split(
                                                                 "(")[0]):
            m = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)", stripped)
            cur_name = m.group(1) if m else "?"
            cur = comps.setdefault(cur_name, [])
            continue
        if stripped == "}":
            cur = None
            continue
        if cur is None:
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _OPCODE.search(line, m.end())
        if not op:
            continue
        kind = op.group(1)
        lpar = op.end() - 1
        depth, i = 0, lpar
        for i in range(lpar, len(line)):
            if line[i] == "(":
                depth += 1
            elif line[i] == ")":
                depth -= 1
                if depth == 0:
                    break
        args = line[lpar:i + 1]
        operands = re.findall(r"%([\w.\-]+)", args)
        cur.append((name, kind, operands))
    return comps


def collective_structure(hlo_text):
    """Counts + dependence structure of the collectives in one module."""
    comps = parse_computations(hlo_text)
    counts = defaultdict(int)
    roots = 0
    max_chain = 0
    for cname, instrs in comps.items():
        defs = {n: ops for n, _, ops in instrs}
        kinds = {n: k for n, k, _ in instrs}
        colls = [n for n, k, _ in instrs if k in COLLECTIVE_OPS]
        for n in colls:
            counts[kinds[n]] += 1
        if not colls:
            continue
        coll_set = set(colls)

        # collective depth: how many collectives sit on this instr's
        # transitive operand path (memoized DAG walk, self included)
        depth = {}

        def coll_depth(n):
            if n in depth:
                return depth[n]
            depth[n] = 0  # cycle guard (HLO is a DAG; belt and braces)
            d = max((coll_depth(o) for o in defs.get(n, ())), default=0)
            depth[n] = d + (1 if n in coll_set else 0)
            return depth[n]

        for n in colls:
            d = coll_depth(n)
            max_chain = max(max_chain, d)
            if d == 1:  # no collective ancestors: independently schedulable
                roots += 1
    return {"collective_counts": dict(counts),
            "total_collectives": int(sum(counts.values())),
            "independent_roots": int(roots),
            "max_collective_chain": int(max_chain)}


# ---------------------------------------------------------------------------
# engine step compilation per exchange mode
# ---------------------------------------------------------------------------
class MLP(nn.Module):
    """Six-leaf model: enough leaves for a multi-bucket plan."""

    @nn.compact
    def __call__(self, x=None, y=None, deterministic=True):
        h = nn.relu(nn.Dense(32)(x))
        h = nn.relu(nn.Dense(16)(h))
        pred = nn.Dense(1)(h)[:, 0]
        return jnp.mean((pred - y) ** 2)


# ~2 KB budget: the 6 fp32 leaves of MLP pack into 3 buckets
BUCKET_MB = 0.002

MODES = {
    "baseline_per_microstep": {},
    "deferred_monolithic": {
        "tpu": {"grad_exchange": {"deferred": True, "wire_dtype": "fp32",
                                  "bucket_mb": 1024.0}}},
    "deferred_bucketed": {
        "tpu": {"grad_exchange": {"deferred": True, "wire_dtype": "fp32",
                                  "bucket_mb": BUCKET_MB}}},
    "int8_per_leaf": {"communication_data_type": "int8"},
    "int8_bucketed": {
        "communication_data_type": "int8",
        "tpu": {"grad_exchange": {"bucket_mb": BUCKET_MB}}},
}


def compile_mode(extra, gas=2):
    import deepspeed_tpu
    from deepspeed_tpu.parallel import mesh
    from deepspeed_tpu.runtime.dataloader import RepeatingLoader

    mesh.reset_default_topology()
    cfg = {"train_micro_batch_size_per_gpu": 8,
           "gradient_accumulation_steps": gas,
           "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
           "steps_per_print": 10 ** 9}
    cfg.update(extra)
    engine, _, _, _ = deepspeed_tpu.initialize(model=MLP(), config=cfg)
    rng = np.random.RandomState(0)
    batch = {"x": rng.randn(64, 13).astype(np.float32),
             "y": rng.randn(64).astype(np.float32)}
    it = iter(RepeatingLoader([batch]))
    engine.train_batch(it)  # materialize + compile both phases

    fwd_hlo = engine._fwd_bwd_fn.lower(
        engine._params, engine._acc_grads, engine._put_batch(batch),
        engine._rng, engine.micro_steps,
        engine._ls_state.scale if engine.fp16_enabled
        else engine._unit_scale).compile().as_text()
    app_hlo = engine._apply_fn.lower(
        engine._params, engine._opt_state, engine._acc_grads,
        engine._ls_state, engine._lr_factor_now()).compile().as_text()
    plan = engine._exchange.plan
    return {
        "bucket_count": plan.num_buckets if plan is not None else None,
        "micro_step": collective_structure(fwd_hlo),
        "boundary_step": collective_structure(app_hlo),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--gas", type=int, default=2)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)

    results = {}
    for name, extra in MODES.items():
        results[name] = compile_mode(extra, gas=args.gas)
        m = results[name]
        print(f"{name:26s} buckets={m['bucket_count']} "
              f"micro={m['micro_step']['total_collectives']} "
              f"boundary={m['boundary_step']['total_collectives']} "
              f"roots={m['boundary_step']['independent_roots']} "
              f"chain={m['boundary_step']['max_collective_chain']}")

    dm = results["deferred_monolithic"]["boundary_step"]
    db = results["deferred_bucketed"]["boundary_step"]
    i8 = results["int8_per_leaf"]["boundary_step"]
    i8b = results["int8_bucketed"]["boundary_step"]
    findings = {
        # the fp32/bf16 exchange: bucketing multiplies the independently
        # schedulable collectives without deepening any phase chain
        "bucketing_multiplies_roots": db["independent_roots"]
        > dm["independent_roots"],
        "bucketing_keeps_chain_depth": db["max_collective_chain"]
        <= dm["max_collective_chain"],
        # the int8 EQuARX pipeline keeps a >1 phase chain per exchange
        # (quantize->all_to_all->...->all_gather CANNOT overlap itself);
        # bucketing cuts the collective COUNT (launch amortization) while
        # every bucket chain stays independent of the others
        "int8_phases_are_chained": i8["max_collective_chain"] > 1,
        "int8_bucketing_cuts_collectives": i8b["total_collectives"]
        < i8["total_collectives"],
        "int8_bucket_chains_independent": i8b["independent_roots"]
        >= results["int8_bucketed"]["bucket_count"],
        # deferred modes shed every per-leaf grad psum from the micro
        # step; the one surviving micro-step all-reduce is the scalar
        # loss (reported every micro batch in all modes)
        "deferred_microstep_sheds_grad_collectives":
            results["deferred_bucketed"]["micro_step"][
                "total_collectives"] == 1 <
            results["baseline_per_microstep"]["micro_step"][
                "total_collectives"],
    }
    out = {"benchmark": "grad_exchange_overlap_hlo",
           "gas": args.gas,
           "world": 8,
           "model_leaves": 6,
           "bucket_mb": BUCKET_MB,
           "metric_doc": "independent_roots = collectives with no "
                         "collective among their transitive operands "
                         "(schedulable concurrently with compute and "
                         "each other); max_collective_chain = phases "
                         "that must serialize",
           "modes": results,
           "findings": findings}
    print(json.dumps(findings, indent=2))

    path = args.out or os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "overlap_hlo_results.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    os.replace(tmp, path)
    print(f"# wrote {path}", file=sys.stderr)
    return 0 if all(findings.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
