"""FLOPS profiler via XLA HLO cost analysis.

Reference ``profiling/flops_profiler/profiler.py:17`` monkey-patches
``torch.nn.functional`` and tensor methods to COUNT MACs per module
(:788-830) and uses module hooks for latency. On TPU the compiler already
knows: ``jit(f).lower(...).compile().cost_analysis()`` returns exact HLO
flops and bytes for the whole fused program — more accurate than
patch-counting (it sees XLA fusions, remat recompute, and collective
traffic). Latency comes from timed, ``block_until_ready``-fenced replays.

``get_model_profile`` is the reference's public entry (same name); the
``FlopsProfiler`` class profiles any jitted callable and pretty-prints a
summary with achieved TFLOPS vs the step wall clock.
"""

import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

from deepspeed_tpu.utils.logging import logger


def _num(x) -> float:
    try:
        return float(x)
    except (TypeError, ValueError):
        return 0.0


def params_count(params) -> int:
    return int(sum(np.prod(np.shape(p))
                   for p in jax.tree.leaves(params)))


def cost_of(compiled) -> Dict[str, float]:
    """HLO cost metrics of an already-compiled program
    (``jax.stages.Compiled``): flops, bytes accessed, and the compiler's
    optimal-seconds estimate."""
    ca = compiled.cost_analysis() or {}
    return {
        "flops": _num(ca.get("flops", 0)),
        "bytes_accessed": _num(ca.get("bytes accessed", 0)),
        "optimal_seconds": _num(ca.get("optimal_seconds", 0)),
    }


def cost_analysis(fn: Callable, *args, **kwargs) -> Dict[str, float]:
    """Compile ``fn`` for the given args and return its :func:`cost_of`."""
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    return cost_of(jitted.lower(*args, **kwargs).compile())


def measure_latency(fn: Callable, *args, warmup: int = 1, iters: int = 5,
                    **kwargs) -> float:
    """Median wall-clock seconds of a device-fenced call."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kwargs))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kwargs))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def number_to_string(num: float, units: Optional[str] = None,
                     precision: int = 2) -> str:
    """Human-readable magnitudes (reference profiler's flops_to_string
    family, one generic implementation)."""
    scale = {"T": 1e12, "G": 1e9, "M": 1e6, "K": 1e3, "": 1.0}
    if units is None:
        for units, s in scale.items():
            if abs(num) >= s and s > 1:
                break
        else:
            units = ""
    return f"{num / scale[units]:.{precision}f} {units}"


flops_to_string = number_to_string
params_to_string = number_to_string
macs_to_string = number_to_string


class FlopsProfiler:
    """Profile a jitted step function (reference FlopsProfiler, but the
    "model" is a function + example args, the JAX unit of execution)."""

    def __init__(self, fn: Callable = None, ds_config=None):
        self.fn = fn
        self.config = getattr(ds_config, "flops_profiler", None)
        self.profile: Dict[str, Any] = {}

    def profile_fn(self, *args, measure_time: bool = True,
                   params=None, **kwargs) -> Dict[str, Any]:
        costs = cost_analysis(self.fn, *args, **kwargs)
        prof = dict(costs)
        prof["params"] = params_count(params) if params is not None else None
        if measure_time:
            latency = measure_latency(self.fn, *args, **kwargs)
            prof["latency_s"] = latency
            prof["achieved_tflops"] = (
                costs["flops"] / latency / 1e12 if latency > 0 else 0.0)
            prof["achieved_gbps"] = (
                costs["bytes_accessed"] / latency / 1e9 if latency > 0
                else 0.0)
        self.profile = prof
        return prof

    def print_profile(self) -> str:
        p = self.profile
        lines = ["-" * 60, "deepspeed_tpu flops profiler (HLO cost analysis)"]
        if p.get("params") is not None:
            lines.append(f"params:            "
                         f"{number_to_string(p['params'])}")
        lines.append(f"flops per call:    "
                     f"{number_to_string(p.get('flops', 0))}FLOPs")
        lines.append(f"bytes accessed:    "
                     f"{number_to_string(p.get('bytes_accessed', 0))}B")
        if "latency_s" in p:
            lines.append(f"latency:           {p['latency_s'] * 1e3:.2f} ms")
            lines.append(f"achieved:          "
                         f"{p['achieved_tflops']:.2f} TFLOPS, "
                         f"{p['achieved_gbps']:.1f} GB/s")
        lines.append("-" * 60)
        out = "\n".join(lines)
        logger.info("\n" + out)
        return out


def get_model_profile(model, args=None, kwargs=None, print_profile=True,
                      as_string: bool = False,
                      **_ignored) -> Tuple[Any, Any, Any]:
    """Reference public API (``get_model_profile``): returns
    (flops, macs, params) of one forward call.

    ``model`` is a callable (e.g. ``lambda x: module.apply(vars, x)``) or
    a flax ``nn.Module`` — modules additionally get the per-module tree
    breakdown (``profile_model_tree``), like the reference's printed
    profile. MACs are reported as flops/2 (HLO counts multiply-adds as 2).
    """
    args = args or ()
    kwargs = kwargs or {}
    import flax.linen as nn

    if isinstance(model, nn.Module):
        rows, total = profile_model_tree(
            model, *args, print_profile=print_profile, model_kwargs=kwargs)
        flops, macs, params = total["flops"], total["macs"], total["params"]
        if as_string:
            return (number_to_string(flops) + "FLOPs",
                    number_to_string(macs) + "MACs",
                    number_to_string(params))
        return flops, macs, params
    prof = FlopsProfiler(model)
    result = prof.profile_fn(*args, measure_time=False, **kwargs)
    if print_profile:
        prof.print_profile()
    flops = result["flops"]
    macs = flops / 2
    params = result["params"]
    if as_string:
        return (number_to_string(flops) + "FLOPs",
                number_to_string(macs) + "MACs",
                number_to_string(params or 0))
    return flops, macs, params


# ---------------------------------------------------------------------------
# per-module tree (reference profiler.py:235 print_model_profile / :788-830
# per-module MAC counting — here each submodule's cost comes from compiling
# it in isolation at the exact avals it saw inside the full forward)
# ---------------------------------------------------------------------------

def _is_array_leaf(x) -> bool:
    return hasattr(x, "dtype") and hasattr(x, "shape")


def _avalize(tree):
    """Array leaves -> ShapeDtypeStruct; everything else passes through."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        if _is_array_leaf(x) else x, tree)


def _split_static(tree):
    """Split a pytree into (avals_list, rebuild_fn). Non-array leaves
    (python bools like ``deterministic``, Nones) stay STATIC inside the
    rebuild closure — re-tracing them as device scalars would break the
    module's python control flow."""
    leaves, treedef = jax.tree.flatten(tree)
    is_arr = [_is_array_leaf(l) for l in leaves]
    avals = [jax.ShapeDtypeStruct(l.shape, l.dtype)
             for l, a in zip(leaves, is_arr) if a]
    statics = [l for l, a in zip(leaves, is_arr) if a is False]

    def rebuild(arrs):
        arrs_it, static_it = iter(arrs), iter(statics)
        rebuilt = [next(arrs_it) if a else next(static_it) for a in is_arr]
        return jax.tree.unflatten(treedef, rebuilt)

    return avals, rebuild


def _scan_multiplier(full_params, path, local_params) -> int:
    """Detect an nn.scan body: the stored param subtree carries a leading
    layer axis the per-iteration view lacks; the ratio is the multiplier."""
    sub = full_params.get("params", full_params)
    for key in path:
        if not isinstance(sub, dict) or key not in sub:
            return 1
        sub = sub[key]
    full_leaves = jax.tree.leaves(sub)
    local_leaves = jax.tree.leaves(local_params.get("params", local_params))
    if not full_leaves or len(full_leaves) != len(local_leaves):
        return 1
    f, l = full_leaves[0], local_leaves[0]
    fs, ls = tuple(np.shape(f)), tuple(np.shape(l))
    if len(fs) == len(ls) + 1 and fs[1:] == ls:
        return int(fs[0])
    return 1


def profile_model_tree(model, *args, variables=None, depth: int = 3,
                       top_n: int = 3, print_profile: bool = True,
                       measure: bool = False, model_kwargs: dict = None,
                       **kwargs):
    """Per-module cost breakdown of a flax model's forward pass.

    Walks the module tree by intercepting every submodule ``__call__``
    during ONE ``eval_shape`` trace (zero device work), then compiles each
    submodule standalone at the avals it actually received and reads the
    HLO cost analysis. Scan bodies are costed once and multiplied by the
    layer count (detected from the stored params' leading layer axis) —
    the reference's per-module tree (profiler.py:17, :788-830) without
    any monkey-patching, and with compiler-exact counts.

    Returns ``(rows, total)``: rows are dicts with path/name/flops/macs/
    params/multiplier/share; ``total`` is the WHOLE-program cost (which
    depth-1 rows plus the "unattributed" remainder sum to exactly).
    """
    import flax.linen as nn

    # model-call kwargs: pass via model_kwargs to avoid collisions with
    # this function's own options (a model whose __call__ takes `depth`
    # would otherwise silently lose it to the tree-depth cutoff)
    kwargs = {**(model_kwargs or {}), **kwargs}
    # split static leaves (python bools like a positional `deterministic`)
    # out of the top-level args — tracing them as device scalars would
    # break the model's python control flow, same as for submodules
    arg_avals, top_rebuild = _split_static(args)

    if variables is None:
        # eval_shape takes ShapeDtypeStructs directly — no concrete zeros
        variables = jax.eval_shape(
            lambda arrs: model.init(jax.random.PRNGKey(0),
                                    *top_rebuild(arrs), **kwargs),
            arg_avals)
    var_avals = _avalize(variables)

    def _apply(v, arrs):
        return model.apply(v, *top_rebuild(arrs), **kwargs)

    whole = cost_analysis(_apply, var_avals, arg_avals)
    whole["params"] = params_count(
        variables.get("params", variables))

    records = {}
    order = []
    active = []  # path stack: skip self-nested re-entry (super().__call__)

    def interceptor(next_fun, call_args, call_kwargs, context):
        mod = context.module
        path = tuple(mod.path)
        if (context.method_name != "__call__" or not path
                or len(path) > depth or path in active):
            return next_fun(*call_args, **call_kwargs)
        active.append(path)
        try:
            if path not in records:
                # record each path ONCE: flax transforms (nn.scan carry
                # discovery, remat) re-trace bodies, so trace-time call
                # counts do not reflect runtime execution counts — the
                # scan multiplier below carries the repetition instead
                try:
                    m, v = mod.unbind()
                    records[path] = {
                        "module": m, "vars": v, "args": call_args,
                        "kwargs": dict(call_kwargs),
                        "name": type(m).__name__,
                    }
                    order.append(path)
                except Exception:  # pragma: no cover - exotic modules
                    pass
            return next_fun(*call_args, **call_kwargs)
        finally:
            active.pop()

    with nn.intercept_methods(interceptor):
        # FRESH lambda on purpose: jax caches traces by function identity,
        # and a cache hit from the cost_analysis above would skip tracing
        # entirely — the interceptor would never fire
        jax.eval_shape(lambda v, a: _apply(v, a), var_avals, arg_avals)

    rows = []
    for path in order:
        r = records[path]
        m = r["module"]
        arg_list, rebuild = _split_static((r["args"], r["kwargs"]))
        v_avals = _avalize(r["vars"])

        def run(v, arrs, _m=m, _rebuild=rebuild):
            a, kw = _rebuild(arrs)
            return _m.apply(v, *a, **kw)

        try:
            cost = cost_analysis(run, v_avals, arg_list)
        except Exception:       # a fragment that cannot compile standalone
            cost = {"flops": 0.0, "bytes_accessed": 0.0,
                    "optimal_seconds": 0.0}
        mult = _scan_multiplier(variables, path, r["vars"])
        p_local = params_count(r["vars"].get("params", {}))
        rows.append({
            "path": path, "name": r["name"], "depth": len(path),
            "multiplier": mult,
            "std_flops": cost["flops"],
            "std_bytes": cost["bytes_accessed"],
            "flops": cost["flops"] * mult,
            "bytes_accessed": cost["bytes_accessed"] * mult,
            "params": p_local * mult,
        })

    # XLA's cost analysis counts a scan/while BODY once, not x trip count:
    # both the whole-program number and every ancestor of a scan body
    # undercount by (mult - 1) x body cost. Detect scan-body roots (the
    # shallowest path where the multiplier appears) and fold the missing
    # repetitions into their ancestors and the program total, so depth-1
    # rows + unattributed still sum to the total EXACTLY.
    mult_of = {}

    def parent_mult(path):
        for i in range(len(path) - 1, 0, -1):
            if path[:i] in mult_of:
                return mult_of[path[:i]]
        return 1

    for r in rows:    # pre-order: parents precede children
        if r["multiplier"] == 1:
            # paramless modules (Dropout, activations) carry no layer axis
            # to detect the scan from — they repeat with their parent
            pm = parent_mult(r["path"])
            if pm > 1:
                r["multiplier"] = pm
                r["flops"] *= pm
                r["bytes_accessed"] *= pm
        mult_of[r["path"]] = r["multiplier"]

    total_flops = whole["flops"]
    total_bytes = whole["bytes_accessed"]
    for r in rows:
        pm = parent_mult(r["path"])
        if r["multiplier"] > pm:    # scan-body root
            extra = r["std_flops"] * (r["multiplier"] - pm)
            extra_bytes = r["std_bytes"] * (r["multiplier"] - pm)
            total_flops += extra
            total_bytes += extra_bytes
            for a in rows:
                if (len(a["path"]) < len(r["path"])
                        and r["path"][:len(a["path"])] == a["path"]):
                    a["flops"] += extra
                    a["bytes_accessed"] += extra_bytes
    for r in rows:
        r["macs"] = r["flops"] / 2
        r["share"] = r["flops"] / total_flops if total_flops else 0.0
        del r["std_flops"], r["std_bytes"]

    top_level = [r for r in rows if r["depth"] == 1]
    attributed = sum(r["flops"] for r in top_level)
    unattributed = total_flops - attributed
    total = dict(whole, flops=total_flops, macs=total_flops / 2,
                 bytes_accessed=total_bytes,
                 scan_body_once_flops=whole["flops"],
                 unattributed_flops=unattributed)

    if measure:
        # whole-program wall clock, attributed to modules by flops share
        # (XLA fuses across module boundaries, so per-module timers do not
        # exist post-compilation; the reference's hook latencies have the
        # mirror-image caveat — they measure eager, unfused execution)
        concrete_arrs = [l for l in jax.tree.leaves(args)
                         if _is_array_leaf(l)]
        all_concrete = not any(
            isinstance(l, jax.ShapeDtypeStruct)
            for l in jax.tree.leaves((variables, concrete_arrs)))
        if all_concrete:
            latency = measure_latency(
                jax.jit(_apply), variables, concrete_arrs)
            total["latency_s"] = latency
            for r in rows:
                r["est_latency_s"] = latency * r["share"]

    if print_profile:
        lines = ["-" * 72,
                 "deepspeed_tpu flops profiler: per-module tree "
                 "(HLO cost analysis)",
                 f"{type(model).__name__}: "
                 f"params {number_to_string(total['params'])}| "
                 f"MACs {number_to_string(total['macs'])}| "
                 f"flops {number_to_string(total['flops'])}"]
        if "latency_s" in total:
            lines.append(f"measured latency: {total['latency_s']*1e3:.2f} ms"
                         f" (per-module estimates = flops share x this)")
        for r in rows:
            pad = "  " * r["depth"]
            x = (f" x{r['multiplier']}" if r["multiplier"] > 1 else "")
            lat = (f"| ~{r['est_latency_s']*1e3:.2f} ms"
                   if "est_latency_s" in r else "")
            lines.append(
                f"{pad}{'/'.join(r['path'])}{x}: "
                f"params {number_to_string(r['params'])}| "
                f"MACs {number_to_string(r['macs'])}| "
                f"{r['share'] * 100:.1f}% of total flops{lat}")
        lines.append(
            f"  (unattributed: ops outside submodules, fusion deltas = "
            f"{number_to_string(unattributed)}FLOPs)")
        for d in sorted({r["depth"] for r in rows}):
            at_d = sorted((r for r in rows if r["depth"] == d),
                          key=lambda r: -r["flops"])[:top_n]
            lines.append(
                f"top {len(at_d)} at depth {d} by flops: "
                + ", ".join(f"{'/'.join(r['path'])} "
                            f"({number_to_string(r['flops'])})"
                            for r in at_d))
        lines.append("-" * 72)
        out = "\n".join(lines)
        logger.info("\n" + out)

    return rows, total
