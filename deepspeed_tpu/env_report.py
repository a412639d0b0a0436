"""Environment report (reference ``deepspeed/env_report.py:140`` / bin/ds_report).

Prints versions, device inventory, and feature availability — the
compat-probe table the reference prints for op builders maps to "which
Pallas/native features are usable here".
"""

import importlib
import platform
import sys


GREEN_OK = "\033[92m[OKAY]\033[0m"
RED_NO = "\033[91m[NO]\033[0m"


def _try_version(mod: str) -> str:
    try:
        m = importlib.import_module(mod)
        return getattr(m, "__version__", "unknown")
    except ImportError:
        return ""


def feature_table():
    import jax

    rows = []
    backend = jax.default_backend()
    rows.append(("jax backend", backend, GREEN_OK))
    try:
        devs = jax.devices()
        rows.append(("devices", f"{len(devs)} x {devs[0].device_kind}",
                     GREEN_OK))
    except RuntimeError as e:
        rows.append(("devices", str(e), RED_NO))
    try:
        from jax.experimental import pallas  # noqa: F401
        rows.append(("pallas kernels",
                     "native" if backend == "tpu" else "interpret mode",
                     GREEN_OK))
    except ImportError:
        rows.append(("pallas kernels", "unavailable", RED_NO))
    from deepspeed_tpu.ops import native

    rows.append(("native host ops (C++)",
                 "built" if native.available() else "not built "
                 "(python -m deepspeed_tpu.ops.native to build)",
                 GREEN_OK if native.available() else RED_NO))

    # Memory accounting (docs/observability.md, "Memory accounting"):
    # live Mem/* watermarks need device.memory_stats(); HBM headroom %
    # needs a device_kind capacity-table entry. Report both per backend.
    from deepspeed_tpu.profiling.step_profiler import peak_tflops
    from deepspeed_tpu.telemetry.memory import (format_bytes, hbm_bytes,
                                                live_memory_stats)

    try:
        devs = jax.devices()
    except RuntimeError:
        devs = []
    if devs:
        n_live = sum(1 for d in devs if live_memory_stats(d) is not None)
        rows.append(("device memory_stats()",
                     f"{n_live}/{len(devs)} devices report live stats",
                     GREEN_OK if n_live else RED_NO))
        cap, cap_src = hbm_bytes(devs[0])
        rows.append(("HBM capacity table",
                     f"{format_bytes(cap)} ({cap_src})" if cap is not None
                     else cap_src,
                     GREEN_OK if cap is not None else RED_NO))
        try:
            peak, peak_src = peak_tflops(devs[0])
            rows.append(("peak bf16 TFLOPS table", f"{peak:g} ({peak_src})",
                         GREEN_OK))
        except ValueError as e:
            rows.append(("peak bf16 TFLOPS table", str(e), RED_NO))
        if n_live == 0 and cap is None:
            rows.append(("memory accounting",
                         f"{backend} backend exposes neither memory_stats() "
                         "nor an HBM table entry: live Mem/* watermarks and "
                         "HBM headroom are OFF (compiled memory_analysis() "
                         "still works)", RED_NO))
    return rows


def main():
    import jax
    import deepspeed_tpu

    print("-" * 64)
    print("deepspeed_tpu environment report")
    print("-" * 64)
    print(f"python ............... {sys.version.split()[0]} "
          f"({platform.platform()})")
    print(f"deepspeed_tpu ........ {deepspeed_tpu.__version__}")
    for mod in ("jax", "jaxlib", "flax", "optax", "numpy"):
        v = _try_version(mod)
        print(f"{mod} {'.' * (21 - len(mod))} {v or 'NOT INSTALLED'}")
    print("-" * 64)
    for name, value, status in feature_table():
        print(f"{name:<24} {value:<28} {status}")
    print("-" * 64)


if __name__ == "__main__":
    main()
