"""Readers: one per-layer metric each, from what a traced run recorded.

``perfbench/layer_metrics/<metric>.json`` names a reader module here and
its arguments. A reader has one function, ``read(ctx, **args)``, and looks
only at ``ctx`` (``perfbench.run.ReadCtx``): the reduced device trace
(``ctx.red``), the traffic kind's named series (``ctx.series``), the
builder's ``ctx.system.info``, the peaks (``ctx.env.peak``), the compile
counter and the allocator's peaks. A reader that finds nothing to read
returns None, and the harness leaves the metric out of the line.
"""
