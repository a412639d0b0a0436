"""perfbench: the benchmark of deepspeed_tpu (see perfbench/README.md).

One command runs one cell: ``python -m perfbench --workload <name> --seed
<n> --seconds <s> --trace <0|1>``. Cells, configurations, traffic mixes and
per-layer metrics are data files that the harness finds by name; nothing in
this package lists them.
"""
