"""A statistic of one column of the serve loop's own account of the traced
run (``deepspeed_tpu.telemetry.serve_account``: the program joins its spans
to the device's runs by the runtime's ``run_id`` and gives the tables
``steps``, ``admissions``, ``gaps``, ``iterations`` and the one-row
``totals``). ``where`` keeps the rows whose fields lie in ``{field: [least,
most]}`` (either bound null for open); the statistic is the percentile
``q``, or ``stat``: ``max`` or ``mean``. None where the checkout's program
has no such module (a commit from before it), where the trace has no device
plane (a rehearsal on the CPU), and where no row is left."""
import importlib

from perfbench import stats
from perfbench import trace_reduce as tr

_ATTR = "_serve_account"
TOP = 12    # rows of each of the account's summaries kept for the log


def program_module():
    """``deepspeed_tpu.telemetry.serve_account``, or None where the
    checkout's program has none."""
    try:
        return importlib.import_module(
            "deepspeed_tpu.telemetry.serve_account")
    except ImportError:
        return None


def of(ctx):
    """The account of a reader's context over the traced window, made once
    per run; its joins and its two summaries go to the run's log, beside
    the whole window's 95th percentile of the clients' gaps where the
    traffic kind keeps them (a traced run prints no end-to-end metric,
    and a decode step plus the stall's 95th percentile is the device's
    twin of that tail)."""
    module = program_module()
    if module is None or not ctx.red.devices:
        return None
    acc = getattr(ctx, _ATTR, None)
    if acc is None:
        acc = module.account(tr.load(tr.find_xplane(ctx.env.trace_dir)),
                             window=ctx.red.window)
        setattr(ctx, _ATTR, acc)
        if acc is not None:
            ctx.notes["serve_account"] = {
                "gap_ms_p95": stats.percentile(
                    ctx.series.get("gap_ms", ()), 95),
                "joins": acc.joins,
                "gaps_by_cause": module.gaps_by_cause(acc)[:TOP],
                "stall_by_buckets": module.stall_by_buckets(acc)[:TOP]}
    return acc


def read(ctx, table, field, where=None, q=None, stat=None):
    acc = of(ctx)
    if acc is None:
        return None
    values = program_module().select(getattr(acc, table), field, where)
    if not values:
        return None
    if q is not None:
        return stats.percentile(values, q)
    return max(values) if stat == "max" else sum(values) / len(values)
