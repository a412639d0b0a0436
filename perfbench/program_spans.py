"""The program's own spans and scopes, read from the same ``.xplane.pb``.

``trace_reduce`` keeps the benchmark's ``pb:`` spans and the device's
events. The program writes its own host spans (``ds:<name>``, with
attributes, through ``deepspeed_tpu.telemetry.span``) into the same trace,
and hands out a scope table (``program_scopes()``) that says which source
scope each HLO instruction of its programs belongs to. This module loads
both, once per traced run, for the readers that need them; the interval
arithmetic is ``trace_reduce``'s and the join of instructions to scopes is
the program's own (``deepspeed_tpu.telemetry.scopes``), so that each has one
implementation.

A program that has no such spans or no ``program_scopes`` (a commit from
before they existed) gives ``None`` here, and every reader built on this
module then returns None: the metric is left out of the line.
"""
import bisect
import importlib
from dataclasses import dataclass, field

from perfbench import stats
from perfbench import trace_reduce as tr

SPAN_PREFIX = "ds:"
_ATTR = "_program_spans"


@dataclass
class Span:
    name: str           # without the prefix
    start: float        # ns, the trace's clock
    end: float
    attrs: dict = field(default_factory=dict)

    @property
    def dur_ms(self):
        return (self.end - self.start) / 1e6


@dataclass
class Program:
    """One traced run as the program describes it."""
    red: object                 # trace_reduce.Reduced (devices, window)
    spans: list                 # Span, sorted by start
    rows: list                  # scopes.time_by_scope rows, or None
    scopes: object = None       # deepspeed_tpu.telemetry.scopes, or None


def program_module():
    """``deepspeed_tpu.telemetry.scopes``, or None where the checkout's
    program has none."""
    try:
        return importlib.import_module("deepspeed_tpu.telemetry.scopes")
    except ImportError:
        return None


def program_constant(module, name):
    """A name the program exports (a step program's, a scope's), or None
    where this checkout's program does not export it."""
    try:
        return getattr(importlib.import_module(module), name, None)
    except ImportError:
        return None


def read_spans(profile):
    """Every ``ds:`` span of the host plane, with its attributes."""
    spans = []
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append(Span(e.name[len(SPAN_PREFIX):], e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      dict(e.stats)))
    spans.sort(key=lambda s: (s.start, -s.end))
    return spans


def build(profile, red, table):
    """A ``Program`` from a loaded trace, its reduction and a scope table
    (None: no scope metric can be read)."""
    scopes = program_module()
    rows = None
    if table is not None and scopes is not None:
        rows = scopes.time_by_scope(profile, table, window=red.window)
    return Program(red=red, spans=read_spans(profile), rows=rows,
                   scopes=scopes)


def of(ctx):
    """The ``Program`` of a reader's context, loaded once per run; None
    when the trace has no device plane. The scope table comes from the
    system under test: its scheduler's ``program_scopes()`` when it
    serves, its engine's otherwise."""
    if not ctx.red.devices:
        # a trace without a device plane (a rehearsal on the CPU): spans
        # timed on a machine nobody measures are left out like the rest
        return None
    prog = getattr(ctx, _ATTR, None)
    if prog is None:
        owner = getattr(ctx.system, "scheduler", None) or ctx.system.engine
        scopes_fn = getattr(owner, "program_scopes", None)
        profile = tr.load(tr.find_xplane(ctx.env.trace_dir))
        prog = build(profile, ctx.red, scopes_fn() if scopes_fn else None)
        setattr(ctx, _ATTR, prog)
    return prog


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
def named(prog, name):
    """The spans called ``name`` that lie inside the traced window."""
    lo, hi = prog.red.window
    return [s for s in prog.spans
            if s.name == name and s.start >= lo and s.end <= hi]


def idle_seconds(prog, inside=None, outside=None):
    """Seconds in which no device operation ran (averaged over the chips),
    over the whole window or only under the spans named ``inside``, less
    the part under the spans named ``outside``."""
    lo, hi = prog.red.window
    keep = [(lo, hi)] if inside is None else tr.clip(tr.union(
        (s.start, s.end) for s in prog.spans if s.name == inside), lo, hi)
    if outside is not None:
        keep = tr.subtract(keep, tr.clip(tr.union(
            (s.start, s.end) for s in prog.spans if s.name == outside),
            lo, hi))
    per = [tr.total(tr.intersect(
        tr.complement(tr.busy(dev, prog.red.window), lo, hi), keep))
        for dev in prog.red.devices.values()]
    return sum(per) / max(1, len(per)) / 1e9


def module_ms_by_span(prog, span_name, program_prefix):
    """For each span called ``span_name`` inside the window, the device
    milliseconds of the programs whose name starts with ``program_prefix``
    that ran inside it (first chip); spans without such a run are left
    out."""
    if not prog.red.devices:
        return []
    dev = prog.red.devices[min(prog.red.devices)]
    mods = sorted((m.start, m.end) for m in dev.modules
                  if m.name.startswith(program_prefix))
    starts = [m[0] for m in mods]
    out = []
    for s in named(prog, span_name):
        i = bisect.bisect_left(starts, s.start)
        ms = 0.0
        while i < len(mods) and mods[i][0] < s.end:
            if mods[i][1] <= s.end:
                ms += (mods[i][1] - mods[i][0]) / 1e6
            i += 1
        if ms:
            out.append(ms)
    return out


# ---------------------------------------------------------------------------
# scopes
# ---------------------------------------------------------------------------
def scope_share(prog, names, program=None):
    """Percent of the device time of the operations (of ``program`` when
    given, of everything otherwise) that lies under one of the scopes
    ``names``; ``(share, {name: share})`` or None without rows or time."""
    if prog.rows is None:
        return None
    sc = prog.scopes
    of = (lambda r: r["program"] == program) if program else None
    apart = {n: sc.share(prog.rows, lambda r, n=n: sc.has_scope(
        r["path"], n), of) for n in names}
    if any(v is None for v in apart.values()):
        return None
    whole = sc.share(prog.rows, lambda r: sc.has_scope(r["path"], *names),
                     of)
    return whole, apart


def unattributed_share(prog):
    """Percent of the device time whose instruction is not in the table
    or has no scope deeper than its program's root."""
    if prog.rows is None:
        return None
    return prog.scopes.share(prog.rows,
                             lambda r: not prog.scopes.attributed(r))


def top_rows(prog, n=10, keep=None):
    """The ``n`` costliest instructions as ``[program, instruction, scope,
    seconds]``, the scope being the path's components joined by ``/``."""
    if prog.rows is None:
        return []
    rows = sorted((r for r in prog.rows if keep is None or keep(r)),
                  key=lambda r: -r["seconds"])[:n]
    return [[r["program"], r["instruction"],
             "/".join(prog.scopes.components(r["path"])) or None,
             r["seconds"]] for r in rows]
