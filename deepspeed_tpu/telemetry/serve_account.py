"""The serve loop's own account of a profiler trace.

A trace of ``ContinuousBatchingScheduler.run`` holds the loop's host spans
(``ds:serve.*``, telemetry/spans.py) and the device's program runs (the
``XLA Modules`` events) on one clock. :func:`account` joins the two and
gives three tables of plain dict rows, for a benchmark's reader and for an
operator who captured a trace alike (docs/observability.md "Reading a
serve trace"):

* ``steps``: one row a decode step: what the device did between the end of
  the decode run before it and its own start (``stall_ms``: the prefills,
  the other programs, idle), and how many admissions its iteration
  dispatched before it: what the running lanes waited for, by cause;
* ``admissions``: one row an admission: the attributes of its span, the
  host's time in its child spans, the device time of ITS OWN prefill
  runs, and the request's time to its first token (``ttft_ms``: its wait
  in the queue and on to the end of its first token's ``ds:serve.emit``);
* ``gaps``: every stretch longer than ``GAP_FLOOR_NS`` in which the device
  ran nothing, with the program before and after it and the span the host
  was in at its start and at its end;

and ``iterations``: one row a loop iteration with the host's own time in
it (the span less the children that only wait for the device).

**The join.** A span says when the host dispatched; the device runs the
program later, and with the host running ahead the next admission's span,
or the step's, is open by then: enclosure in time gives a run to the wrong
span. The runtime's own ids do not: every ``XLA Modules`` event carries a
``run_id``; on the host plane a ``DoEnqueueProgram`` event carries the same
``run_id`` and lies, in time and on its thread, inside a
``tpu::System::Execute=>IssueSequencedEvent`` event whose consumer id
``_c`` is the producer id ``_p`` of one ``tpu::System::Execute`` event,
which runs on the dispatching thread inside the Python call and so inside
the ``ds:`` span that dispatched the program. (The enqueue's own time is
no guide: it happens on a queue thread after the call has returned.) A
run the chain does not place (one dispatched before the trace began, a
trace cut mid-chain) is in no row and is counted (``joins``: ``run_id``
against ``none``). Every traced window of every serve cell on the v5e
joined whole (PERF.md, PR 54); a trace of a runtime that writes no such
ids would want a join by order, tuned on that trace.

stdlib only at import; a ``ProfileData`` comes from ``scopes.load_trace``.
Times are the trace's own nanoseconds; ``*_ms`` fields are milliseconds.
One chip: the lowest-numbered ``/device:TPU:<n>`` plane is read (the
scheduler shards neither lanes nor caches).
"""
import bisect
import math
import re
import sys
from dataclasses import dataclass, field

from deepspeed_tpu.telemetry import scopes
from deepspeed_tpu.telemetry.spans import (
    SERVE_ADMIT,
    SERVE_DECODE_READ,
    SERVE_DECODE_STEP,
    SERVE_EMIT,
    SERVE_FIRST_TOKEN_READ,
    SERVE_ITERATION,
    SERVE_PREFILL,
    SERVE_SPLICE,
    SPAN_PREFIX,
)

# the serving programs as the trace names their runs (inference/engine.py
# owns the names; tests/unit/test_serve_account.py holds the two together).
# ``jit_prefill_more``, a later chunk's program, is a prefill run too
PROGRAM_PREFILL = "jit_prefill"
PROGRAM_DECODE_K = "jit_decode_k"
# a shorter stretch of idle time is the device's own pause between two
# programs of one queue; they are summed, not listed
GAP_FLOOR_NS = 20_000

JOIN_CHAIN = "run_id"
JOIN_NONE = "none"

_EXECUTE = "tpu::System::Execute"
_ISSUE = "tpu::System::Execute=>IssueSequencedEvent"
_ENQUEUE = "DoEnqueueProgram"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


class Span:
    """A ``ds:`` span of the loop's thread, with the span that encloses
    it."""
    __slots__ = ("name", "start", "end", "attrs", "parent")

    def __init__(self, name, start, end, attrs):
        self.name, self.start, self.end = name, start, end
        self.attrs, self.parent = attrs, None

    def up(self, name):
        """This span or the nearest one around it called ``name``."""
        s = self
        while s is not None and s.name != name:
            s = s.parent
        return s

    @property
    def ms(self):
        return (self.end - self.start) / 1e6


@dataclass
class Run:
    """One run of a program on the device."""
    name: str
    start: int
    end: int
    run_id: object = None

    @property
    def ms(self):
        return (self.end - self.start) / 1e6


@dataclass
class Account:
    window: tuple                   # (start_ns, end_ns)
    steps: list = field(default_factory=list)
    admissions: list = field(default_factory=list)
    gaps: list = field(default_factory=list)
    iterations: list = field(default_factory=list)
    # one row: the window's length, all of its idle time, its longest gap
    # of any length, the gaps under the floor (how many, their sum), and
    # the idle time beyond the trace's first and last device event
    totals: list = field(default_factory=list)
    # {program: {"run_id": n, "none": n}} over the prefill and decode runs
    # inside the window
    joins: dict = field(default_factory=dict)


def percentile(values, q):
    """The q-th percentile by linear interpolation between order
    statistics; None of an empty list."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


# ---------------------------------------------------------------------------
# reading the two planes
# ---------------------------------------------------------------------------
def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _device(profile):
    """``(runs sorted by start, busy intervals)`` of the first chip, or
    None without a device plane. Busy is the union of the executed
    instructions, containers left out, as the benchmark's idle shares
    have it: a loop's internal pauses are idle."""
    planes = sorted((int(m.group(1)), p) for p in profile.planes
                    for m in [_DEVICE_PLANE.match(p.name)] if m)
    if not planes:
        return None
    runs, ops, parsed = [], [], {}
    for line in planes[0][1].lines:
        if line.name == "XLA Modules":
            for e in line.events:
                runs.append(Run(e.name.split("(")[0], e.start_ns,
                                e.start_ns + e.duration_ns,
                                dict(e.stats).get("run_id")))
        elif line.name == "XLA Ops":
            for e in line.events:
                text = e.name
                if text not in parsed:
                    parsed[text] = scopes.event_instruction(text)[1]
                if parsed[text] not in scopes.CONTAINERS:
                    ops.append((e.start_ns, e.start_ns + e.duration_ns))
    runs.sort(key=lambda r: r.start)
    return runs, _union(ops)


@dataclass
class _Host:
    spans: list         # Span of the loop's thread, sorted by start
    dispatched: dict    # run_id -> start of its tpu::System::Execute

    def innermost(self, t):
        """The innermost span open at ``t``, or None."""
        i = bisect.bisect_right(self._starts, t) - 1
        s = self.spans[i] if i >= 0 else None
        while s is not None and s.end <= t:
            s = s.parent
        return s

    def __post_init__(self):
        self._starts = [s.start for s in self.spans]


def _host(profile):
    by_line = {}
    executes, issues, enqueues = {}, {}, []
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for n, line in enumerate(plane.lines):
            for e in line.events:
                name = e.name
                a, b = e.start_ns, e.start_ns + e.duration_ns
                if name.startswith(SPAN_PREFIX):
                    by_line.setdefault(n, []).append(Span(
                        name[len(SPAN_PREFIX):], a, b, dict(e.stats)))
                elif name == _EXECUTE:
                    p = dict(e.stats).get("_p")
                    if p is not None:
                        executes[p] = a
                elif name == _ISSUE:
                    c = dict(e.stats).get("_c")
                    if c is not None:
                        issues.setdefault(n, []).append((a, b, c))
                elif name == _ENQUEUE:
                    rid = dict(e.stats).get("run_id")
                    if rid is not None:
                        enqueues.append((n, a, b, rid))
    # the loop's thread: the line with the most iterations (one scheduler
    # a process is what the account reads)
    loop = max(by_line, default=None, key=lambda n: sum(
        s.name == SERVE_ITERATION for s in by_line[n]))
    spans = sorted(by_line.get(loop, ()), key=lambda s: (s.start, -s.end))
    stack = []
    for s in spans:
        while stack and stack[-1].end <= s.start:
            stack.pop()
        s.parent = stack[-1] if stack else None
        stack.append(s)
    dispatched = {}
    for n in issues:
        issues[n].sort()
    for n, a, b, rid in enqueues:
        around = issues.get(n, ())
        i = bisect.bisect_right(around, (a, math.inf, 0)) - 1
        if i >= 0 and around[i][1] >= b and around[i][2] in executes:
            dispatched[rid] = executes[around[i][2]]
    return _Host(spans=spans, dispatched=dispatched)


# ---------------------------------------------------------------------------
# the join
# ---------------------------------------------------------------------------
def _join(runs, host, prefix, span_name):
    """``{index in runs: span}`` for the runs whose program starts with
    ``prefix``: the span called ``span_name`` that dispatched each."""
    out = {}
    for i, r in enumerate(runs):
        t = host.dispatched.get(r.run_id) \
            if r.name.startswith(prefix) else None
        s = host.innermost(t) if t is not None else None
        s = s.up(span_name) if s is not None else None
        if s is not None:
            out[i] = s
    return out


# ---------------------------------------------------------------------------
# the tables
# ---------------------------------------------------------------------------
def _inside(x, lo, hi):
    return x.start >= lo and x.end <= hi


def account(profile, window=None):
    """The :class:`Account` of a loaded trace over ``window`` = (start_ns,
    end_ns), by default from the first device event to the last; None for
    a trace without a device plane."""
    device = _device(profile)
    if device is None:
        return None
    runs, busy = device
    host = _host(profile)
    if window is None:
        window = (min((r.start for r in runs), default=0),
                  max((r.end for r in runs), default=0))
    acc = Account(window=window)
    prefills = _join(runs, host, PROGRAM_PREFILL, SERVE_PREFILL)
    decodes = _join(runs, host, PROGRAM_DECODE_K, SERVE_DECODE_STEP)
    for prefix, joined in ((PROGRAM_PREFILL, prefills),
                           (PROGRAM_DECODE_K, decodes)):
        count = acc.joins.setdefault(prefix, {JOIN_CHAIN: 0, JOIN_NONE: 0})
        for i, r in enumerate(runs):
            if r.name.startswith(prefix) and _inside(r, *window):
                count[JOIN_CHAIN if i in joined else JOIN_NONE] += 1
    admits_of = {}      # id(iteration) -> its admissions, by start
    for s in host.spans:
        if s.name == SERVE_ADMIT and s.up(SERVE_ITERATION) is not None:
            admits_of.setdefault(id(s.up(SERVE_ITERATION)), []).append(s)
    acc.steps = _steps(runs, decodes, admits_of, window)
    acc.admissions = _admissions(runs, host, prefills, window)
    acc.totals, acc.gaps = _gaps(runs, busy, host, window)
    acc.iterations = _iterations(host, admits_of, window)
    return acc


def _steps(runs, decodes, admits_of, window):
    """One row a decode step span; a span takes the first run joined to
    it. The stall is measured from the decode run before, in the window
    or not."""
    starts = [r.start for r in runs]
    rows, before, seen = [], None, set()
    for i, r in enumerate(runs):
        if not r.name.startswith(PROGRAM_DECODE_K):
            continue
        prev, before = before, r
        if i not in decodes or not _inside(r, *window) \
                or id(decodes[i]) in seen:
            continue
        span = decodes[i]
        seen.add(id(span))
        iteration = span.up(SERVE_ITERATION)
        admits = [a for a in admits_of.get(id(iteration), ())
                  if a.start < span.start]
        row = {"run_id": r.run_id, "start_ns": r.start,
               "end_ns": r.end, "device_ms": r.ms,
               "dispatch_ns": span.start,
               "step": iteration.attrs.get("decode_steps")
               if iteration is not None else None,
               "lanes_active": span.attrs.get("lanes_active"),
               "ahead": span.attrs.get("ahead"),
               "admissions": len(admits),
               "buckets": [a.attrs.get("bucket") for a in admits],
               # None for the trace's first decode run: none before it
               "stall_ms": None, "stall_prefill_ms": None,
               "stall_other_ms": None, "stall_idle_ms": None}
        if prev is not None:
            between = [x for x in
                       runs[bisect.bisect_left(starts, prev.end):i]
                       if x.end <= r.start]
            ran = _union((x.start, x.end) for x in between)
            row.update(
                stall_ms=(r.start - prev.end) / 1e6,
                stall_prefill_ms=sum(x.ms for x in between if
                                     x.name.startswith(PROGRAM_PREFILL)),
                stall_other_ms=sum(x.ms for x in between if not
                                   x.name.startswith(PROGRAM_PREFILL)),
                stall_idle_ms=(r.start - prev.end
                               - sum(b - a for a, b in ran)) / 1e6)
        rows.append(row)
    return rows


def _first_tokens(host):
    """``{id(admission span): (read, emit)}``: the
    ``ds:serve.first_token_read`` span of its request and the
    ``ds:serve.emit`` span of its first token, either None where the trace
    has none. Both name their request; a read from before it did lies
    inside its admission alone, and a trace with a span a token has the
    request's first."""
    by_request = {s.attrs.get("request_id"): s for s in host.spans
                  if s.name == SERVE_ADMIT}
    reads, emits = {}, {}
    for s in host.spans:
        if s.name == SERVE_FIRST_TOKEN_READ:
            admit = by_request.get(s.attrs["request_id"]) \
                if "request_id" in s.attrs else s.up(SERVE_ADMIT)
            if admit is not None:
                reads[id(admit)] = s
        elif s.name == SERVE_EMIT:
            admit = by_request.get(s.attrs.get("request_id"))
            if admit is not None and s.start >= admit.start:
                emits.setdefault(id(admit), s)
    return {k: (reads.get(k), emits.get(k)) for k in {*reads, *emits}}


def _admissions(runs, host, prefills, window):
    """One row an admission span inside the window. ``ttft_ms`` is the
    request's time to its first token on the trace's clock: what it
    waited in the queue (the span's ``queue_wait_us``) and from the
    admission's start to the end of its first token's ``ds:serve.emit``."""
    own = {}            # id(admit) -> its prefill runs
    for i, span in prefills.items():
        admit = span.up(SERVE_ADMIT)
        if admit is not None:
            own.setdefault(id(admit), []).append(runs[i])
    first_tokens = _first_tokens(host)
    kids = {}           # id(admit) -> {child name: ms}
    for s in host.spans:
        if s.name in (SERVE_PREFILL, SERVE_SPLICE) \
                and s.up(SERVE_ADMIT) is not None:
            into = kids.setdefault(id(s.up(SERVE_ADMIT)), {})
            into[s.name] = into.get(s.name, 0.0) + s.ms
    rows = []
    for s in host.spans:
        if s.name != SERVE_ADMIT or not _inside(s, *window):
            continue
        mine = own.get(id(s), [])
        read, emit = first_tokens.get(id(s), (None, None))
        rows.append({
            **{k: s.attrs.get(k) for k in (
                "request_id", "lane", "bucket", "prompt_len",
                "queue_wait_us")},
            "start_ns": s.start, "end_ns": s.end,
            "dispatch_ms": kids.get(id(s), {}).get(SERVE_PREFILL),
            "splice_ms": kids.get(id(s), {}).get(SERVE_SPLICE),
            "first_token_read_ms": read.ms if read is not None else None,
            "ttft_ms": (emit.end - s.start) / 1e6
            + (s.attrs.get("queue_wait_us") or 0) / 1e3
            if emit is not None else None,
            "prefill_runs": len(mine),
            "prefill_device_ms": sum(r.ms for r in mine) if mine else None,
            "run_ids": [r.run_id for r in mine]})
    return rows


def _gaps(runs, busy, host, window):
    """``(totals, gaps)``: the window's one row of sums, and one row a
    stretch over the floor in which the device ran nothing. A stretch of
    the window that lies before the trace's first device event or after
    its last is idle time like any other (the benchmark's idle shares
    count it) but no gap: the trace does not say what the device did
    beyond its own ends (the profiler's device side starts a few hundred
    microseconds after the window's span opens)."""
    lo, hi = window
    idle, at = [], lo
    for a, b in busy:
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if a > at:
            idle.append((at, a))
        at = max(at, b)
    if hi > at:
        idle.append((at, hi))
    first = busy[0][0] if busy else hi
    last = busy[-1][1] if busy else lo
    within = [(a, b) for a, b in idle if b > first and a < last]
    short = [b - a for a, b in within if b - a <= GAP_FLOOR_NS]
    totals = [{
        "window_ms": (hi - lo) / 1e6,
        "idle_ms": sum(b - a for a, b in idle) / 1e6,
        "idle_gap_max_ms": max((b - a for a, b in within), default=0) / 1e6,
        "short_gaps": len(short), "short_gaps_ms": sum(short) / 1e6,
        "edge_ms": (sum(b - a for a, b in idle)
                    - sum(b - a for a, b in within)) / 1e6}]
    starts = [r.start for r in runs]
    rows = []
    for a, b in within:
        if b - a <= GAP_FLOOR_NS:
            continue
        i = bisect.bisect_right(starts, a) - 1
        j = bisect.bisect_left(starts, b)
        inside = i >= 0 and runs[i].end >= b
        at_a, at_b = host.innermost(a), host.innermost(b)
        rows.append({
            "start_ns": a, "ms": (b - a) / 1e6,
            "within": runs[i].name if inside else None,
            "after": runs[i].name if i >= 0 and not inside else None,
            "before": runs[j].name if j < len(runs) and not inside
            else None,
            "host_at_start": at_a.name if at_a is not None else None,
            "host_at_end": at_b.name if at_b is not None else None})
    return totals, rows


def _iterations(host, admits_of, window):
    """One row an iteration span inside the window: the host's own time
    is the span less the children that only wait for the device."""
    waits = {}
    for s in host.spans:
        if s.name in (SERVE_DECODE_READ, SERVE_FIRST_TOKEN_READ) \
                and s.up(SERVE_ITERATION) is not None:
            waits.setdefault(id(s.up(SERVE_ITERATION)), []).append(s)
    rows = []
    for s in host.spans:
        if s.name != SERVE_ITERATION or not _inside(s, *window):
            continue
        waited = sum(b - a for a, b in _union(
            (max(w.start, s.start), min(w.end, s.end))
            for w in waits.get(id(s), ()))) / 1e6
        rows.append({
            "start_ns": s.start, "step": s.attrs.get("decode_steps"),
            "admissions": len(admits_of.get(id(s), ())),
            "ms": s.ms, "wait_ms": waited, "host_ms": s.ms - waited})
    return rows


def select(rows, field, where=None):
    """The values of ``field`` over the rows that have one and that
    ``where`` keeps: ``{field: [least, most]}``, either bound None for
    open."""
    out = []
    for r in rows:
        if r.get(field) is None:
            continue
        for k, (least, most) in (where or {}).items():
            v = r.get(k)
            if v is None or (least is not None and v < least) \
                    or (most is not None and v > most):
                break
        else:
            out.append(r[field])
    return out


# ---------------------------------------------------------------------------
# what an operator reads
# ---------------------------------------------------------------------------
def _short(name):
    return name.replace("serve.", "") if name else "-"


def gaps_by_cause(acc):
    """``[(count, total_ms, after, before, host_at_start, host_at_end)]``,
    the longest total first; a gap inside one program's run is ``in
    <program>``."""
    sums = {}
    for g in acc.gaps:
        key = ("in " + g["within"] if g["within"] else g["after"] or "-",
               g["before"] or "-", _short(g["host_at_start"]),
               _short(g["host_at_end"]))
        n, ms = sums.get(key, (0, 0.0))
        sums[key] = (n + 1, ms + g["ms"])
    return sorted(((n, ms) + k for k, (n, ms) in sums.items()),
                  key=lambda row: -row[1])


def stall_by_buckets(acc):
    """``[(label, steps, median stall_ms, median prefill, other, idle
    part)]`` by what a step's iteration dispatched before it: nothing, one
    admission (by its prompt bucket), or several (by how many)."""
    groups = {}
    for s in acc.steps:
        if s["stall_ms"] is None:
            continue
        n = len(s["buckets"])
        key = (n, s["buckets"][0] or 0) if n == 1 else (n, 0)
        groups.setdefault(key, []).append(s)
    return [("(none)" if n == 0 else str(bucket) if n == 1
             else "%d admissions" % n, len(rows)) + tuple(
        percentile([r[f] for r in rows], 50) for f in (
            "stall_ms", "stall_prefill_ms", "stall_other_ms",
            "stall_idle_ms"))
        for (n, bucket), rows in sorted(groups.items())]


def report(acc, out=None):
    """The account as text: the joins, then the three tables, each in the
    order of what it costs."""
    lo, hi = acc.window
    (totals,) = acc.totals
    span_ms = totals["window_ms"] or 1.0

    def p(text=""):
        print(text, file=out or sys.stdout)

    def pct(values, q):
        v = percentile(values, q)
        return "-" if v is None else "%.3f" % v

    p("window %.3f ms, device idle %.3f ms (%.2f%%), %d gaps under %d us "
      "sum %.3f ms" % (span_ms, totals["idle_ms"],
                       100 * totals["idle_ms"] / span_ms,
                       totals["short_gaps"], GAP_FLOOR_NS // 1000,
                       totals["short_gaps_ms"]))
    for program, n in acc.joins.items():
        p("runs of %s* in the window: %d joined by run_id, %d not joined"
          % (program, n[JOIN_CHAIN], n[JOIN_NONE]))
    p()
    p("steps: %d; admissions a step %.3f" % (
        len(acc.steps),
        sum(s["admissions"] for s in acc.steps) / max(1, len(acc.steps))))
    stalls = select(acc.steps, "stall_ms")
    admitting = select(acc.steps, "stall_ms", {"admissions": [1, None]})
    p("  decode run ms p50 %s; stall ms p50 %s p95 %s max %s; of the %d "
      "steps behind an admission p50 %s" % (
          pct([s["device_ms"] for s in acc.steps], 50), pct(stalls, 50),
          pct(stalls, 95), pct(stalls, 100), len(admitting),
          pct(admitting, 50)))
    p("  %-22s %6s %10s %10s %10s %10s" % (
        "admitted before a step", "steps", "stall p50", "prefill", "other",
        "idle"))
    for label, n, *parts in stall_by_buckets(acc):
        p("  %-22s %6d %10.3f %10.3f %10.3f %10.3f" % (label, n, *parts))
    p()
    p("admissions: %d" % len(acc.admissions))
    p("  %-8s %6s %15s %12s %10s %12s %9s %9s" % (
        "bucket", "n", "own prefill p50", "dispatch p50", "splice p50",
        "ft read p50", "ttft p50", "ttft p95"))
    by_bucket = {}
    for a in acc.admissions:
        by_bucket.setdefault(a["bucket"], []).append(a)
    for bucket, rows in sorted(by_bucket.items(),
                               key=lambda kv: kv[0] or 0):
        p("  %-8s %6d %15s %12s %10s %12s %9s %9s" % (
            bucket, len(rows), pct(select(rows, "prefill_device_ms"), 50),
            pct(select(rows, "dispatch_ms"), 50),
            pct(select(rows, "splice_ms"), 50),
            pct(select(rows, "first_token_read_ms"), 50),
            pct(select(rows, "ttft_ms"), 50),
            pct(select(rows, "ttft_ms"), 95)))
    p()
    longest = max(acc.gaps, key=lambda g: g["ms"], default=None)
    p("gaps over %d us: %d, sum %.3f ms, longest %s" % (
        GAP_FLOOR_NS // 1000, len(acc.gaps),
        sum(g["ms"] for g in acc.gaps),
        "under the floor" if longest is None
        else "%.3f ms at %.3f ms into the window"
        % (longest["ms"], (longest["start_ns"] - lo) / 1e6)))
    p("  %8s %6s %10s  %-26s %-26s %s" % (
        "window%", "n", "mean us", "after", "before", "host"))
    for n, ms, after, before, h0, h1 in gaps_by_cause(acc)[:24]:
        p("  %8.3f %6d %10.1f  %-26s %-26s %s -> %s" % (
            100 * ms / span_ms, n, 1e3 * ms / n, after, before, h0, h1))
    p()
    free = select(acc.iterations, "host_ms", {"admissions": [0, 0]})
    p("iterations: %d, %d without an admission: host's own ms p50 %s "
      "p95 %s (the span less its reads)" % (
          len(acc.iterations), len(free), pct(free, 50), pct(free, 95)))


def main(argv):
    if len(argv) != 1:
        print("usage: python -m deepspeed_tpu.telemetry.serve_account "
              "<trace.xplane.pb[.gz]>", file=sys.stderr)
        return 2
    acc = account(scopes.load_trace(argv[0]))
    if acc is None:
        print("no /device:TPU plane in this trace", file=sys.stderr)
        return 1
    report(acc)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
