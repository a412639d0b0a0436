"""From a profiler trace (``.xplane.pb``) to intervals the readers can use.

Read with ``jax.profiler.ProfileData`` only. What a v5e trace holds (seen
in the recorded traces under ``perfbench/testdata``):

* one plane ``/device:TPU:<n>`` per chip, with the lines ``XLA Modules``
  (one event per executed program, named ``jit_<fn>(<hash>)``), ``XLA Ops``
  (one event per executed HLO instruction, named by the instruction's whole
  text ``%name = <type> opcode(...)``) and, on some chips, ``Async XLA
  Ops`` (one event from each ``*-start`` to its ``*-done``);
* ``while`` / ``conditional`` / ``call`` events on ``XLA Ops`` span the
  events of their bodies, so they are containers and not work: they are left
  out of every sum and of the busy union (a loop's internal gaps are idle);
* one plane ``/host:CPU`` whose thread lines hold the
  ``jax.profiler.TraceAnnotation`` spans; the benchmark's own start with
  ``pb:``. Host and device events share one clock.

Everything is kept in nanoseconds as the trace gives them.
"""
import bisect
import glob
import gzip
import os
import re
from dataclasses import dataclass, field

SPAN_PREFIX = "pb:"
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
_NAME = re.compile(r"^%?(\S+) = ")
_OPCODE = re.compile(r"[\]})] ([a-z][\w\-]*)\(")


def parse_op(text):
    """``(name, opcode)`` of an ``XLA Ops`` event. The name is the HLO
    instruction's (``fusion.12``); the opcode is what follows the result
    type (``fusion``, ``custom-call``, ``all-gather`` ...). A text that is
    no HLO instruction is its own name with the opcode ``?``."""
    m = _NAME.match(text)
    if not m:
        return text.lstrip("%"), "?"
    op = _OPCODE.search(text, m.end() - 1)
    return m.group(1), (op.group(1) if op else "?")


def is_collective(opcode):
    return any(opcode == c or opcode in (c + "-start", c + "-done")
               for c in COLLECTIVES)


@dataclass
class Op:
    name: str
    opcode: str
    start: float
    end: float
    text: str = ""

    @property
    def dur(self):
        return self.end - self.start


@dataclass
class Device:
    ops: list = field(default_factory=list)        # XLA Ops, no containers
    async_ops: list = field(default_factory=list)  # Async XLA Ops
    modules: list = field(default_factory=list)    # XLA Modules


@dataclass
class Reduced:
    devices: dict = field(default_factory=dict)    # device id -> Device
    spans: list = field(default_factory=list)      # (name, start, end), pb:
    window: tuple = (0.0, 0.0)


# ---------------------------------------------------------------------------
# interval arithmetic on sorted, disjoint lists of (start, end)
# ---------------------------------------------------------------------------
def union(intervals):
    out = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals):
    return sum(b - a for a, b in intervals)


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def complement(intervals, lo, hi):
    """Gaps of a disjoint sorted list inside [lo, hi]."""
    out, at = [], lo
    for a, b in clip(intervals, lo, hi):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def intersect(xs, ys):
    """Intersection of two disjoint sorted lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(xs, ys):
    """The part of disjoint sorted ``xs`` outside disjoint sorted ``ys``."""
    if not xs:
        return []
    return intersect(xs, complement(ys, xs[0][0], xs[-1][1]))


# ---------------------------------------------------------------------------
# reading
# ---------------------------------------------------------------------------
def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path):
    """A ``ProfileData`` from an ``.xplane.pb`` or ``.xplane.pb.gz`` file."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def reduce_trace(profile, window_span=None):
    """``Reduced`` from a ``ProfileData``. The window is the benchmark span
    named ``window_span`` when the trace has it, otherwise from the first
    device event to the last."""
    red = Reduced()
    for plane in profile.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m:
            dev = red.devices.setdefault(int(m.group(1)), Device())
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev.modules = [
                        Op(e.name.split("(")[0], "module", e.start_ns,
                           e.start_ns + e.duration_ns) for e in line.events]
                elif line.name in ("XLA Ops", "Async XLA Ops"):
                    parsed = {}
                    into = dev.ops if line.name == "XLA Ops" \
                        else dev.async_ops
                    for e in line.events:
                        text = e.name
                        if text not in parsed:
                            parsed[text] = parse_op(text)
                        name, opcode = parsed[text]
                        if opcode in CONTAINERS:
                            continue
                        into.append(Op(name, opcode, e.start_ns,
                                       e.start_ns + e.duration_ns, text))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        red.spans.append((e.name[len(SPAN_PREFIX):],
                                          e.start_ns,
                                          e.start_ns + e.duration_ns))
    red.spans.sort(key=lambda s: (s[1], -s[2]))
    events = [o for d in red.devices.values() for o in d.ops + d.modules]
    lo = min((o.start for o in events), default=0.0)
    hi = max((o.end for o in events), default=0.0)
    if window_span:
        for name, a, b in red.spans:
            if name == window_span:
                lo, hi = a, b
                break
    red.window = (lo, hi)
    return red


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------
def busy(dev, window):
    """Disjoint intervals inside ``window`` in which an operation runs."""
    return clip(union((o.start, o.end) for o in dev.ops), *window)


def busy_seconds(red):
    """Seconds in which an operation ran, averaged over the chips."""
    if not red.devices:
        return 0.0
    per = [total(busy(d, red.window)) for d in red.devices.values()]
    return sum(per) / len(per) / 1e9


def window_seconds(red):
    return (red.window[1] - red.window[0]) / 1e9


def op_seconds(red, keep=None):
    """``{name: seconds}`` of the device operations inside the window,
    averaged over the chips; ``keep(op)`` filters."""
    sums = {}
    lo, hi = red.window
    for dev in red.devices.values():
        for o in dev.ops:
            if o.end <= lo or o.start >= hi or (keep and not keep(o)):
                continue
            sums[o.name] = sums.get(o.name, 0.0) \
                + (min(o.end, hi) - max(o.start, lo))
    n = max(1, len(red.devices))
    return {k: v / n / 1e9 for k, v in sums.items()}


def top_ops(red, n=10):
    sums = op_seconds(red)
    return [[k, v] for k, v in sorted(sums.items(), key=lambda kv: -kv[1])[:n]]


def host_timeline(spans):
    """Disjoint ``(start, end, name)`` segments labelled by the innermost
    benchmark span that covers them (spans of one thread nest)."""
    out, stack, cursor = [], [], 0.0

    def emit(a, b, name):
        if b <= a:
            return
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            top, end = stack.pop()
            emit(cursor, end, top)
            cursor = max(cursor, end)
        if stack:
            emit(cursor, s, stack[-1][0])
        cursor = s
        stack.append((name, e))
    while stack:
        top, end = stack.pop()
        emit(cursor, end, top)
        cursor = max(cursor, end)
    return out


def idle_by_span(red, n=10, window_span=None, host="unattributed"):
    """``[[name, seconds], ...]``: the idle time of the window (averaged
    over the chips) by what the host was doing, longest first. The span
    named ``window_span`` only marks the window; idle time under no other
    benchmark span goes to ``host``."""
    segs = host_timeline([s for s in red.spans if s[0] != window_span])
    starts = [s[0] for s in segs]
    sums = {}
    for dev in red.devices.values():
        for a, b in complement(busy(dev, red.window), *red.window):
            i = max(0, bisect.bisect_right(starts, a) - 1)
            covered = 0.0
            while i < len(segs) and segs[i][0] < b:
                lo, hi = max(a, segs[i][0]), min(b, segs[i][1])
                if hi > lo:
                    sums[segs[i][2]] = sums.get(segs[i][2], 0.0) + hi - lo
                    covered += hi - lo
                i += 1
            if b - a > covered:
                sums[host] = sums.get(host, 0.0) + (b - a) - covered
    k = max(1, len(red.devices))
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, v / k / 1e9] for name, v in ranked]


def collective_intervals(dev, window):
    """Disjoint intervals in which a collective is running or in flight."""
    both = [(o.start, o.end) for o in dev.ops + dev.async_ops
            if is_collective(o.opcode)]
    return clip(union(both), *window)


def compute_intervals(dev, window):
    """Disjoint intervals in which a non-collective operation runs."""
    return clip(union((o.start, o.end) for o in dev.ops
                      if not is_collective(o.opcode)), *window)


def collective_seconds(red):
    """``(in_flight_s, exposed_s)`` averaged over the chips: the time a
    collective runs or is in flight, and the part of it in which no other
    operation runs on that chip."""
    flight, exposed = [], []
    for dev in red.devices.values():
        c = collective_intervals(dev, red.window)
        flight.append(total(c))
        exposed.append(total(subtract(c, compute_intervals(dev, red.window))))
    n = max(1, len(red.devices))
    return sum(flight) / n / 1e9, sum(exposed) / n / 1e9


def module_durations_ms(red, prefix):
    """Device milliseconds of every run of the programs whose name starts
    with ``prefix`` inside the window, all chips."""
    lo, hi = red.window
    return [(m.end - m.start) / 1e6 for d in red.devices.values()
            for m in d.modules
            if m.name.startswith(prefix) and m.start >= lo and m.end <= hi]


def module_runs(red, prefix):
    """Runs of those programs on one chip (the first)."""
    if not red.devices:
        return 0
    dev = red.devices[min(red.devices)]
    lo, hi = red.window
    return sum(1 for m in dev.modules if m.name.startswith(prefix)
               and m.start >= lo and m.end <= hi)
