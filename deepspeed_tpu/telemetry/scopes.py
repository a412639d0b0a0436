"""Which source scope each device operation belongs to.

A TPU profiler trace names a device operation by its HLO instruction
(``%fusion.304 = ...``) and carries no ``op_name``; the compiled program's
own HLO text does (``metadata={op_name="jit(train_step)/.../optimizer/mul"}``).
This module joins the two:

* :func:`scope_table` turns the ``as_text()`` of compiled executables into
  ``{program_name: {hlo_instruction_name: op_name_path}}`` (the engines'
  ``program_scopes()`` call it with the executables they have dispatched);
* :func:`time_by_scope` lays a trace's ``XLA Ops`` events against such a
  table;
* ``python -m deepspeed_tpu.telemetry.scopes <trace> <table.json>`` prints
  the time per scope, for an operator who captured a trace and dumped
  ``program_scopes()`` next to it.

A scope path keeps JAX's own components (``jvp(GPT)``, ``transpose(...)``,
``checkpoint`` / ``rematted_computation`` for rematerialised operations);
:func:`components` drops only what says nothing about the source: the
``jit(...)`` wrappers, loop and branch structure, and the primitive's name
at the end. stdlib only at import; jax is imported where a trace is read.
"""
import collections
import gzip
import json
import re
import sys

from deepspeed_tpu.telemetry.builds import build_log

# named scopes the program sets (jax.named_scope), by the name a reader
# looks for among a path's components
SCOPE_OPTIMIZER = "optimizer"
SCOPE_GRAD_CAST = "grad_cast"
SCOPE_OVERFLOW_CHECK = "overflow_check"
SCOPE_GRAD_NORM_CLIP = "grad_norm_clip"
SCOPE_LM_HEAD = "lm_head"
SCOPE_LM_HEAD_CE = "lm_head_ce"
SCOPE_MLM_HEAD = "mlm_head"
SCOPE_ATTN_CORE = "attn_core"
SCOPE_KV_CACHE_WRITE = "kv_cache_write"
SCOPE_KV_CACHE_READ = "kv_cache_read"
SCOPE_SAMPLE = "sample"
# the dropless MoE layer (moe/layer.py): the router's matmul, softmax and
# top-k; the sort of the (token, expert) pairs and the gather of their rows;
# the grouped matmuls with the activation between; the weighted sum back
SCOPE_MOE_ROUTER = "moe_router"
SCOPE_MOE_DISPATCH = "moe_dispatch"
SCOPE_MOE_EXPERTS = "moe_experts"
SCOPE_MOE_COMBINE = "moe_combine"
# the shared experts that every token passes through beside its routed ones
SCOPE_MOE_SHARED = "moe_shared"
# ZeRO-3 (runtime/zero/gather.py): a layer's weights cast and gathered where
# the layer reads them, and their cotangents reduce-scattered back
SCOPE_ZERO3_GATHER = "zero3_gather"
# the Mamba-2 mixer (models/mamba2.py): the input projection and its muP
# vector; the depthwise causal convolution, its activation and the tail it
# keeps; the recurrence (the state's read-modify-write and ``y``); the gate
# and the grouped norm; the output projection
SCOPE_SSM_IN_PROJ = "ssm_in_proj"
SCOPE_SSM_CONV = "ssm_conv"
SCOPE_SSM_SCAN = "ssm_scan"
SCOPE_SSM_GATE_NORM = "ssm_gate_norm"
SCOPE_SSM_OUT_PROJ = "ssm_out_proj"
# not a named scope: the tag of an instruction that no scope above owns and
# whose result is a whole KV-cache leaf, stacked or one layer's: a copy XLA
# makes of a loop's carry, a layer's slice of the stacked cache that did
# not fuse into its reader. The decode step's own row update also has a
# whole (stacked) leaf as its result, in place; it is ``kv_cache_write``'s
# and keeps that name. Since the layer loop carries the cache (PR 25) a
# serving program should have no time under this tag: what shows up here
# is a whole leaf being moved again.
SCOPE_KV_CACHE_CARRY = "kv_cache_carry"
# the same for a whole recurrent-state or convolution-tail leaf of the
# Mamba-2 mixer: the mixer's own update of its layer's slice is
# ``ssm_scan``'s / ``ssm_conv``'s, in place; anything else that produces a
# whole leaf is a copy
SCOPE_SSM_STATE_CARRY = "ssm_state_carry"
# the gated short convolution (models/short_conv.py): the input projection
# to ``[B | C | z]``; the gate ``B * z``, the depthwise causal convolution
# and the tail it keeps, the gate ``C`` on its output; the output projection
SCOPE_CONV_IN_PROJ = "conv_in_proj"
SCOPE_CONV_GATE_CONV = "conv_gate_conv"
SCOPE_CONV_OUT_PROJ = "conv_out_proj"
# a whole convolution-tail leaf of that mixer that no scope owns: the
# mixer's own update of its layer's slice is ``conv_gate_conv``'s, in place
SCOPE_CONV_STATE_CARRY = "conv_state_carry"
# the power-retention mixer (models/power_retention.py): the q, k, v and
# gate projections; the per-head norm of q and k and rotary; the gate, the
# symmetric square, the state's and normaliser's read-modify-write, the
# query and the division; the output projection
SCOPE_RET_PROJ = "ret_proj"
SCOPE_RET_QK_NORM_ROPE = "ret_qk_norm_rope"
SCOPE_RET_STATE = "ret_state"
SCOPE_RET_OUT_PROJ = "ret_out_proj"
# a whole retention-state or normaliser leaf that no scope owns: the
# mixer's own update of its layer's slice is ``ret_state``'s, in place
SCOPE_RET_STATE_CARRY = "ret_state_carry"
# latent attention (models/latent_attention.py): the queries' low-rank
# projections, norm and rotary; the latent's projection, norm, the rotary
# key and, where a pass decompresses them, the per-head keys and values;
# the absorbed form's products with the decompression matrices (the query
# into the latent space, the output out of it); scores, softmax and the
# weighted sum, per head or over the latent; the output projection. A whole
# latent leaf that no scope owns is ``kv_cache_carry``'s, as keys and
# values are
SCOPE_MLA_Q_PROJ = "mla_q_proj"
SCOPE_MLA_KV_PROJ = "mla_kv_proj"
SCOPE_MLA_ABSORB = "mla_absorb"
SCOPE_MLA_ATTN = "mla_attn"
SCOPE_MLA_OUT_PROJ = "mla_out_proj"
# attention over a chosen few of the cached positions (ops/
# indexed_attention.py; ``GPTConfig.indexer``): the indexer's three
# projections with their norm and rotary; its scores over a lane's index
# keys; the choice of the best positions; scores, softmax and weighted sum
# over the chosen rows (``attn_core`` stays the dense path's). The index
# key's row writes are ``kv_cache_write``'s and a whole index-key leaf that
# no scope owns is ``kv_cache_carry``'s, as keys and values are
SCOPE_DSA_INDEX_PROJ = "dsa_index_proj"
SCOPE_DSA_INDEX_SCORES = "dsa_index_scores"
SCOPE_DSA_SELECT = "dsa_select"
SCOPE_DSA_ATTN = "dsa_attn"
# attention layers that differ by kind (models/kind_attention.py;
# ``GPTConfig.attention_kind``): scores, softmax, weighted sum and the
# output's gate of the layers that see a window of positions, and of those
# that see every position. Their row writes are ``kv_cache_write``'s and a
# whole leaf that no scope owns is ``kv_cache_carry``'s. A call without a
# cache (a training step) times its flash kernels under them too: the
# ``window_flash_*`` calls under ``window_attn``, the ``flash_*`` calls of
# the layers that see every position under ``full_attn``
SCOPE_WINDOW_ATTN = "window_attn"
SCOPE_FULL_ATTN = "full_attn"
# the same stack with LATENT attention as each kind's mixer (models/
# latent_attention.py ``KindLatentAttention``; ``GPTConfig.latent_kinds``):
# the absorbed form's scores, softmax, weighted sum and the heads' gate
# over a window layer's ring of latents, and over the rows a full layer's
# indexer chose; that indexer's projections (its query from the query
# latent) and its scores over a lane's index keys; the choice of the best
# positions. The projections around them keep latent attention's scopes
# (``mla_q_proj`` ... ``mla_out_proj``); the ``dsa_*`` scopes stay the
# selection over keys and values per head alone
SCOPE_WINDOW_LATENT_ATTN = "window_latent_attn"
SCOPE_SPARSE_LATENT_ATTN = "sparse_latent_attn"
SCOPE_LATENT_INDEX = "latent_index"
SCOPE_LATENT_SELECT = "latent_select"
# JAX's own name-stack component of a rematerialised (recomputed) operation;
# ``checkpoint`` alone is also on the backward pass of a checkpointed region
SCOPE_REMAT = "rematted_computation"

# XLA's TPU compiler replaces a ragged dot by Mosaic calls of its own
# (``ragged-dot-none.N`` and the small ``ragged-dot-metadata.N``) whose
# ``op_name`` is that name alone, not the path of the line that asked for
# the dot. The program has one such line, the experts' grouped matmul
# where it falls back to ``jax.lax.ragged_dot`` (moe/experts.py, under
# ``moe_experts``), so an instruction with such a bare name is given that
# scope; whether it ran forward, backward or recomputed cannot be told
# from it. The repo's own kernel (ops/pallas/grouped_matmul.py:
# ``ragged-dot-gmm.N``, ``ragged-dot-tgmm.N``) keeps the whole path as
# every Pallas call does, ``moe_experts`` and ``rematted_computation``
# included, and needs no rule.
_RAGGED_DOT = "ragged-dot"

_CARRY_FREE = frozenset((
    SCOPE_OPTIMIZER, SCOPE_GRAD_CAST, SCOPE_OVERFLOW_CHECK,
    SCOPE_GRAD_NORM_CLIP, SCOPE_LM_HEAD, SCOPE_LM_HEAD_CE, SCOPE_MLM_HEAD,
    SCOPE_ATTN_CORE, SCOPE_KV_CACHE_WRITE, SCOPE_KV_CACHE_READ, SCOPE_SAMPLE,
    SCOPE_SSM_IN_PROJ, SCOPE_SSM_CONV, SCOPE_SSM_SCAN, SCOPE_SSM_GATE_NORM,
    SCOPE_SSM_OUT_PROJ, SCOPE_CONV_IN_PROJ, SCOPE_CONV_GATE_CONV,
    SCOPE_CONV_OUT_PROJ, SCOPE_RET_PROJ, SCOPE_RET_QK_NORM_ROPE,
    SCOPE_RET_STATE, SCOPE_RET_OUT_PROJ, SCOPE_MLA_Q_PROJ,
    SCOPE_MLA_KV_PROJ, SCOPE_MLA_ABSORB, SCOPE_MLA_ATTN,
    SCOPE_MLA_OUT_PROJ, SCOPE_DSA_INDEX_PROJ, SCOPE_DSA_INDEX_SCORES,
    SCOPE_DSA_SELECT, SCOPE_DSA_ATTN, SCOPE_WINDOW_ATTN, SCOPE_FULL_ATTN,
    SCOPE_WINDOW_LATENT_ATTN, SCOPE_SPARSE_LATENT_ATTN, SCOPE_LATENT_INDEX,
    SCOPE_LATENT_SELECT))
_STRUCTURE = re.compile(
    r"^(jit\(.*\)|pjit\(.*\)|while|body|cond|branch_\d+_fun|closed_call|"
    r"core_call|custom_jvp_call|custom_vjp_call|custom_vjp_call_jaxpr)$")
CONTAINERS = ("while", "conditional", "call")

_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"[\]})] ([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_SHAPE = re.compile(r"^\(?\w+\[([\d,]*)\]")
_EVENT_NAME = re.compile(r"^%?(\S+) = ")


def split_path(path):
    """The components of an ``op_name`` path. ``/`` inside parentheses
    (``jit(a/b)``) does not split."""
    out, depth, cur = [], 0, []
    for ch in path:
        if ch == "/" and depth == 0:
            out.append("".join(cur))
            cur = []
            continue
        depth += ch == "("
        depth -= ch == ")"
        cur.append(ch)
    out.append("".join(cur))
    return [c for c in out if c]


def components(path):
    """The scope components of a path: what is left of it without the
    ``jit(...)`` wrappers, the loop and branch structure and the primitive
    at the end. Empty when the path names nothing deeper than its
    program's root (or is None)."""
    if not path:
        return []
    return [c for c in split_path(path)[:-1] if not _STRUCTURE.match(c)]


def has_scope(path, *names):
    """Whether one of ``names`` is a component of ``path``, bare or under
    JAX's transform wrappers (``transpose(jvp(optimizer))``)."""
    for c in components(path):
        bare = c.rsplit("(", 1)[-1].rstrip(")") if "(" in c else c
        if bare in names:
            return True
    return False


def parse_hlo(text):
    """``(module_name, instructions, fused)`` of an HLO module's text.
    ``instructions`` maps each instruction outside fused computations (the
    ones a trace has events for) to ``(op_name, opcode, dims, callee)``:
    ``dims`` is the result's shape (of the first array of a tuple),
    ``callee`` a fusion's computation. ``fused`` maps each fused
    computation to the op_names of its instructions."""
    module = None
    comps = {}    # computation -> [(name, op_name, opcode, dims, callee)]
    cur = None
    for line in text.splitlines():
        if module is None:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
                continue
        if cur is None:
            m = _COMPUTATION.match(line)
            if m:
                cur = comps.setdefault(m.group(1), [])
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = _OPCODE.search(" " + rest)
        opcode = op.group(1) if op else "?"
        on = _OP_NAME.search(rest)
        shape = _SHAPE.match(rest)
        dims = tuple(int(d) for d in shape.group(1).split(",") if d) \
            if shape else None
        callee = None
        if opcode == "fusion":
            c = _CALLS.search(rest)
            callee = c.group(1) if c else None
        # XLA joins the op_names of instructions it merged with ";"
        op_name = on.group(1).split(";")[0] if on else None
        cur.append((name, op_name, opcode, dims, callee))
    fused = {row[4] for rows in comps.values() for row in rows if row[4]}
    out = {row[0]: row[1:] for comp, rows in comps.items()
           if comp not in fused for row in rows}
    return module, out, {c: [r[1] for r in comps.get(c, ())] for c in fused}


def _tag_carry(path, module, opcode, tag):
    parts = split_path(path) if path else ["jit(%s)" % module, opcode]
    return "/".join(parts[:-1] + [tag, parts[-1]])


def _carry_tags(carry_shapes):
    """``{shape: tag}`` from ``carry_shapes``: a mapping ``{tag: shapes}``,
    or shapes alone, which are KV-cache leaves'."""
    if not hasattr(carry_shapes, "items"):
        carry_shapes = {SCOPE_KV_CACHE_CARRY: carry_shapes}
    return {tuple(s): tag for tag, shapes in carry_shapes.items()
            for s in shapes}


def instruction_scopes(text, carry_shapes=()):
    """``(program_name, {instruction: path})`` from one executable's
    ``as_text()``. A fusion takes its own ``op_name``; where it has none,
    the most common one among the instructions of its fused computation.
    The compiler's own ragged-dot calls get ``moe_experts`` (see
    ``_RAGGED_DOT``).
    An instruction that none of the program's named scopes owns and whose
    result has one of ``carry_shapes`` (tuples of ints, or ``{tag:
    shapes}``) gets the ``kv_cache_carry`` component, or the mapping's tag.
    An instruction with no scope at all is kept, mapped to None."""
    module, parsed, fused = parse_hlo(text)
    carry = _carry_tags(carry_shapes)
    table = {}
    for name, (op_name, opcode, dims, callee) in parsed.items():
        path = op_name
        if path is None and callee:
            inner = collections.Counter(
                p for p in fused.get(callee, ()) if p)
            if inner:
                path = inner.most_common(1)[0][0]
        if path and path.startswith(_RAGGED_DOT) and "/" not in path:
            path = "jit(%s)/%s/%s" % (module, SCOPE_MOE_EXPERTS, path)
        if dims in carry and opcode not in CONTAINERS \
                and not has_scope(path, *_CARRY_FREE):
            path = _tag_carry(path, module, opcode, carry[dims])
        table[name] = path
    return module, table


def avals_like(tree):
    """Avals that lower to the SAME executable jit already dispatched for
    ``tree``: shape, dtype and, for committed arrays, the sharding.
    ``jitted.lower(avals).compile()`` is then a hit in jit's own lowering
    cache; an aval without the sharding is a different cache key and
    costs a second full XLA compile. Leaves that are no arrays (static
    arguments) stay as they are."""
    import jax

    def aval(x):
        if not hasattr(x, "shape") or not hasattr(x, "dtype"):
            return x
        sharding = x.sharding if getattr(x, "committed", False) else None
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    return jax.tree.map(aval, tree)


class DispatchedProgram:
    """A jitted step program that remembers what it was dispatched on.

    Calls go straight through to ``fn``. The first call under each
    ``key(args)`` (the shapes that select a specialisation: a prompt
    bucket, a scan length) also keeps the arguments' avals, so that
    :meth:`lowered` can hand out exactly the executables that ran, after
    the fact and off the hot path. That first call is also where JAX
    traces, lowers and compiles or loads the specialisation: the build log
    (telemetry/builds.py) is told of it beforehand and closes it itself
    when the compile or load ends, so that ``__call__`` stays the frame it
    was under every jitted call (a ``with`` around the first call, three
    more stack slots, cost the serve ramp 1.2 s of 17.5 on the v5e's host:
    PERF.md, section 6, PR 37). ``before_first`` (settable, called with a
    first call's arguments inside its window) exists for the build log's
    attribution alone: what the admission prefill's one tracing for all
    prompt buckets costs (inference/engine.py ``traced_once``) is timed in
    the first call that needs it, as stages of its own and not as part of
    that bucket's tracing."""

    __slots__ = ("fn", "key", "avals", "before_first")

    def __init__(self, fn, key):
        self.fn = fn
        self.key = key
        self.avals = {}
        self.before_first = None

    def __call__(self, *args):
        k = self.key(args)
        if k not in self.avals:
            self.avals[k] = self._first(args)
        return self.fn(*args)

    def _first(self, args):
        """The avals to keep for a specialisation's first call, which
        follows; the build log is told what it builds."""
        build_log.first_call(self.fn, self.key(args))
        if self.before_first is not None:
            self.before_first(*args)
        return avals_like(args)

    def lowered(self):
        """``jax.stages.Lowered`` of every specialisation run (its
        ``compile()`` is a hit in jit's own cache)."""
        return [self.fn.lower(*a) for a in self.avals.values()]


def _common_path(a, b):
    if a is None or b is None:
        return None
    pa, pb = split_path(a), split_path(b)
    n = 0
    while n < min(len(pa), len(pb)) - 1 and pa[n] == pb[n]:
        n += 1
    # the agreed components, then a primitive slot so that components()
    # drops nothing real
    return "/".join(pa[:n] + ["*"]) if n else None


def scope_table(texts, carry_shapes=()):
    """``{program_name: {instruction: path}}`` from the ``as_text()`` of
    several executables. Executables of one name (a prefill program per
    prompt bucket) share one entry: where they disagree on an instruction,
    the entry keeps the components they agree on, so a joined trace is
    never told a scope that one of them does not have."""
    table = {}
    for text in texts:
        program, scopes = instruction_scopes(text, carry_shapes)
        into = table.setdefault(program, {})
        for name, path in scopes.items():
            into[name] = _common_path(into[name], path) \
                if name in into and into[name] != path else path
    return table


# ---------------------------------------------------------------------------
# joining a trace
# ---------------------------------------------------------------------------
def event_instruction(text):
    """``(instruction, opcode)`` of an ``XLA Ops`` event, which is named
    by its HLO instruction's whole text; a text that is no instruction is
    its own name with the opcode ``?``. An opcode in ``CONTAINERS`` spans
    the events of its body and is no work of its own."""
    nm = _EVENT_NAME.match(text)
    op = _OPCODE.search(text, nm.end() - 1) if nm else None
    return (nm.group(1) if nm else text.lstrip("%"),
            op.group(1) if op else "?")


def load_trace(path):
    """A ``ProfileData`` from an ``.xplane.pb`` or ``.xplane.pb.gz`` file."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def time_by_scope(profile, table, window=None):
    """Rows ``{"program", "instruction", "path", "seconds", "count"}``: the
    device time of every executed HLO instruction (``XLA Ops`` of the
    ``/device:TPU:<n>`` planes, containers left out, clipped to ``window``
    = (start_ns, end_ns) when given), averaged over the chips, with the
    program it ran in (the enclosing ``XLA Modules`` event) and its path in
    ``table`` (None when the table lacks the program or the instruction)."""
    import bisect

    sums, counts, chips = {}, {}, 0
    for plane in profile.planes:
        if not re.match(r"^/device:TPU:\d+$", plane.name):
            continue
        chips += 1
        modules, ops = [], None
        for line in plane.lines:
            if line.name == "XLA Modules":
                modules = sorted(
                    (e.start_ns, e.start_ns + e.duration_ns,
                     e.name.split("(")[0]) for e in line.events)
            elif line.name == "XLA Ops":
                ops = line.events
        starts = [m[0] for m in modules]
        opcodes = {}
        for e in ops or ():
            text = e.name
            if text not in opcodes:
                opcodes[text] = event_instruction(text)
            name, opcode = opcodes[text]
            if opcode in CONTAINERS:
                continue
            a, b = e.start_ns, e.start_ns + e.duration_ns
            if window:
                a, b = max(a, window[0]), min(b, window[1])
                if b <= a:
                    continue
            i = bisect.bisect_right(starts, e.start_ns) - 1
            program = modules[i][2] \
                if i >= 0 and e.start_ns < modules[i][1] else "?"
            key = (program, name)
            sums[key] = sums.get(key, 0.0) + (b - a)
            counts[key] = counts.get(key, 0) + 1
    chips = max(1, chips)
    return [{"program": p, "instruction": n,
             "path": table.get(p, {}).get(n),
             "seconds": v / chips / 1e9, "count": counts[(p, n)]}
            for (p, n), v in sums.items()]


def share(rows, keep, of=None):
    """Percent of the rows' device time (of those ``of`` keeps, when given)
    spent in the rows ``keep`` keeps; None when there is no time."""
    base = [r for r in rows if of is None or of(r)]
    whole = sum(r["seconds"] for r in base)
    if not whole:
        return None
    return 100.0 * sum(r["seconds"] for r in base if keep(r)) / whole


def attributed(row):
    return bool(components(row["path"]))


def by_scope(rows):
    """``[(scope, seconds)]``, longest first; a scope is the row's
    components joined by ``/``, ``(unattributed)`` when it has none."""
    sums = {}
    for r in rows:
        key = "/".join(components(r["path"])) or "(unattributed)"
        sums[key] = sums.get(key, 0.0) + r["seconds"]
    return sorted(sums.items(), key=lambda kv: -kv[1])


def main(argv):
    if len(argv) != 2:
        print("usage: python -m deepspeed_tpu.telemetry.scopes "
              "<trace.xplane.pb[.gz]> <table.json>", file=sys.stderr)
        return 2
    with open(argv[1]) as f:
        table = json.load(f)
    rows = time_by_scope(load_trace(argv[0]), table)
    whole = sum(r["seconds"] for r in rows) or 1.0
    print(f"{'seconds':>10}  {'share':>6}  scope")
    for scope, secs in by_scope(rows):
        print(f"{secs:10.6f}  {100 * secs / whole:5.1f}%  {scope}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
