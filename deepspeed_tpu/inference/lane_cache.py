"""What a lane keeps on the device, and what may be done to it.

A model declares the leaves of its decode cache once (``GPTConfig.
cache_leaves``, a tuple of ``CacheLeaf``: models/transformer_lm.py).
This module is the one reader of that declaration on the serving side and
the one place that knows a lane cache's leaves by name or shape: its
geometry and bytes, the empty cache, the programs that write one lane of it,
copy it and step it back, the host's copy of the lanes' clocks, the readers
of what a run left behind, and what a kind of leaf refuses. The scheduler
makes one ``LaneLayout`` per engine; ``serving/disagg.py`` sizes capacity
from the same geometry. Not declared, and named here alone: the clocks and
masks every cache has (``cache_index``, ``position``, ``valid``,
``slot_pos``).

A declaration may hold THREE families of leaf side by side, each held by a
kind of layer (``CacheLeaf.held_by``): a dense leaf of every position, a
window's ring of rows beside ``slot_pos``, and an index key beside the rows
it chooses among with what a step leaves of its choice; and two kinds may
keep leaves of one name at different LENGTHS (PR 56: a ring beside a dense
leaf) and WIDTHS (latent attention by kind: a 1,024-wide latent in a ring
beside a 512-wide one in a dense leaf). Everything here finds a leaf by
name AND kind (``_declared``), counts each family apart (``geometry``:
``latent_``, ``window_`` and ``index_key_bytes_per_lane``; a ring of
latents counts as both ``latent`` and ``window``), reads one kind's stack
(``LanesAtExit.positions(held_by=)``) and refuses by family
(``_REFUSALS``: ``LatentCacheError``, ``MixedCacheError``,
``IndexKeyError``, the first leaf declared answering first).
``LaneLayout.window`` and ``LaneLayout.chosen`` are the rows a window
layer and a choosing layer read of a lane (``serve.stats``
``live_window_positions`` / ``live_chosen_positions``).
"""

from collections.abc import Mapping
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.engine import probe_length
from deepspeed_tpu.models.transformer_lm import (
    KIND_ATTENTION,
    KIND_WINDOW,
    IndexKeyError,
    LatentCacheError,
    MixedCacheError,
    declared_cache_leaves,
)
from deepspeed_tpu.ops.pallas.decode_attention import live_blocks
from deepspeed_tpu.ops.sparse_attention.sparse_attention_utils import (
    ring_engaged,
)
from deepspeed_tpu.telemetry.scopes import DispatchedProgram


# what of ``LaneLayout.geometry`` the scheduler's ``serve.cache_plan``
# event says, each where the cache has it: a lane's bytes in all and by
# family of leaf
PLAN_FIELDS = ("kv_bytes_per_lane", "state_bytes_per_lane",
               "conv_bytes_per_lane", "norm_bytes_per_lane",
               "latent_bytes_per_lane", "bytes_per_lane",
               "window_bytes_per_lane", "index_key_bytes_per_lane")


class RecurrentStateError(ValueError):
    """A serving feature that truncates a cache to a shorter prefix was
    asked of a model whose blocks hold recurrent state
    (``GPTConfig.recurrent_leaves``): keys and values of the first ``n``
    positions are a prefix's cache, a state after ``m > n`` tokens is
    not."""

    def __init__(self, feature: str, why: str, leaves):
        super().__init__(
            f"{feature} cannot serve a model with recurrent state "
            f"({', '.join(leaf.name for leaf in leaves)}: "
            f"GPTConfig.recurrent_leaves): {why}")
        self.feature = feature


# (the feature asked, the kind of declared leaf that refuses it, why);
# "latent" is a position leaf counted as latent: one clock a lane, no
# heads, its leaves with whoever runs the layers; "index" a position leaf
# counted as index: an indexer's key beside keys and values, under their
# clock (so ``_rewind`` steps it back with them, and a draft engine is
# served); "window" a position leaf counted as window: a ring of rows
# beside ``slot_pos`` (``GPTConfig.sliding_window``), which in such a model
# stands beside other layers' dense leaves
_REFUSALS = (
    ("draft_engine (speculative decoding)", "recurrent",
     "_rewind steps the cache clocks back past the rejected tokens, and a "
     "state that has absorbed them cannot be stepped back"),
    ("prefix_cache", "recurrent",
     "an entry is a cache cut at a promotion boundary, and the state of a "
     "longer prompt cannot be cut there (a snapshot of the state at the "
     "boundary would do; serving/prefix_cache.py takes none)"),
    ("draft_engine (speculative decoding)", "latent",
     "_rewind restores each attention module's leaves beside its own "
     "clock, and a latent cache has one clock a lane and its leaves with "
     "whoever runs the layers; nothing has verified a draft against it"),
    ("prefix_cache", "latent",
     "a continuation over a cached prefix runs the absorbed form with many "
     "query tokens, which no entry has been cut for or checked against; "
     "serving/prefix_cache.py sizes its entries by keys and values per "
     "head"),
    ("tp > 1", "latent",
     "the latent is shared by all heads, so sharding the heads over tp "
     "leaves every device the whole cache and the decompression matrices "
     "have no sharding rule (models/transformer_lm.py gpt_tp_rules)"),
    ("kv_cache_dtype='int8'", "index",
     "the int8 format keeps one scale per (position, KV head) beside the "
     "keys and the values; the index key has no heads and no scale, and "
     "an indexer that scores rounded keys chooses other positions"),
    ("prefix_cache", "index",
     "serving/prefix_cache.py sizes and cuts its entries by keys and "
     "values per head; an entry without the prefix's index keys would "
     "leave a continuation nothing to choose among"),
    ("tp > 1", "index",
     "there is one index key head for all query heads, so sharding the "
     "heads over tp leaves every device the whole leaf and the indexer's "
     "projections have no sharding rule (models/transformer_lm.py "
     "gpt_tp_rules)"),
    ("draft_engine (speculative decoding)", "window",
     "the verify pass writes spec_k + 1 rows into every layer before it "
     "reads, and nothing has verified a draft against a cache whose window "
     "layers overwrite their oldest rows while the others keep all "
     "(_rewind steps both kinds back: tests/unit/test_afmoe.py; the "
     "scheduler's rules for a ring are the block layout's, "
     "ops/sparse_attention ring_storage_len)"),
    ("prefix_cache", "window",
     "an entry is a cache cut at a promotion boundary and continued by "
     "another prompt; a window layer's ring at the boundary has already "
     "dropped the rows a shorter cut would need, and "
     "serving/prefix_cache.py sizes its entries by keys and values of "
     "every position in every layer"),
)

_REFUSAL_ERRORS = {"latent": LatentCacheError, "index": IndexKeyError,
                   "window": MixedCacheError}


def _path_keys(path):
    return [str(getattr(part, "key", part)) for part in path]


def _leaf_name(path) -> str:
    return _path_keys(path)[-1]


def _ranks(leaves, kind: str):
    """name -> rank of the declared leaves of ``kind`` that hold the
    model's own values (not an int8 store's sideband)."""
    return {leaf.name: leaf.rank for leaf in leaves
            if leaf.kind == kind and "sideband" not in leaf.counted_as}


def _declared(leaves, path):
    """The declared leaf that the cache leaf at ``path`` is, or None: by
    name and, where the declaration says which kind of layer holds it
    (a model that mixes kinds stacks each kind's leaves under its name),
    by that kind among the path's keys."""
    keys = _path_keys(path)
    return next((leaf for leaf in leaves if leaf.name == keys[-1]
                 and (leaf.held_by is None or leaf.held_by in keys)), None)


def _first_leaf_shape(tree):
    return jax.tree.leaves(tree)[0].shape


class LaneClocks:
    """Where each lane's rows begin and where its next query sits, kept on
    the host from what admissions and steps do to the device's clocks, so
    that a decode step can say what its attention reads without asking the
    device: ``step`` gives the blocks read over the blocks held, all lanes,
    by the kernel's own rule (ops/pallas/decode_attention.py
    ``live_blocks``), 1.0 where attention takes the einsums over every
    position (``block`` None), and None for a cache with nothing per
    position (``block`` 0): nothing is summed, and the step's span carries
    no ``kv_blocks_read_share``. A lane that holds no request keeps its
    clock running, as on the device, and is read up to it."""

    def __init__(self, stats, slots: int, positions: int, block):
        self.stats = stats
        self.block = block
        self.positions = positions
        self.first = np.zeros((slots,), np.int64)
        self.clock = np.zeros((slots,), np.int64)

    def admit(self, lane: int, bucket: int, prompt_len: int, replayed: int):
        self.first[lane] = bucket - prompt_len
        self.clock[lane] = bucket + replayed

    def live_positions(self, lanes, window: Optional[int] = None) -> int:
        """Rows that the requests now in ``lanes`` (None: a free lane)
        have written: each one's prompt and what it has decoded, all
        lanes summed; the positions a decode step's attention has to
        read, whatever it does read. With ``window``, each lane's newest
        ``window`` rows at most: what a layer that sees a window reads."""
        held = np.fromiter((lane is not None for lane in lanes), bool,
                           len(lanes))
        rows = (self.clock - self.first)[held]
        return int((rows if window is None
                    else np.minimum(rows, window)).sum())

    def step(self) -> Optional[float]:
        if self.block == 0:
            return None
        share = 1.0
        if self.block is not None:
            lo, hi = live_blocks(
                self.first, np.minimum(self.clock, self.positions - 1),
                self.block)
            share = float((hi - lo + 1).sum()) \
                / (-(-self.positions // self.block) * len(self.clock))
        self.clock += 1
        self.stats.kv_blocks_read_share_sum += share
        return share


class LanesAtExit:
    """What ``run`` left on the device when it ended with a decode step in
    flight (``ContinuousBatchingScheduler.retain_lanes``): the lane cache
    as that step left it, and ``live``, lane number -> the ``Completion``
    so far of the request that still held the lane.

    A live lane's rows, its recurrent state included, have taken in the
    request's prompt and every token of ``Completion.tokens``: the step in
    flight consumed the last of them, and what it computed is nobody's.
    That holds for a run ended from ``poll_fn``, between two steps; a
    ``stream_callback`` that raises ends it inside a step's delivery, and
    the lanes after its own are then one undelivered token ahead."""

    def __init__(self, leaves, owners, cache):
        self.leaves = leaves     # the model's declaration
        self.cache = cache
        self.live = {n: lane.comp for n, lane in enumerate(owners)
                     if lane is not None and not lane.comp.t_done}

    def recurrent_state(self, lane: int):
        """``{leaf name: [layers, ...]}`` of one lane, as stored, for each
        leaf declared as recurrent (``ssm_state`` ``[layers, H, P, N]``,
        ``ret_norm`` ``[layers, Hkv, D]``, ...): the stacked leaves of
        ``ScannedBlocks`` or, layer by layer in tree order, an unrolled
        model's. Empty for a model without one."""
        return self._lane_leaves(lane, _ranks(self.leaves, "recurrent"))

    def positions(self, lane: int, held_by: Optional[str] = None):
        """The same for what the model keeps PER POSITION (keys and values
        ``[layers, S, Hkv, D]``, or a latent and a rotary key ``[layers,
        S, width]``), with ``valid`` ``[layers or 1, S]``: which rows the
        lane's request wrote. Where kinds of layer keep leaves of one name
        and different lengths (a window's ring beside a dense cache),
        ``held_by`` names the kind whose stack is read, and a ring comes
        with ``slot_pos`` ``[layers, rows]``: the position each row holds."""
        return self._lane_leaves(
            lane, dict(_ranks(self.leaves, "position"), valid=2, slot_pos=2),
            held_by)

    def last_step(self, lane: int):
        """The same for what the lane's last decode step left of itself
        (an indexer's ``chosen_rows`` ``[layers, topk]``: the rows that
        step's query attended over, and the query). Empty for a model that
        leaves nothing."""
        return self._lane_leaves(lane, _ranks(self.leaves, "step"))

    def _lane_leaves(self, lane: int, rank, held_by=None):
        out = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self.cache)[0]:
            name = _leaf_name(path)
            if name in rank and (held_by is None
                                 or held_by in _path_keys(path)):
                one = jax.lax.dynamic_index_in_dim(
                    leaf, jnp.int32(lane), leaf.ndim - rank[name],
                    keepdims=False)
                out.setdefault(name, []).append(
                    one if one.ndim == rank[name] else one[None])
        return {name: jnp.concatenate(parts) for name, parts in out.items()}


def _splice_program():
    def splice(full, sub, lane_idx):
        # the batch axis differs per leaf (flax nn.scan caches carry a
        # leading layer axis: ``[L, B, ...]`` vs the top-level
        # ``position``/``cache_index`` at ``[B]``), so each leaf locates
        # its own first differing axis
        def one(f, s):
            if f.shape == s.shape:  # slots == 1
                return s
            ax = next(i for i, (a, b)
                      in enumerate(zip(f.shape, s.shape)) if a != b)
            starts = tuple(lane_idx if i == ax else 0
                           for i in range(f.ndim))
            return jax.lax.dynamic_update_slice(f, s, starts)

        return jax.tree.map(one, full, sub)

    return DispatchedProgram(jax.jit(splice, donate_argnums=(0,)),
                             key=lambda a: _first_leaf_shape(a[1]))


def _copy_program():
    def copy_tree(t):
        return jax.tree.map(jnp.copy, t)

    return DispatchedProgram(jax.jit(copy_tree),
                             key=lambda a: _first_leaf_shape(a[0]))


def _rewind_program(per_position, as_left=()):
    """Selective per slot, not a snapshot swap: the accepted prefix's
    writes are exactly those sequential decode would have made and must
    SURVIVE, so a slot is stale (take the snapshot's) iff it was written
    at a position at or past the new clock: ring caches compare
    ``slot_pos``, dense ones the storage index, which is the position
    (``per_position``: name -> rank of the leaves that have one).
    ``cache_index`` and the top-level ``position`` counters step back by
    delta. What a step leaves of itself (``as_left``: names) has no slots
    and stays as the last pass left it."""
    def rewind(c0, c1, d):
        def rewind_attn(a0, a1):
            ci = a1["cache_index"]
            # ci is [B] ([L, B] under nn.scan); d broadcasts up
            idx_new = ci - d.astype(ci.dtype)
            if "slot_pos" in a1:
                stale = a1["slot_pos"] >= idx_new[..., None]
            else:
                name, rank = next(iter(per_position.items()))
                s_len = a1[name].shape[1 - rank]
                pos = jnp.arange(s_len, dtype=ci.dtype)
                stale = pos >= idx_new[..., None]
            out = {}
            for k in a1:
                if k == "cache_index":
                    out[k] = idx_new
                    continue
                if k in as_left:
                    out[k] = a1[k]
                    continue
                v0, v1 = a0[k], a1[k]
                m = stale.reshape(
                    stale.shape + (1,) * (v1.ndim - stale.ndim))
                out[k] = jnp.where(m, v0, v1)
            return out

        def walk(t0, t1, top):
            out = {}
            for k in t1:
                v1 = t1[k]
                if isinstance(v1, Mapping):
                    if "cache_index" in v1:
                        out[k] = rewind_attn(t0[k], v1)
                    else:
                        out[k] = walk(t0[k], v1, False)
                elif top and k == "position":
                    out[k] = v1 - d.astype(v1.dtype)
                else:
                    out[k] = v1
            return out

        return walk(c0, c1, True)

    return DispatchedProgram(jax.jit(rewind, donate_argnums=(1,)),
                             key=lambda a: _first_leaf_shape(a[1]))


class LaneLayout:
    """The ``[slots]``-lane decode cache of one engine's model (or of a
    bare module, for sizing: abstract parameters, nothing materialized)."""

    def __init__(self, owner, slots: int):
        self.engine = owner if hasattr(owner, "module") else None
        self.module = getattr(owner, "module", owner)
        self.config = getattr(self.module, "config", None)
        self.slots = int(slots)
        self.leaves = declared_cache_leaves(self.config)
        # the positions a window layer sees, where the model has such
        # layers (the declaration's: ``GPTConfig.attention_kind``)
        kind_of = getattr(self.config, "attention_kind", lambda mixer: None)
        kind = kind_of(KIND_WINDOW)
        self.window = None if kind is None else kind.window
        # and the rows a decode query attends over where the layers that
        # keep every position choose among them (a latent kind's indexer
        # with a choice to make: ``LatentKind.indexer``)
        full = kind_of(KIND_ATTENTION)
        ix = getattr(getattr(full, "latent", None), "indexer", None)
        self.chosen = ix.topk if ix is not None \
            and ix.engaged(self.config) else None
        self._shapes = None
        self._geometry = None
        # each layout's own jitted functions: a build is seen per scheduler
        self._splice_fn = _splice_program()
        self._copy_fn = _copy_program()
        self._rewind_fn = _rewind_program(_ranks(self.leaves, "position"),
                                          tuple(_ranks(self.leaves, "step")))

    def refuse(self, draft_engine: bool, prefix_cache: bool) -> None:
        """Raise for the first serving feature asked that a declared leaf
        cannot serve (``_REFUSALS``)."""
        asked = {"draft_engine (speculative decoding)": draft_engine,
                 "prefix_cache": prefix_cache,
                 "tp > 1": self.engine.topology.size("tp") > 1,
                 "kv_cache_dtype='int8'": getattr(
                     self.config, "kv_cache_dtype", None) == "int8"}
        for feature, kind, why in _REFUSALS:
            held = tuple(leaf for leaf in self.leaves
                         if kind in (leaf.kind,) + leaf.counted_as)
            if held and asked[feature]:
                raise (RecurrentStateError(feature, why, held)
                       if kind == "recurrent"
                       else _REFUSAL_ERRORS[kind](feature, why))

    @property
    def shapes(self):
        """Leaf geometry (``jax.eval_shape``, nothing materialized) of the
        cache, memoized: an engine's from its parameters as they are, so
        after they exist; a bare module's from abstract ones."""
        if self._shapes is None:
            model, eng = self.module, self.engine
            probe = jnp.zeros((self.slots, 1), jnp.int32)

            def shape_fn(params):
                _, vars_out = model.apply(
                    {"params": params if eng is None
                     else eng._dequant(params)}, probe,
                    deterministic=True, decode=True, mutable=["cache"])
                return vars_out["cache"]

            self._shapes = jax.eval_shape(
                shape_fn,
                self._abstract_params() if eng is None else eng._params)
        return self._shapes

    def _abstract_params(self):
        ring = ring_engaged(self.config)
        ids = jnp.zeros((1, probe_length(
            self.config, ring[2] if ring is not None else 64)), jnp.int32)
        return jax.eval_shape(
            lambda: self.module.init(jax.random.PRNGKey(0), ids,
                                     deterministic=True))["params"]

    def empty(self):
        """A cache with every per-row clock at its virgin value, WITHOUT
        running the model (a real apply would advance the clocks and bake
        garbage into ``slot_pos``): ``slot_pos`` is -1 (no position
        cached), a declared leaf what its declaration says an empty cache
        holds (``CacheLeaf.unset``), everything else zeros (``valid``
        False, clocks 0). The leaves are made on the sharding that the
        splice and decode programs hand back (committed, as every jitted
        result is when an argument is): an uncommitted first cache would
        be a second specialisation of each program that takes it."""
        where = None if self.engine is None \
            else self.engine.topology.replicated()

        unset = {"slot_pos": -1,
                 **{leaf.name: leaf.unset for leaf in self.leaves}}

        def init_leaf(path, sd):
            value = unset.get(_leaf_name(path), 0)
            if value:
                return jnp.full(sd.shape, value, sd.dtype, device=where)
            return jnp.zeros(sd.shape, sd.dtype, device=where)

        return jax.tree_util.tree_map_with_path(init_leaf, self.shapes)

    def splice(self, cache, sub_cache, lane):
        """Write a freshly prefilled ``[1, ...]`` cache into batch lane
        ``lane`` of the full cache (donated). Jitted once, lane traced."""
        return self._splice_fn(cache, sub_cache, jnp.int32(lane))

    def copy(self, tree):
        """Jitted deep copy of a cache pytree. Continuation prefill DONATES
        its cache argument, so both the cached entry handed to a lane and
        the snapshot taken at a promotion boundary must be fresh buffers:
        extending a cached tree in place would invalidate the cache."""
        return self._copy_fn(tree)

    def rewind(self, snapshot, cache, delta):
        """Step every per-row cache clock back by ``delta[B]`` REJECTED
        tokens, restoring from ``snapshot`` (the copy taken before the
        speculative pass) every entry those rejected writes clobbered.
        Jitted once; only the live cache is donated (an output leaf can
        reuse one input buffer at most)."""
        return self._rewind_fn(snapshot, cache, delta)

    @property
    def streams(self) -> bool:
        """Whether a lane may run past ``n_positions``: every leaf it keeps
        per position is a window's ring, which overwrites its oldest rows.
        One layer that keeps every position bounds the lane."""
        held = [leaf for leaf in self.leaves if leaf.kind == "position"]
        return bool(held) and all("window" in leaf.counted_as
                                  for leaf in held)

    def programs(self):
        """The programs dispatched so far (``empty`` fills eagerly)."""
        return [p for p in (self._splice_fn, self._copy_fn, self._rewind_fn)
                if p.avals]

    def at_exit(self, owners, cache) -> LanesAtExit:
        return LanesAtExit(self.leaves, owners, cache)

    def geometry(self) -> Dict[str, Any]:
        """What ``kv_cache_stats`` says without asking for the HBM size
        (its docstring says what each sum is): the bytes of the memoized
        shapes, each leaf in the sums its declaration names, and
        ``leaf_layers``, how many layers keep each declared leaf (a model
        that mixes kinds of layer stacks each leaf over its kind's layers
        alone), computed once."""
        if self._geometry is None:
            compute_dt = jnp.dtype(getattr(self.config, "dtype",
                                           jnp.float32))
            total = dict.fromkeys(
                ("resident", "unquantized", "recurrent", "state", "conv",
                 "norm", "latent", "index", "window", "sideband"), 0)
            layers = dict.fromkeys((leaf.name for leaf in self.leaves), 0)
            for path, sd in jax.tree_util.tree_flatten_with_path(
                    self.shapes)[0]:
                leaf = _declared(self.leaves, path)
                nbytes = sd.size * jnp.dtype(sd.dtype).itemsize
                total["resident"] += nbytes
                if leaf is not None:
                    # stacked over the layers that keep it, or one layer's
                    layers[leaf.name] += sd.shape[0] \
                        if len(sd.shape) > leaf.rank else 1
                for part in leaf.counted_as if leaf is not None else ():
                    total[part] += nbytes
                # the unquantised twin: the per-position leaves at the
                # compute dtype, no sideband, and everything else (clocks,
                # masks, states) as it is
                if leaf is not None and leaf.kind == "recurrent":
                    total["recurrent"] += nbytes
                if leaf is None or leaf.kind != "position":
                    total["unquantized"] += nbytes
                elif "sideband" not in leaf.counted_as:
                    total["unquantized"] += sd.size * compute_dt.itemsize
            total["kv"] = total["resident"] - total["recurrent"]
            geo = {"kv_cache_dtype": (getattr(self.config, "kv_cache_dtype",
                                              None) or "compute"),
                   "resident_bytes": total["resident"],
                   "unquantized_bytes": total["unquantized"],
                   "bytes_per_lane": total["resident"] // self.slots}
            for part in ("state", "conv", "norm", "kv"):
                geo[part + "_bytes"] = total[part]
            for part in ("state", "conv", "norm", "kv", "latent"):
                geo[part + "_bytes_per_lane"] = total[part] // self.slots
            if total["index"]:      # said only of a cache that has one
                geo["index_key_bytes_per_lane"] = \
                    total["index"] // self.slots
            if total["window"]:     # the rings' keys and values, likewise
                geo["window_bytes_per_lane"] = total["window"] // self.slots
            geo["lanes"] = self.slots
            geo["leaf_layers"] = layers
            geo["compression_ratio"] = (
                float(total["unquantized"]) / float(total["resident"])
                if total["resident"] else 1.0)
            self._geometry = geo
        return self._geometry
