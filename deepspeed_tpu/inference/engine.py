"""The inference engine.

Parity with reference ``deepspeed/inference/engine.py`` (InferenceEngine :32)
and ``deepspeed.init_inference`` (__init__.py:225): wrap a model for serving
with tensor-parallel sharding, dtype conversion (fp16/bf16/int8), sharded
checkpoint loading, and a generation loop over a KV-cache decode path.

TPU re-design:

* MP groups + tensor slicing (engine.py:212, replace_module.py) become a
  ``tp`` mesh axis + PartitionSpecs from the injection policy
  (module_inject); params materialize pre-sharded.
* CUDA-graph capture/replay (engine.py:523-551) is just jit: prefill and
  decode-step are compiled once and replayed.
* The fused decode kernels (softmax_context KV-cache attention,
  pt_binding.cpp) are the model's ``decode=True`` path; its cache lives in a
  flax ``cache`` collection threaded through the jitted step.
"""

import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import serialization

from deepspeed_tpu.module_inject import policy_for
from deepspeed_tpu.parallel.mesh import MeshTopology, set_default_topology
from deepspeed_tpu.runtime.checkpoint_engine import MsgpackCheckpointEngine
from deepspeed_tpu.runtime.zero.sharding import ZeroShardingRules
from deepspeed_tpu.telemetry.builds import build_log
from deepspeed_tpu.telemetry.scopes import (
    SCOPE_KV_CACHE_CARRY,
    SCOPE_SAMPLE,
    DispatchedProgram,
    scope_table,
)
from deepspeed_tpu.utils.compile_cache import ensure_compile_cache
from deepspeed_tpu.utils.logging import log_dist


# the names of the serving programs as a profiler trace has them (its
# ``XLA Modules`` events) and as ``program_scopes()`` keys them
PROGRAM_PREFILL = "jit_prefill"
PROGRAM_PREFILL_MORE = "jit_prefill_more"
PROGRAM_DECODE_K = "jit_decode_k"


def probe_length(config, bucket: int) -> int:
    """Tokens of the probe that materializes parameters: ``init`` traces
    the TRAINING forward, whose sparse layout needs a multiple of
    ``bucket`` (a multiple of the layout's block) with at least the full
    window of blocks present (sparsity_config ``make_layout``)."""
    sc = getattr(config, "sparse_attention", None)
    window = int(getattr(sc, "num_sliding_window_blocks", None) or 0) \
        * int(getattr(sc, "block", None) or 0)
    return -(-max(bucket, window) // bucket) * bucket


def carried_leaf_shapes(tree, leaves):
    """``{carry tag: shapes}``: the shapes each declared leaf
    (``GPTConfig.cache_leaves``) takes as a whole inside a program, from
    any pytree of arrays or avals that holds caches: the leaf as stored
    and, where the declaration says a layer's slice is a whole leaf too,
    without its leading layer axis where ``ScannedBlocks`` stacked it
    (which a turn of the layer loop reads inside its fusions and should
    never produce)."""
    declared = {leaf.name: leaf for leaf in leaves}
    shapes = {leaf.carry_tag: set() for leaf in leaves}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        decl = declared.get(str(getattr(path[-1], "key", "")))
        if decl is None:
            continue
        shape = tuple(leaf.shape)
        shapes[decl.carry_tag].add(shape)
        if decl.slice_is_whole and len(shape) > decl.rank:
            shapes[decl.carry_tag].add(shape[1:])
    return shapes


def programs_scope_table(programs, cache_leaves):
    """``scope_table`` of ``DispatchedProgram``s, with each declared
    leaf's carry tag (``kv_cache_carry``, ``ssm_state_carry``,
    ``ret_state_carry``) for the whole cache leaves among their arguments
    and results."""
    lowered = [(avals, low) for prog in programs
               for avals, low in zip(prog.avals.values(), prog.lowered())]
    carry = {SCOPE_KV_CACHE_CARRY: set()}
    for avals, low in lowered:
        for tag, shapes in carried_leaf_shapes(
                (avals, low.out_info), cache_leaves).items():
            carry.setdefault(tag, set()).update(shapes)
    return scope_table((low.compile().as_text() for _, low in lowered),
                       carry)


def _conform_host_quantized(host, shapes):
    """Host-side conversion of a dense imported param tree to the model's
    {q, scale} int8 storage structure. The structure (which leaves are
    quantized) comes from ``shapes`` — the eval_shape of
    models.transformer_lm.quantize_block_params — and the scale/clip math
    from the quantizer module, so neither can drift from the device path."""
    from deepspeed_tpu.ops.quantizer import quantize_weight_per_column_np

    if isinstance(shapes, dict) and set(shapes) == {"q", "scale"}:
        q, scale = quantize_weight_per_column_np(host, num_bits=8)
        return {"q": q, "scale": scale}
    if isinstance(shapes, dict):
        if not isinstance(host, dict):
            raise ValueError(
                f"imported params have a leaf where the model expects a "
                f"submodule with keys {sorted(shapes)}")
        if set(host) != set(shapes):
            # keep the loud structure-mismatch the dense placement path
            # raises — silently dropping misnamed imported leaves would
            # serve a half-loaded model
            raise ValueError(
                f"imported params do not match the model: extra "
                f"{sorted(set(host) - set(shapes))}, missing "
                f"{sorted(set(shapes) - set(host))}")
        return {k: _conform_host_quantized(host[k], v)
                for k, v in shapes.items()}
    return host


def prefill_chunk_spans(model_cfg, T: int):
    """Spans for an EXACT ring-cache prefill of a ``T``-token prompt.

    Returns None when a single pass is already exact: dense-cache models
    (no ring), or ``T <= ring_len`` from a fresh cache (no key is evicted
    before every query of the pass has attended it). Otherwise returns
    ``[(start, end), ...]`` block-aligned spans of at most ONE layout block
    each: a mid-stream pass covering layout blocks ``[b0, b1]`` needs
    blocks ``[b0 - w_blk .. b1]`` simultaneously ring-resident, and the
    ring holds exactly ``w_blk + 1`` blocks, so ``b1 == b0`` — one block
    per pass. The partial tail span stays inside one block, so it is exact
    too. ``<= ring_len``-token passes per the model's prefill guard.
    """
    from deepspeed_tpu.ops.sparse_attention.sparse_attention_utils import (
        ring_engaged,
        ring_storage_len,
    )

    ring = ring_engaged(model_cfg) if model_cfg is not None else None
    if ring is None:
        # window layers beside full ones (GPTConfig.pass_tokens): a pass
        # of at most that many tokens, from the first on, so that no pass
        # holds more than [pass, n_positions] scores a head either
        step = getattr(model_cfg, "pass_tokens", None)
        if step is None or T <= step:
            return None
        return [(s, min(s + step, T)) for s in range(0, T, step)]
    w_blk, g_tok, blk = ring
    ring_len = ring_storage_len(model_cfg, ring)
    if T <= ring_len:
        return None
    return [(s, min(s + blk, T)) for s in range(0, T, blk)]


def continuation_chunk_spans(model_cfg, start: int, end: int):
    """Spans for an EXACT continuation prefill of columns ``[start, end)``
    on a cache that already holds ``start`` written positions.

    The prefix-cache admission path resumes a chunked prefill mid-prompt
    (``prefill_chunk_spans`` only covers start-from-0), and ``start`` need
    NOT be block-aligned: a promotion snapshot can cut anywhere. The same
    residency argument applies span-by-span: a pass writing positions
    ``[s, e)`` evicts up to position ``e - ring_len``, while its earliest
    query needs block ``s//blk - w_blk`` resident — guaranteed iff the
    span never crosses a layout-block boundary. When ``end <= ring_len``
    nothing is evicted at all, so one pass is exact regardless of
    alignment; dense caches are always one pass.
    """
    from deepspeed_tpu.ops.sparse_attention.sparse_attention_utils import (
        ring_engaged,
        ring_storage_len,
    )

    if not 0 <= start < end:
        raise ValueError(f"bad continuation span [{start}, {end})")
    ring = ring_engaged(model_cfg) if model_cfg is not None else None
    if ring is not None:
        w_blk, g_tok, blk = ring
        ring_len = ring_storage_len(model_cfg, ring)
        if end > ring_len:
            return [(s, min(end, (s // blk + 1) * blk))
                    for s in range(start, end)
                    if s == start or s % blk == 0]
    step = getattr(model_cfg, "pass_tokens", None) or end - start
    return [(s, min(s + step, end)) for s in range(start, end, step)]


def export_prefill(prefill, params, granule: int, buckets_max: int,
                   platforms=None):
    """``jax.export.Exported`` of the jitted ``prefill(params, ids, mask)``
    over ``[1, granule * b]`` tokens with ``b`` a symbol, ``1 <= b <=
    buckets_max``: the model's Python runs once, here, and the module it
    leaves is specialised for a bucket wherever it is called under jit.
    ``params`` gives the parameters' shapes and dtypes (arrays, tracers or
    avals); ``platforms`` as ``jax.export.export`` takes them (None: the
    default backend's). Raises what tracing raises where a shape decision
    of the model needs a number for ``b``
    (``GPTConfig.prefill_bucket_dependence`` says which models)."""
    from jax import export

    b, = export.symbolic_shape(
        "b", constraints=("b >= 1", f"b <= {buckets_max}"))
    return export.export(prefill, platforms=platforms)(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                     params),
        jax.ShapeDtypeStruct((1, granule * b), jnp.int32),
        jax.ShapeDtypeStruct((1, granule * b), jnp.bool_))


def traced_once(per_bucket, granule: int, buckets_max: int):
    """``(program, before_first)`` for the admission prefill of a model
    whose token count decides shapes alone. ``program`` is ``jax.jit`` of a
    function named ``prefill`` as ``per_bucket``'s is (the compiled module
    stays ``jit_prefill``) that calls ONE export of ``per_bucket`` for every
    ``[1, granule * b]`` prompt, ``1 <= b <= buckets_max``: jit specialises
    the exported module for each bucket it meets in milliseconds, where
    tracing the model again is most of a second, and the operations and
    shapes, so the results, are the bucket's own program's to the bit. The
    export is made by the first call it covers. ``before_first`` makes it
    ahead of that call's own build (``DispatchedProgram.before_first``),
    for the build log's sake alone: made inside the bucket's tracing, the
    export's lowering would be counted as tracing. A call it does not cover
    (a batch of ``generate()``, a span that is no whole bucket) runs the
    model's Python under the same jit, as it always did."""
    made = []

    def once(params, ids, mask):
        rows, tokens = ids.shape
        if rows != 1 or tokens % granule or tokens > granule * buckets_max \
                or (ids.dtype, mask.dtype) != (jnp.int32, jnp.bool_):
            return None
        if not made:
            made.append(export_prefill(per_bucket, params, granule,
                                       buckets_max))
        return made[0]

    def prefill(params, ids, mask):
        exported = once(params, ids, mask)
        if exported is None:
            return per_bucket.__wrapped__(params, ids, mask)
        return exported.call(params, ids, mask)

    return jax.jit(prefill), once


def init_inference(model, config: Optional[Dict[str, Any]] = None,
                   mp_size: int = 1, dtype=None, checkpoint: Optional[str] = None,
                   replace_with_kernel_inject: bool = True, seed: int = 0,
                   ep_size: int = 1, **kwargs):
    """Build an InferenceEngine (reference deepspeed/__init__.py:225;
    ``ep_size`` is the reference's expert-parallel serving knob — engine.py
    :227 builds the EP process groups, moe_inference.py:206 serves through
    them)."""
    ensure_compile_cache()
    build_log.listen()
    config = dict(config or {})
    config.setdefault("tensor_parallel", {"tp_size": mp_size})
    if ep_size != 1:
        # copy the nested dict (the shallow config copy above would let
        # setdefault mutate the CALLER's moe block), and overwrite like
        # dtype/checkpoint do — an explicit argument wins over the config
        config["moe"] = dict(config.get("moe") or {}, ep_size=ep_size)
    if dtype is not None:
        config["dtype"] = dtype
    if checkpoint is not None:
        config["checkpoint"] = checkpoint
    config["replace_with_kernel_inject"] = replace_with_kernel_inject
    return InferenceEngine(model, config, seed=seed)


class InferenceEngine:
    def __init__(self, model, config: Dict[str, Any], seed: int = 0):
        self.module = model
        self._config = config
        tp_size = int(config.get("tensor_parallel", {}).get("tp_size", 1))
        self.mp_world_size = tp_size
        # expert-parallel serving (reference inference/engine.py:227
        # _create_ep_parallel_group + moe_inference.py:206): converted MoE
        # expert stacks shard over the ep axis instead of replicating —
        # an 8-expert model at ep=4 holds 2 experts' weights per chip, and
        # GSPMD emits the dispatch/combine all-to-alls from the layer's
        # sharding constraints
        ep_size = int(config.get("moe", {}).get("ep_size", 1))
        self.ep_world_size = ep_size

        n = len(jax.devices())
        assert n % (tp_size * ep_size) == 0, (
            f"tp_size {tp_size} x ep_size {ep_size} does not divide "
            f"{n} devices")
        self.topology = MeshTopology(tp=tp_size, ep=ep_size,
                                     dp=n // (tp_size * ep_size))
        set_default_topology(self.topology)

        dtype = config.get("dtype")
        self.dtype = {None: None, "fp16": jnp.float16, "float16": jnp.float16,
                      "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
                      "fp32": jnp.float32, "float32": jnp.float32,
                      "int8": jnp.int8}.get(dtype, dtype)

        # HF torch model? Run the injection policy: convert weights into the
        # equivalent flax model (reference replace_transformer_layer,
        # module_inject/replace_module.py:277 — there it swaps fused CUDA
        # modules in; here the flax model IS the fused path)
        from deepspeed_tpu.module_inject.hf import import_hf_model, is_hf_model

        hf_params = None
        if is_hf_model(model):
            compute = self.dtype if self.dtype in (
                jnp.float16, jnp.bfloat16, jnp.float32) else jnp.bfloat16
            self.module, hf_params = import_hf_model(model, dtype=compute)
            model = self.module

        # int8 serving, model-level: when the model's config supports
        # quantized_weights, let it store kernels int8-at-rest and
        # dequantize per layer INSIDE its scan (the convert fuses with
        # that layer's dots).
        # Models without the flag fall back to engine-level quantization
        # in _cast (functional, but the stacked dequant outside the layer
        # scan costs bandwidth).
        self._model_quantized = False
        cfg_obj = getattr(model, "config", None)
        if self.dtype == jnp.int8 and cfg_obj is not None:
            import dataclasses as _dc

            if any(f.name == "quantized_weights"
                   for f in _dc.fields(cfg_obj)):
                model = model.clone(config=_dc.replace(
                    cfg_obj, quantized_weights=True))
                self.module = model
                self._model_quantized = True
            # below ~200M params decode is dispatch-bound, not weight-
            # bandwidth-bound, and int8 measured a LOSS at 125M; the win
            # started around 350M and grew with size (last measured before
            # PR 1 through a chip access that no longer exists; not
            # measured on the current machine). Serve as asked, but say so
            # once.
            try:
                from deepspeed_tpu.models.transformer_lm import num_params
                n_model_params = num_params(cfg_obj)
            except Exception:
                n_model_params = None
            if n_model_params is not None and n_model_params < 200e6:
                from deepspeed_tpu.utils.logging import warning_once

                warning_once(
                    f"dtype=int8 on a ~{n_model_params / 1e6:.0f}M-param "
                    "model: decode at this size is dispatch-bound and int8 "
                    "has measured slower than bf16; the win starts around "
                    "350M params")

        # int8 KV cache (serving capacity lever, GPTConfig.kv_cache_dtype):
        # orthogonal to weight quantization — "kv_cache": "int8" stores the
        # decode cache int8 with per-slot f32 scales and dequantizes on
        # read (models/transformer_lm.py decode attention). Same clone
        # pattern as quantized_weights above.
        kv_cache = config.get("kv_cache")
        if kv_cache is not None:
            import dataclasses as _dc

            kcfg = getattr(model, "config", None)
            if kcfg is None or not any(f.name == "kv_cache_dtype"
                                       for f in _dc.fields(kcfg)):
                raise ValueError(
                    "inference config 'kv_cache' needs a model whose config "
                    "carries kv_cache_dtype (models/transformer_lm.GPTConfig)")
            model = model.clone(config=_dc.replace(
                kcfg, kv_cache_dtype=kv_cache))
            self.module = model

        # injection policy -> TP sharding rules (reference
        # _apply_injection_policy, inference/engine.py:364)
        rules = policy_for(model) if config.get(
            "replace_with_kernel_inject", True) else None
        # int8 quantized_weights composes with tp>1: ZeroShardingRules
        # derives the {q, scale} leaf specs from the dense kernel rule
        # (sharding.py _quantized_leaf_spec — the reference's post-slice
        # GroupQuantizer geometry, replace_module.py:139)
        self.sharding_rules = ZeroShardingRules(
            self.topology, stage=0, tp_rules=rules)

        self._rng = jax.random.PRNGKey(seed)
        # imported weights stay HOST-side until _materialize device_puts
        # each leaf with its TP sharding: an eager jnp.asarray would land
        # the full unsharded model on one chip first (7B fp32 = 28 GB),
        # OOMing even when tp>1 would fit (same rule as the training
        # engine's _place_initial_params)
        self._params = None
        self._host_params = hf_params
        self._prefill_fn = None
        self._prefill_plan = None   # (granule, buckets_max) last planned
        self._decode_k_fn = None
        self._fwd_fn = None
        self._profile = bool(config.get("profile_model_time", False))
        self._model_times = []

        if config.get("checkpoint"):
            # params materialize directly from the checkpoint, sharded
            self._load_checkpoint(config["checkpoint"])

        log_dist(f"InferenceEngine: tp={tp_size}, ep={ep_size}, "
                 f"dtype={self.dtype}", ranks=[0])

    # ------------------------------------------------------------------
    def _compute_dtype(self):
        """The module's compute dtype (bf16 fallback) — the dtype in-graph
        dequant converts to and host placement casts non-quantized floating
        leaves to; one definition so the two cannot diverge."""
        return getattr(getattr(self.module, "config", None), "dtype",
                       None) or jnp.bfloat16

    def _cast(self, params):
        if self.dtype in (jnp.float16, jnp.bfloat16):
            return jax.tree.map(lambda x: x.astype(self.dtype)
                                if jnp.issubdtype(x.dtype, jnp.floating)
                                else x, params)
        if self.dtype == jnp.int8:
            if self._model_quantized:
                # the model stores its own {q, scale} layout (init/
                # conform already produced it) — nothing to do here
                return params
            # engine-level fallback for models WITHOUT the config flag:
            # same self-describing {q, scale} storage (reference
            # GroupQuantizer + int8 GEMM path, replace_module.py:139,
            # pt_binding.cpp:1535), dequantized in _dequant at the apply
            # call sites. Caveat vs the model-level path: for scanned
            # models the dequant sits OUTSIDE the layer scan, so the
            # stacked bf16 copy materializes per step — functional, not
            # the bandwidth win.
            from deepspeed_tpu.models.transformer_lm import \
                quantize_block_params

            self._engine_quantized = True
            return quantize_block_params(params)
        return params

    def _dequant(self, params):
        """Trace-level inverse of the engine-level int8 cast (identity for
        model-level quantized_weights, where the layer scan dequantizes)."""
        if not getattr(self, "_engine_quantized", False):
            return params
        from deepspeed_tpu.models.transformer_lm import \
            dequantize_block_params

        return dequantize_block_params(params, self._compute_dtype())

    def _materialize(self, input_ids):
        model = self.module
        rng = self._rng

        # quantized models cannot run init through their map_variables
        # transform (see _maybe_quantized_block) — initialize a DENSE twin
        # and convert its tree to the {q, scale} storage structure
        init_model = model
        if self._model_quantized:
            import dataclasses as _dc

            init_model = model.clone(config=_dc.replace(
                model.config, quantized_weights=False))

        def init_fn(r):
            return init_model.init({"params": r}, input_ids,
                                   deterministic=True)["params"]

        shapes = jax.eval_shape(init_fn, rng)
        if self._model_quantized:
            from deepspeed_tpu.models.transformer_lm import \
                quantize_block_params

            shapes = jax.eval_shape(quantize_block_params, shapes)
        if self._model_quantized and self._host_params is not None:
            # imported weights are dense; conform them HOST-side to the
            # model's {q, scale} storage structure before placement (an
            # on-device quantize would land each full-precision leaf on
            # one chip first — the exact OOM placement exists to avoid)
            self._host_params = _conform_host_quantized(
                self._host_params, shapes)
        self._param_shardings = self.sharding_rules.param_sharding_tree(shapes)
        if self._host_params is not None:
            # each device receives only its shard; half-precision cast
            # happens on HOST so full-precision leaves never transit
            cast = self.dtype if self.dtype in (jnp.float16, jnp.bfloat16) \
                else None
            if self.dtype == jnp.int8:
                # non-quantized floating leaves (embeddings, norms) serve
                # at the module's compute dtype — imported fp32 would
                # double their HBM footprint/traffic. Scales pre-cast too:
                # dequant casts them to the same dtype in-graph, so the
                # quantized math is unchanged.
                cast = self._compute_dtype()

            def place(leaf, shape_dtype, sharding):
                arr = np.asarray(leaf)
                # jnp.issubdtype: ml_dtypes bfloat16 is NOT np.floating
                if cast is not None and jnp.issubdtype(
                        arr.dtype, jnp.floating):
                    arr = arr.astype(cast)
                if arr.shape != shape_dtype.shape:
                    raise ValueError(
                        f"loaded leaf shape {arr.shape} != model shape "
                        f"{shape_dtype.shape}")
                return jax.device_put(arr, sharding)

            self._params = jax.tree.map(
                place, self._host_params, shapes, self._param_shardings)
            self._host_params = None  # free the host copy
            if self.dtype == jnp.int8:
                self._params = self._cast(self._params)
        else:
            # no imported/loaded weights: random init, sharded at creation
            if self._model_quantized:
                from deepspeed_tpu.models.transformer_lm import \
                    quantize_block_params

                # ONE jit: the dense init tree is an internal value XLA
                # frees layer-by-layer, never a materialized output
                # (dense-plus-int8 peak would be the OOM pattern the
                # host-placement path above exists to avoid)
                self._params = jax.jit(
                    lambda r: quantize_block_params(init_fn(r)),
                    out_shardings=self._param_shardings)(rng)
            elif self.dtype in (jnp.float16, jnp.bfloat16) and any(
                    sd.dtype != self.dtype
                    and jnp.issubdtype(sd.dtype, jnp.floating)
                    for sd in jax.tree.leaves(shapes)):
                # a model whose param_dtype is wider than the serving
                # dtype: cast inside the one jit, so that the weights are
                # born in the serving dtype and the wide tree is an
                # internal value XLA frees leaf by leaf (5 B parameters
                # are 21 GB in float32 and 10.5 in bf16)
                self._params = jax.jit(
                    lambda r: self._cast(init_fn(r)),
                    out_shardings=self._param_shardings)(rng)
            else:
                self._params = jax.jit(
                    init_fn, out_shardings=self._param_shardings)(rng)
                self._params = self._cast(self._params)

    # ------------------------------------------------------------------
    def _place_batch(self, arr):
        """Shard a [B, ...] serving batch over the mesh's data axes
        (dp x ep) when B divides them — the inference analogue of the
        training engine's _put_batch. For MoE models this is what makes
        expert parallelism real: tokens live batch-sharded, so the MoE
        dispatch/combine constraints become all-to-alls instead of local
        slicing over a replicated copy. Indivisible batches (e.g. batch-1
        latency serving) stay replicated."""
        bs = int(np.prod([self.topology.size(a)
                          for a in ("dp", "fsdp", "ep")]))
        if bs > 1 and arr.shape[0] % bs == 0:
            return jax.device_put(arr, self.topology.batch_sharding())
        return arr

    def forward(self, input_ids, **kwargs):
        """Full forward returning logits (jit-compiled once — the CUDA-graph
        analogue)."""
        # model modules read the ambient topology at trace time (VocabEmbed
        # one-hot vs gather) — re-assert before any lazy compile
        set_default_topology(self.topology)
        input_ids = self._place_batch(jnp.asarray(input_ids))
        if self._params is None or not hasattr(self, "_param_shardings"):
            self._materialize(input_ids)
        if self._fwd_fn is None:
            model = self.module

            def f(params, ids):
                return model.apply({"params": self._dequant(params)}, ids,
                                   deterministic=True)

            self._fwd_fn = jax.jit(f)
        t0 = time.time()
        out = self._fwd_fn(self._params, input_ids)
        if self._profile:
            jax.block_until_ready(out)
            self._model_times.append(time.time() - t0)
        return out

    __call__ = forward

    def model_times(self):
        times = self._model_times
        self._model_times = []
        return times

    # ------------------------------------------------------------------
    # generation (prefill + greedy/sampled decode over the KV cache)
    # ------------------------------------------------------------------
    def _build_decode_fns(self):
        """Compiled once per input shape (jit's shape cache). The cache is
        donated, and stays one buffer per leaf through the scan over ``k``
        and the model's layer loop (models/transformer_lm.py
        ScannedBlocks): a decode step writes its new rows into it and
        produces no other whole leaf."""
        model = self.module

        def prefill(params, ids, mask):
            # cache variables are created on first mutable apply; the whole
            # prompt is written into the KV cache in one pass
            logits, vars_out = model.apply(
                {"params": self._dequant(params)}, ids, attention_mask=mask,
                deterministic=True, decode=True, mutable=["cache"])
            return logits[:, -1], vars_out["cache"]

        def prefill_more(params, ids, mask, cache):
            # continuation pass of a chunked prefill: the cache already
            # exists, this span's tokens append at the rows' cache_index
            logits, vars_out = model.apply(
                {"params": self._dequant(params), "cache": cache}, ids,
                attention_mask=mask, deterministic=True, decode=True,
                mutable=["cache"])
            return logits[:, -1], vars_out["cache"]

        def one_token(params, token, cache, rng, temperature):
            # dequant HERE, inside the decode scan body: the int8->compute
            # convert fuses into the dots, so the per-token weight traffic
            # stays int8 on the wire
            logits, vars_out = model.apply(
                {"params": self._dequant(params), "cache": cache},
                token[:, None],
                deterministic=True, decode=True, mutable=["cache"])
            logits = logits[:, -1]

            def sample(r):
                return jax.random.categorical(r, logits / temperature, axis=-1)

            def greedy(_):
                return jnp.argmax(logits, axis=-1)

            with jax.named_scope(SCOPE_SAMPLE):
                next_tok = jax.lax.cond(
                    temperature > 0, sample, greedy, rng)
            return next_tok.astype(jnp.int32), vars_out["cache"]

        def decode_k(params, token, cache, rng, temperature, k):
            """k tokens in ONE compiled program (lax.scan over the step).

            A Python token loop pays a dispatch round-trip per token —
            pure overhead at small batch; the reference amortizes it with
            CUDA-graph replay (inference/engine.py:523), the jit analogue
            of which is this scan. The rng chain (split per step) matches
            the per-token loop exactly, so sampled output is identical for
            a given starting key.
            """

            def body(carry, _):
                tok, cache, rng = carry
                rng, sub = jax.random.split(rng)
                nxt, cache = one_token(params, tok, cache, sub, temperature)
                return (nxt, cache, rng), nxt

            (tok, cache, rng), toks = jax.lax.scan(
                body, (token, cache, rng), None, length=k)
            # toks: [k, B] -> [B, k]
            return toks.swapaxes(0, 1), tok, cache, rng

        def verify_greedy(params, toks, cache):
            """Speculative-decode verification: ONE batched forward over
            ``[B, k+1]`` columns ``[t0, d1..dk]``. Column ``j``'s logits
            condition on ``t0..d_j`` exactly as sequential decode would, so
            ``argmax`` per column IS the greedy token after accepting ``j``
            drafts — acceptance is a host-side prefix match, and the
            scheduler rewinds the cache clocks past the first mismatch
            (inference/lane_cache.py ``LaneLayout.rewind``)."""
            logits, vars_out = model.apply(
                {"params": self._dequant(params), "cache": cache}, toks,
                deterministic=True, decode=True, mutable=["cache"])
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), \
                vars_out["cache"]

        # plan_prefill keeps this one, or calls one export of it
        self._prefill_per_bucket = jax.jit(prefill)
        # each remembers the avals of the first dispatch per prompt bucket
        # (ids' shape) or scan length, for program_scopes()
        self._prefill_fn = DispatchedProgram(
            self._prefill_per_bucket, key=lambda a: a[1].shape)
        self._prefill_more_fn = DispatchedProgram(
            jax.jit(prefill_more, donate_argnums=(3,)),
            key=lambda a: a[1].shape)
        self._decode_k_fn = DispatchedProgram(
            jax.jit(decode_k, static_argnums=(5,), donate_argnums=(2,)),
            key=lambda a: (a[1].shape, a[5]))
        self._verify_greedy_fn = DispatchedProgram(
            jax.jit(verify_greedy, donate_argnums=(2,)),
            key=lambda a: a[1].shape)

    def plan_prefill(self, granule: int, positions: int):
        """How the ``[1, T]`` admission prefill is built for a scheduler
        that pads prompts to multiples of ``granule`` tokens over lanes of
        ``positions``: traced ONCE for all its buckets (``traced_once``)
        where the model's declaration says that the token count decides
        nothing but shapes (``GPTConfig.prefill_bucket_dependence``) and
        the parameters are whole on one device, else a bucket at a time as
        ``jax.jit(prefill)`` always has. Decided from what the engine holds, by no option and by
        no trial: a tracing that fails costs seconds of the set-up of
        exactly the models that gain nothing. Publishes
        ``serve.prefill_plan`` when it decides (once for an engine under
        one scheduler)."""
        from deepspeed_tpu.models.transformer_lm import GPTConfig
        from deepspeed_tpu.telemetry.bus import (
            KIND_SERVE_PREFILL_PLAN,
            publish,
        )

        buckets_max = positions // granule
        if self._prefill_plan == (granule, buckets_max):
            return
        self._prefill_plan = (granule, buckets_max)
        mcfg = getattr(self.module, "config", None)
        # replicas (dp) hold the parameters whole
        split = [a for a, n in self.topology.axis_sizes.items()
                 if n > 1 and a != "dp"]
        if not isinstance(mcfg, GPTConfig):
            why = "the module declares no GPTConfig to ask"
        elif split:
            why = ("the parameters are split over the mesh's "
                   f"{', '.join(split)}: the one tracing is a single "
                   "device's program")
        elif buckets_max < 2:
            why = "one bucket fills a lane: nothing to share"
        else:
            why = mcfg.prefill_bucket_dependence
        per_bucket = self._prefill_per_bucket
        self._prefill_fn.fn, self._prefill_fn.before_first = \
            (per_bucket, None) if why \
            else traced_once(per_bucket, granule, buckets_max)
        publish(KIND_SERVE_PREFILL_PLAN,
                traced="per_bucket" if why else "once",
                why=why or "the token count decides shapes alone",
                granule=granule, buckets_max=buckets_max)

    def step_programs(self):
        """The serving programs built so far (``DispatchedProgram``s)."""
        if self._prefill_fn is None:
            return []
        return [self._prefill_fn, self._prefill_more_fn, self._decode_k_fn,
                self._verify_greedy_fn]

    def program_scopes(self) -> Dict[str, Dict[str, Optional[str]]]:
        """``{program_name: {hlo_instruction_name: op_name_path}}`` of the
        prefill and decode programs this engine has dispatched, one entry
        per program name over all the prompt buckets it ran
        (telemetry/scopes.py). Operations that no named scope owns and
        whose result is a whole KV-cache leaf are tagged
        ``kv_cache_carry`` (none, while the cache crosses the layer loop
        in place). Re-lowers (a cache hit) and parses HLO text:
        call it after the measured window, never inside it."""
        from deepspeed_tpu.models.transformer_lm import declared_cache_leaves

        return programs_scope_table(
            self.step_programs(),
            declared_cache_leaves(getattr(self.module, "config", None)))

    def program_builds(self, before: Optional[float] = None):
        """What this process built so far, by JAX's own account
        (telemetry/builds.py ``BuildLog.snapshot``): a row per stage of
        every program, those of this engine's prefill and decode programs
        with their prompt bucket or scan length (``key``), each such first
        call's wall time (``dispatches``), and per stage the seconds of
        the union of the rows; ``before`` keeps what ended by that
        ``time.monotonic()``. The log is the process's, not this
        engine's."""
        return build_log.snapshot(before)

    def _chunked_prefill(self, input_ids, attention_mask):
        """Prefill ``input_ids`` exactly: one pass when that is exact,
        block-aligned ``<= ring_len``-token passes for prompts longer than
        the ring (prefill_chunk_spans has the derivation). Returns
        (last-token logits, cache); with LEFT-aligned prompts the final
        span's last column is the last real token of every row."""
        mcfg = getattr(self.module, "config", None)
        spans = prefill_chunk_spans(mcfg, int(input_ids.shape[1]))
        if spans is None:
            return self._prefill_fn(self._params, input_ids, attention_mask)
        s0, e0 = spans[0]
        logits_last, cache = self._prefill_fn(
            self._params, input_ids[:, s0:e0], attention_mask[:, s0:e0])
        for s, e in spans[1:]:
            logits_last, cache = self._prefill_more_fn(
                self._params, input_ids[:, s:e], attention_mask[:, s:e],
                cache)
        return logits_last, cache

    def generate(self, input_ids, max_new_tokens: int = 32,
                 temperature: float = 0.0, attention_mask=None):
        """Greedy (temperature=0) or sampled generation.

        Ragged batches: pass ``attention_mask`` (1 = real token). Prompts
        are LEFT-aligned internally (pads moved to the front) so valid
        tokens stay physically contiguous in the KV cache — the masked
        decode then matches per-sequence generation exactly (reference
        inference_context.h masked decode; the padding-mask-aware cache
        lives in models/transformer_lm.py's decode attention).
        """
        set_default_topology(self.topology)
        mcfg = getattr(self.module, "config", None)
        # ONE ring decision for this call: drives both the dense-decode
        # divergence warning and the streaming cap below (shared helper —
        # the model's decode branch consults the same one)
        from deepspeed_tpu.ops.sparse_attention.sparse_attention_utils \
            import ring_engaged

        ring = ring_engaged(mcfg) if mcfg is not None else None
        if getattr(mcfg, "sparse_attention", None) is not None:
            # window(+leading-global) layouts decode through the ring KV
            # cache — the training sparse math exactly (transformer_lm
            # sparse_kv_cache); only layouts a ring cannot express (e.g.
            # BigBird's random links) fall back to dense decode, which
            # sees strictly MORE keys than training did — close, not
            # identical math (docs/DIVERGENCES.md Inference section)
            if ring is None:
                from deepspeed_tpu.utils.logging import warning_once

                warning_once(
                    "generate() on a sparse_attention-configured model: "
                    "this layout decodes with DENSE attention (training "
                    "was block-sparse); window/longformer layouts decode "
                    "sparse-exactly via the ring KV cache — including "
                    "prompts longer than the ring, which prefill in "
                    "block-aligned chunks — see docs/DIVERGENCES.md")
        input_ids = jnp.asarray(input_ids)
        if attention_mask is not None:
            ids_np = np.asarray(input_ids)
            m_np = np.asarray(attention_mask).astype(bool)
            if m_np.shape != ids_np.shape:
                raise ValueError(
                    f"attention_mask shape {m_np.shape} != input_ids "
                    f"shape {ids_np.shape}")
            if not m_np.any(axis=1).all():
                empty = np.where(~m_np.any(axis=1))[0].tolist()
                raise ValueError(
                    f"attention_mask rows {empty} have no valid tokens; "
                    "an empty prompt cannot seed generation")
            T = ids_np.shape[1]
            out_ids = np.zeros_like(ids_np)
            out_m = np.zeros_like(m_np)
            for b in range(ids_np.shape[0]):
                vtok = ids_np[b][m_np[b]]
                out_ids[b, T - len(vtok):] = vtok
                out_m[b, T - len(vtok):] = True
            input_ids = jnp.asarray(out_ids)
            attention_mask = jnp.asarray(out_m)
        if max_new_tokens < 0:
            raise ValueError(f"max_new_tokens must be >= 0, got {max_new_tokens}")
        if max_new_tokens == 0:
            return jnp.zeros((input_ids.shape[0], 0), jnp.int32)
        max_pos = getattr(mcfg, "n_positions", None)
        if max_pos is not None and input_ids.shape[1] + max_new_tokens > max_pos:
            # streaming decode: a ring-cached model with no learned
            # position table (rotary/ALiBi-free-running positions) has
            # nothing that saturates at n_positions — the ring evicts old
            # window blocks and globals persist (the attention-sink
            # pattern), so generation length is unbounded at O(window)
            # memory. Models with a wpe table keep the hard cap.
            streaming = (ring is not None
                         and not getattr(mcfg, "learned_positions", True))
            if not streaming:
                raise ValueError(
                    f"prompt ({input_ids.shape[1]}) + max_new_tokens "
                    f"({max_new_tokens}) exceeds the KV cache capacity "
                    f"(n_positions={max_pos})")
        if self._params is None or not hasattr(self, "_param_shardings"):
            self._materialize(input_ids)
        if self._prefill_fn is None:
            self._build_decode_fns()
        self._rng, rng = jax.random.split(self._rng)

        if attention_mask is None:
            attention_mask = jnp.ones(input_ids.shape, jnp.bool_)
        input_ids = self._place_batch(input_ids)
        attention_mask = self._place_batch(attention_mask)
        logits_last, cache = self._chunked_prefill(input_ids,
                                                   attention_mask)
        rng, sub = jax.random.split(rng)
        with jax.named_scope(SCOPE_SAMPLE):
            if temperature > 0:
                tok = jax.random.categorical(
                    sub, logits_last / temperature,
                    axis=-1).astype(jnp.int32)
            else:
                tok = jnp.argmax(logits_last, axis=-1).astype(jnp.int32)
        out = [tok[:, None]]
        temp = jnp.float32(temperature)
        # chunked scan decode, binary-decomposed: each dispatch runs the
        # largest power-of-two scan <= min(chunk, remaining), so ANY
        # max_new_tokens is served by at most log2(chunk) distinct compiled
        # scan lengths (cached across calls — no per-length recompile) and
        # never by per-token dispatches (each costs a host->device
        # round-trip the scan amortizes). chunk defaults to 32: the
        # plateau of a scan-length sweep last measured before PR 1
        # through a chip access that no longer exists; not measured on
        # the current machine.
        chunk = max(1, int(self._config.get("decode_chunk", 32)))
        eff = 1 << (chunk.bit_length() - 1)
        if eff != chunk:
            from deepspeed_tpu.utils.logging import warning_once

            # each dispatch runs the largest power-of-two scan <= chunk
            # (binary tail decomposition bounds the compile cache); say so
            # once instead of silently flooring a configured 24 to 16
            warning_once(
                f"decode_chunk={chunk} is not a power of two; dispatches "
                f"use {eff}-token scans (plus a binary-decomposed tail)")
        remaining = max_new_tokens - 1
        while remaining > 0:
            k = min(chunk, remaining)
            k = 1 << (k.bit_length() - 1)  # largest power of two <= k
            toks, tok, cache, rng = self._decode_k_fn(
                self._params, tok, cache, rng, temp, k)
            out.append(toks)
            remaining -= k
        return jnp.concatenate(out, axis=1)

    # ------------------------------------------------------------------
    def _load_checkpoint(self, path: str):
        """Load a msgpack state dict saved by the training engine
        (save_checkpoint model states or save_16bit_model); resharding onto
        the inference mesh happens at materialization (reference
        state_dict_factory MP resharding, state_dict_factory.py:20)."""
        state = MsgpackCheckpointEngine().load(path)
        module = state.get("module", state)
        # HOST-side arrays; placed per-shard at _materialize (see __init__)
        self._host_params = serialization.msgpack_restore(
            serialization.msgpack_serialize(module)) if not isinstance(
                module, dict) else module
        self._params = None

    @property
    def params(self):
        return self._params
