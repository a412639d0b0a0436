"""Grouped-query attention whose window, rotary and cache are its layer's
KIND (``GPTConfig.attention_kind``): the attention of a stack in which
layers that see the newest ``sliding_window`` positions, with rotary, stand
beside layers that see every position, with none (AFMoE / Trinity:
``sliding_attention`` and ``full_attention``). ``Block`` puts it where
``CausalSelfAttention`` stands in a stack of one kind; the parameters keep
that module's names (``c_attn``: q, k and v side by side; ``q_norm``,
``k_norm``; ``c_proj``) beside ``c_gate``, the projection whose sigmoid
gates the attention output (``GPTConfig.attn_output_gate``).

The window is exact by position, on every path: a query at position ``i``
sees key ``j`` where ``0 <= i - j < window``.

What a lane keeps (models/kind_stacks.py stacks it over the kind's layers):

* a layer that sees everything: ``cached_key`` / ``cached_value`` ``[B,
  n_positions, Hkv, D]``, row ``p`` holding position ``p``;
* a window layer: the same leaves with ``AttentionKind.ring`` rows (the
  window and ``window_slack`` more) whatever ``n_positions`` is, position
  ``p`` at row ``p % ring``, and ``slot_pos [B, ring]``: the position each
  row holds, -1 for none. Visibility is read from ``slot_pos``, never from
  a row's index, so the slack changes no value: it is the room that lets
  one pass of up to ``window_slack + 1`` tokens write all its rows before
  any of its queries reads (``GPTConfig.pass_tokens``).

One query token a lane (a decode step) goes through the block-skipping
kernel ``decode_attn`` (ops/pallas/decode_attention.py) for both kinds, out
of the stacked leaf where it lies: a window layer hands it ``valid &`` what
its window holds, as ops/indexed_attention.py hands ``valid & chosen``, and
the kernel reads the ring's blocks. More tokens on a cache (the passes of a
prefill) take two einsums a KV head, one head after another, so that a pass
of ``T`` tokens holds ``[heads / kv_heads, T, rows]`` scores at a time. A
call without a cache (training) runs the flash kernels under the kind's
window where :func:`flash_takes` says the shapes allow
(ops/pallas/flash_attention.py: no ``[T, T]`` array anywhere, tiles outside
a window layer's band not computed, K and V read at their own head count),
and the same two einsums a KV head otherwise.

What a kind declares HERE is its window, its ring and whether q and k are
rotated; heads, head size and ``rope_theta`` are the model's one set. A
kind that declares its own mixer (``AttentionKind.latent``: heads, ranks,
widths, ``rope_theta``, an indexer, a gate a head) is latent attention and
is run by models/latent_attention.py ``KindLatentAttention``; ``Block``
chooses between the two from the kind. How a kind's rows are kept, written
and read back is ONE thing for both (:class:`KindCache`), whatever a row
is: keys and values per head here, a latent and a rotary key there.
"""
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.telemetry.scopes import (
    SCOPE_KV_CACHE_READ,
    SCOPE_KV_CACHE_WRITE,
)


def attend_by_kv_head(qg, k, v, visible):
    """Softmax attention of ``qg [B, T, Hkv, G, D]`` over ``k`` / ``v``
    ``[B, S, Hkv, D]`` under ``visible [B, T, S]``, float32 softmax, one KV
    head at a time (``lax.map``). A query that sees nothing gets zeros.
    Returns ``[B, T, Hkv, G, D]`` in ``qg``'s dtype."""
    scale = 1.0 / np.sqrt(qg.shape[-1])
    seen = visible[:, None]                                 # [B, 1, T, S]

    def one(head):
        qh, kh, vh = head           # [B, T, G, D], [B, S, D], [B, S, D]
        att = jnp.einsum("bqgd,bkd->bgqk", qh, kh) * scale
        att = jnp.where(seen, att, jnp.finfo(att.dtype).min)
        att = jax.nn.softmax(att.astype(jnp.float32), axis=-1,
                             where=seen).astype(qg.dtype)
        return jnp.einsum("bgqk,bkd->bqgd", att, vh)

    out = jax.lax.map(one, (jnp.moveaxis(qg, 2, 0), jnp.moveaxis(k, 2, 0),
                            jnp.moveaxis(v, 2, 0)))
    return jnp.moveaxis(out, 0, 2)


def flash_takes(cfg, kind, T: int, mask) -> bool:
    """Whether a call without a cache runs the flash kernels
    (ops/pallas/flash_attention.py, under the kind's window), chosen as
    ``CausalSelfAttention`` chooses: ``use_flash_attention`` True, or
    "auto" from the measured crossover up (no ceiling here: the kernels
    compile at 16,384 positions with and without a window, and two einsums
    a KV head hold ``[heads / kv_heads, T, T]`` scores); no padding mask;
    whole lane tiles of positions, and of the window. Everything else keeps
    the einsum form."""
    from deepspeed_tpu.models.transformer_lm import FLASH_AUTO_MIN_SEQ

    want = T >= FLASH_AUTO_MIN_SEQ if cfg.use_flash_attention == "auto" \
        else cfg.use_flash_attention
    return bool(want and mask is None and T % 128 == 0
                and (kind.window is None or kind.window % 128 == 0))


class KindCache:
    """What ONE KIND of layer keeps of each position and how a call on a
    cache writes and reads it, whatever a row is. The leaves are ``[B,
    rows, ...]`` beside ``valid``, ``cache_index`` (the clock a layer) and,
    of a ring, ``slot_pos``; ``rows`` is ``AttentionKind.ring`` or
    ``n_positions``; position ``p`` is written at row ``p`` (``p % ring``);
    a query at ``p`` sees the rows that hold a position in ``(p - window,
    p]``, read from ``slot_pos`` and never from a row's index. Under
    ``KindStackedBlocks`` the leaves are the kind's stacked ``[layers of
    the kind, B, rows, ...]`` buffers and this call is layer
    ``cache_layer`` of them (as ``CausalSelfAttention`` under
    ``ScannedBlocks``): rows are written in place, a slice is only read.
    Whoever runs the layers makes the kind's stack before the first pass,
    so a pass cannot tell a new ring from a full one."""

    def __init__(self, module, kind, B, T, rows, cache_layer, step=None):
        """``rows``: ``{leaf: (the shape after [B, rows], dtype)}`` of what
        a position keeps; ``step``: ``{leaf: (the shape after [B], fill,
        dtype)}`` of what a call leaves of itself beside them."""
        cfg = module.config
        self.kind, self.layer, self.T = kind, cache_layer, T
        self.rows = S = kind.ring or cfg.n_positions
        spec = {name: ((B, S) + tuple(tail), 0, dtype)
                for name, (tail, dtype) in rows.items()}
        spec["valid"] = ((B, S), False, jnp.bool_)
        if kind.ring is not None:
            spec["slot_pos"] = ((B, S), -1, jnp.int32)      # nothing cached
        spec["cache_index"] = ((B,), 0, jnp.int32)
        for name, (tail, fill, dtype) in (step or {}).items():
            spec[name] = ((B,) + tuple(tail), fill, dtype)
        self.leaves = {
            name: module.variable("cache", name, jnp.full, *leaf_spec)
            for name, leaf_spec in spec.items()}
        if kind.ring is not None and T > S - kind.window + 1:
            raise ValueError(
                f"a pass of {T} tokens over a window layer's ring of {S} "
                f"rows (window {kind.window}) would overwrite rows that "
                "its own queries still attend over: prefill in passes of "
                "GPTConfig.pass_tokens (inference/engine.py "
                "prefill_chunk_spans)")
        self.clock = self.leaf("cache_index")                   # [B]
        self.pos = self.clock[:, None] + jnp.arange(T)[None, :]  # [B, T]

    def stacked(self, name):
        """The leaf as it lies (a kernel reads its blocks out of it)."""
        return self.leaves[name].value

    def leaf(self, name):
        value = self.leaves[name].value
        return value if self.layer is None else \
            jax.lax.dynamic_index_in_dim(value, self.layer, 0,
                                         keepdims=False)

    def put(self, name, index, val):
        if self.layer is not None:
            index = (self.layer,) + index
        self.leaves[name].value = self.leaves[name].value.at[index].set(
            val, mode="drop")

    def write(self, new, written):
        """The pass's tokens into their rows (``new``: ``{leaf: [B, T,
        ...]}``; ``written [B, T]``: which of them hold a token), and the
        clock on by the pass."""
        lanes = jnp.arange(written.shape[0])[:, None]
        slots = self.pos if self.kind.ring is None else self.pos % self.rows
        with jax.named_scope(SCOPE_KV_CACHE_WRITE):
            new = dict(new, valid=written)
            if self.kind.ring is not None:
                new["slot_pos"] = self.pos
            for name, val in new.items():
                self.put(name, (lanes, slots), val)
            self.put("cache_index", (Ellipsis,), self.clock + self.T)

    def visible(self):
        """``[B, T, rows]``: which rows each of the pass's queries sees."""
        pos, kind = self.pos, self.kind
        with jax.named_scope(SCOPE_KV_CACHE_READ):
            if kind.ring is None:
                held = jnp.arange(self.rows)[None, None, :] \
                    <= pos[:, :, None]
            else:
                at = self.leaf("slot_pos")[:, None, :]
                held = (at >= 0) & (at <= pos[:, :, None]) \
                    & (at > pos[:, :, None] - kind.window)
            return held & self.leaf("valid")[:, None, :]


class KindAttention(nn.Module):
    config: "GPTConfig"  # noqa: F821  (models/transformer_lm.py)
    kind: "AttentionKind"  # noqa: F821

    @nn.compact
    def __call__(self, x, *, mask=None, segment_ids=None, decode=False,
                 cache_layer=None):
        from deepspeed_tpu.models.transformer_lm import step_kernel

        cfg, kind = self.config, self.kind
        B, T, C = x.shape
        H, Hkv, D = cfg.n_head, cfg.kv_heads, cfg.head_dim
        if segment_ids is not None:
            raise NotImplementedError(
                "packed-sequence segment_ids with attention layers that "
                "differ by kind: a window would run across documents")

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                            param_dtype=cfg.param_dtype, name=name)

        qkv = dense((H + 2 * Hkv) * D, "c_attn")(x)
        q = qkv[..., :H * D].reshape(B, T, H, D)
        k = qkv[..., H * D:(H + Hkv) * D].reshape(B, T, Hkv, D)
        v = qkv[..., (H + Hkv) * D:].reshape(B, T, Hkv, D)
        if cfg.qk_norm:             # "head": over each head's D
            q, k = (nn.RMSNorm(epsilon=cfg.layer_norm_epsilon,
                               dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                               name=name)(t)
                    for t, name in ((q, "q_norm"), (k, "k_norm")))

        def rope(t, positions):
            if not kind.rotary:
                return t
            from deepspeed_tpu.ops.rotary import apply_rotary_pos_emb

            return apply_rotary_pos_emb(
                t, positions, base=cfg.rope_theta, rotary_dim=cfg.rotary_dim,
                interleaved=cfg.rotary_interleaved)

        def close(y):
            """The heads' outputs side by side, under the output's gate."""
            y = y.reshape(B, T, H * D)
            if cfg.attn_output_gate:
                y = y * jax.nn.sigmoid(gate)
            return y

        gate = dense(H * D, "c_gate")(x) if cfg.attn_output_gate else None
        written = (jnp.ones((B, T), jnp.bool_) if mask is None
                   else mask.astype(jnp.bool_))

        if not decode:
            # no cache: the tokens at hand, position t at row t
            pos = jnp.arange(T)[None, :]
            q, k = rope(q, pos), rope(k, pos)
            if flash_takes(cfg, kind, T, mask):
                # no [T, T] scores anywhere: the kernels under the kind's
                # window, K and V read at their own head count
                from deepspeed_tpu.models.transformer_lm import \
                    _mesh_flash_attention

                with jax.named_scope(kind.scope):
                    y = close(_mesh_flash_attention(
                        q, k, v, None, causal=True, window=kind.window))
                return dense(C, "c_proj")(y)
            ahead = pos[0][:, None] - pos[0][None, :]        # i - j
            visible = (ahead >= 0) if kind.window is None \
                else (ahead >= 0) & (ahead < kind.window)
            with jax.named_scope(kind.scope):
                y = close(attend_by_kv_head(
                    q.reshape(B, T, Hkv, H // Hkv, D), k, v,
                    visible[None] & written[:, None, :]))
            return dense(C, "c_proj")(y)

        cache = KindCache(
            self, kind, B, T, dict.fromkeys(
                ("cached_key", "cached_value"), ((Hkv, D), cfg.dtype)),
            cache_layer)
        S, idx = cache.rows, cache.clock
        q, k = rope(q, cache.pos), rope(k, cache.pos)
        cache.write({"cached_key": k.astype(cfg.dtype),
                     "cached_value": v.astype(cfg.dtype)}, written)
        visible = cache.visible()
        if T == 1 and step_kernel():
            from deepspeed_tpu.ops.pallas.decode_attention import (
                block_positions,
                decode_attention,
            )

            # the rows between a lane's first visible one and its clock, in
            # blocks; a ring past its first turn is read whole (the kernel
            # holds the clock to the last row)
            with jax.named_scope(kind.scope):
                y = close(decode_attention(
                    q[:, 0], cache.stacked("cached_key"),
                    cache.stacked("cached_value"), visible[:, 0], idx,
                    cache_layer, block=block_positions(
                        S, Hkv, D, jnp.dtype(cfg.dtype).itemsize)))
            return dense(C, "c_proj")(y)
        with jax.named_scope(SCOPE_KV_CACHE_READ):
            k_all, v_all = (cache.leaf("cached_key"),
                            cache.leaf("cached_value"))
        with jax.named_scope(kind.scope):
            y = close(attend_by_kv_head(
                q.reshape(B, T, Hkv, H // Hkv, D), k_all, v_all, visible))
        return dense(C, "c_proj")(y)
