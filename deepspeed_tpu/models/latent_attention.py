"""Multi-head latent attention (MLA) in place of attention as a block's
token mixer, as DeepSeek-V2 has it (arXiv:2405.04434, section 2.1;
``GPTConfig.mla``, a ``MLAConfig``). With ``x`` the block's normalised
input:

    c_q = RMSNorm(x W_qa)            q = c_q W_qb -> H x [q_nope | q_rope]
    [c | r] = x W_kva                c_kv = RMSNorm(c)     k_rope = RoPE(r)
    [k_nope_h | v_h] = c_kv W_kvb    (per head h)
    score_h(t, s) = (q_nope_h(t) . k_nope_h(s)
                     + RoPE(q_rope_h(t)) . k_rope(s)) * scale
    o_h = sum_s softmax_s(score_h)(t, s) v_h(s)      y = concat_h(o_h) W_o

``k_rope`` is ONE vector a token, shared by all heads; rotary is
rotate-half over the rotary part's halves with YaRN's frequencies
(ops/rotary.py); the softmax is float32.

One layer, two forms of the same sum, chosen from what the call shows and
by no option:

* **per head** (training, and the pass that CREATES a lane's cache, a
  prefill): ``k_nope`` and ``v`` of the tokens at hand are decompressed
  and attended per head, causally;
* **absorbed** (a decode call on a cache that exists, any number of query
  tokens): ``W_kvb`` is split per head into ``W_UK_h`` and ``W_UV_h``
  ``[kv_rank, d]`` and re-associated into the query and the output,

      q_lat_h = q_nope_h W_UK_h^T
      score_h(t, s) = (q_lat_h(t) . c_kv(s) + q_rope_h(t) . k_rope(s)) * scale
      o_h = (sum_s p_h(t, s) c_kv(s)) W_UV_h

  so that H query heads attend over ONE ``kv_rank + rope_dim``-wide key
  and one ``kv_rank``-wide value a position: the cache is read as it lies
  and never decompressed (decompressing costs ``2 kv_rank H (nope_dim +
  v_dim)`` operations a cached position and layer at every step).

  The sum between the two products with ``W_kvb`` has two routes, again
  told from the call (``models/transformer_lm.py``
  ``decode_attention_block``). ONE query token a lane: the Pallas kernel
  ``mla_decode_attn`` (ops/pallas/latent_decode_attention.py), which
  reads of each lane only the position blocks between its first valid
  row and its clock, each once (the block fetched for the scores is the
  value too), out of the stacked leaves where they lie, with scores and
  softmax statistics in float32 on the chip; what its calls share, the
  grid and the mask, is made once a step and rides on the ``Lane``
  (``plan``). More query tokens at once (a continuation, verification)
  or heads sharded over ``tp``: two einsums over every position of a
  layer's slice, float32 scores and softmax through memory.

The cache. A lane holds ``c_kv`` (after its norm) and ``k_rope`` (after
rotary) per position and layer and nothing per head: ``cached_latent``
``[layers, B, S, kv_rank]`` and ``cached_rope_key`` ``[layers, B, S,
rope_dim]`` in the compute dtype, with ONE ``valid [B, S]`` and one clock
``cache_index [B]`` a lane (positions are the lane's, not a layer's). The
leaves belong to whoever runs the layers (``ScannedBlocks``, or ``GPT``
with ``scan_layers=False``: :func:`open_lane_cache`) and cross them as a
value that each layer updates in place at its own index, so the leading
dense blocks and the scanned stack write layers of the same leaves.
Left-padded prompts pass ``mask``; a pad's latent and rotary key are
written and never read (``valid``).

The parameters under ``ScannedBlocks``' scan. Four of the layer's five
matrices are read where they lie in the stacked leaves, by a fusion that
holds the scan's slice: ``q_a``, ``kv_a``, ``c_proj`` as plain 2-D
products, and ``q_b`` because its 2-D product is held as a value before
the per-head view is taken (the comment at the product says what the
compiler does otherwise). ``kv_b`` is read in place by the per-head form
(one 2-D product) and NOT by the absorbed form: its two einsums have the
head as a batch dimension between the matrix's two dimensions in memory,
and the TPU compiler writes the layer's slice out and a transposed copy
of it (2 x 33.5 MB a layer, 0.35 ms of a 15.5 ms decode step at
DeepSeek-V2's widths). A Pallas kernel over the leaf as stored removed
those copies and lost more than it won to the swap of lanes and heads
around the attention kernel (PERF.md, section 6, PR 55; ROADMAP S16).

**Latent attention as a KIND of layer** (``KindLatentAttention``, at the
end of this module). ``LatentAttention`` is the mixer of a stack of ONE
kind: heads, ranks, widths and ``rope_theta`` are the model's, the lane
cache belongs to whoever runs the layers. A stack whose attention layers
differ by kind (``GPTConfig.latent_kinds``, read back through
``GPTConfig.attention_kind``) runs the second module instead: what a kind
declares there is its OWN head count, its own ``MLAConfig`` (ranks and
widths), its own ``rope_theta``, a window with a ring of latents
(``sliding_window`` / ``window_slack``), an indexer whose queries come from
the query latent and whose chosen rows are latents, a gate a head, and the
rescale of both normed latents; its cache is the kind's own stack under
``KindStackedBlocks`` (``cached_latent`` / ``cached_rope_key`` of the
kind's width and length, ``slot_pos`` for a ring, ``cached_index_key`` and
the three "step" leaves for an indexer). ``ScannedBlocks`` and this module's
first class are untouched by it.
"""
from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax import struct

from deepspeed_tpu.telemetry.scopes import (
    SCOPE_KV_CACHE_READ,
    SCOPE_KV_CACHE_WRITE,
    SCOPE_MLA_ABSORB,
    SCOPE_MLA_ATTN,
    SCOPE_MLA_KV_PROJ,
    SCOPE_MLA_OUT_PROJ,
    SCOPE_MLA_Q_PROJ,
)

CACHED_LATENT = "cached_latent"
CACHED_ROPE_KEY = "cached_rope_key"


@struct.dataclass
class Lane:
    """A latent lane cache as the layers see it on one decode call."""
    latent: Any      # [layers, B, S, kv_rank], this call's rows written
    rope_key: Any    # [layers, B, S, rope_dim]  by each layer as it runs
    valid: Any       # [B, S] bool, this call's tokens included
    index: Any       # [B] each lane's clock BEFORE this call
    # static: whether this call made the cache (every row a lane holds is
    # among the tokens at hand) or found it
    fresh: bool = struct.field(pytree_node=False)
    # the decode kernel's grid and mask for this call (ops/pallas/
    # latent_decode_attention.py ``StepPlan``), the same for every layer;
    # None where the call keeps the einsums
    plan: Any = None


class LaneCache(NamedTuple):
    lane: Lane
    close: Any       # ``close(lane)``: the layers' writes back to the leaves


def open_lane_cache(module, cfg, B: int, T: int, mask) -> LaneCache:
    """Declare on ``module`` (inside its compact ``__call__``) the lane
    cache of a latent-attention model, mark this call's ``T`` tokens per
    lane valid where ``mask`` (or everywhere) says and advance the
    clocks."""
    m, S, L = cfg.mla, cfg.n_positions, cfg.n_layer
    fresh = not module.has_variable("cache", CACHED_LATENT)
    latent = module.variable("cache", CACHED_LATENT, jnp.zeros,
                             (L, B, S, m.kv_rank), cfg.dtype)
    rope_key = module.variable("cache", CACHED_ROPE_KEY, jnp.zeros,
                               (L, B, S, m.rope_dim), cfg.dtype)
    valid = module.variable("cache", "valid", jnp.zeros, (B, S), jnp.bool_)
    index = module.variable("cache", "cache_index", jnp.zeros, (B,),
                            jnp.int32)
    idx = index.value
    with jax.named_scope(SCOPE_KV_CACHE_WRITE):
        pos = idx[:, None] + jnp.arange(T)[None, :]
        now_valid = valid.value.at[jnp.arange(B)[:, None], pos].set(
            mask.astype(jnp.bool_) if mask is not None
            else jnp.ones((B, T), jnp.bool_), mode="drop")
        valid.value = now_valid
        index.value = idx + T

    def close(lane):
        latent.value, rope_key.value = lane.latent, lane.rope_key

    plan = None
    if not fresh:
        from deepspeed_tpu.models.transformer_lm import decode_attention_block

        block = decode_attention_block(cfg, T)
        if block is not None:
            from deepspeed_tpu.ops.pallas.latent_decode_attention import \
                step_plan

            with jax.named_scope(SCOPE_KV_CACHE_READ):
                plan = step_plan(now_valid, idx, block)
    return LaneCache(Lane(latent.value, rope_key.value, now_valid, idx,
                          fresh, plan), close)


class _Kernel(nn.Module):
    """A ``Dense``'s kernel under a ``Dense``'s name, for a projection
    that is also used re-associated (``W_kvb``)."""
    shape: tuple
    param_dtype: Any
    kernel_init: Any = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self):
        return self.param("kernel", self.kernel_init, self.shape,
                          self.param_dtype)


class LatentAttention(nn.Module):
    config: Any

    @nn.compact
    def __call__(self, x, *, mask=None, segment_ids=None, positions=None,
                 lane=None, cache_layer=None):
        """``lane`` None: no cache (training; the per-head form over the
        ``T`` tokens). With a ``Lane`` (a decode call) returns ``(y,
        lane)``, this layer's rows written at ``cache_layer``."""
        cfg = self.config
        m = cfg.mla
        B, T, C = x.shape
        H, dn, dr, dv, r = (cfg.n_head, m.nope_dim, m.rope_dim, m.v_dim,
                            m.kv_rank)
        if segment_ids is not None and lane is not None:
            raise NotImplementedError(
                "packed-sequence segment_ids are a training-path feature; "
                "decode caches are per-sequence")

        def dense(width, name):
            return nn.Dense(width, use_bias=False, dtype=cfg.dtype,
                            param_dtype=cfg.param_dtype, name=name)

        def norm(name):
            return nn.RMSNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                              param_dtype=cfg.param_dtype, name=name)

        def rope(t, pos):
            # [B, T, heads, dr] in float32, rounded once
            from deepspeed_tpu.ops.rotary import apply_rotary_pos_emb

            out = apply_rotary_pos_emb(
                t.astype(jnp.float32), pos, base=cfg.rope_theta,
                inv_freq=m.inv_freq(cfg.rope_theta))
            if m.rope_mscale != 1.0:
                out = out * m.rope_mscale
            return out.astype(cfg.dtype)

        if lane is not None:
            pos = lane.index[:, None] + jnp.arange(T)[None, :]      # [B, T]
        else:
            pos = positions if positions is not None \
                else jnp.arange(T)[None, :]
        with jax.named_scope(SCOPE_MLA_Q_PROJ):
            q = dense(H * (dn + dr), "q_b")(
                norm("q_a_norm")(dense(m.q_rank, "q_a")(x)))
            # The 2-D product is held as a value before the per-head view
            # is taken. Left free, XLA folds the dot and the reshape into
            # one convolution that writes [B, H, dn + dr] directly and
            # wants the WEIGHTS as [H, dn + dr, q_rank]: a head's dn + dr
            # (192) is not a whole number of 128-lane tiles, so it moves
            # the kernel and not the activations, through a transposed
            # copy and, under a layer scan, a slice of the stack before it
            # (2 x 75.5 MB written and read again a layer and call at
            # DeepSeek-V2's widths, 1.4 ms of a 16.9 ms decode step on the
            # v5e: PERF.md, section 6, PR 55). Held, the product is one
            # fusion that reads the kernel where it lies in the stack, as
            # every other dense layer of the step does, and what is
            # re-laid is the activations.
            q = jax.lax.optimization_barrier(q).reshape(B, T, H, dn + dr)
            q_nope, q_rope = q[..., :dn], rope(q[..., dn:], pos)
        with jax.named_scope(SCOPE_MLA_KV_PROJ):
            ckr = dense(r + dr, "kv_a")(x)
            c_kv = norm("kv_a_norm")(ckr[..., :r])                  # [B,T,r]
            k_rope = rope(ckr[..., None, r:], pos)[:, :, 0]         # [B,T,dr]
        w_kvb = _Kernel((r, H * (dn + dv)), cfg.param_dtype, name="kv_b")() \
            .astype(cfg.dtype)
        if lane is not None:
            with jax.named_scope(SCOPE_KV_CACHE_WRITE):
                at = (cache_layer, jnp.arange(B)[:, None], pos)
                lane = lane.replace(
                    latent=lane.latent.at[at].set(
                        c_kv.astype(cfg.dtype), mode="drop"),
                    rope_key=lane.rope_key.at[at].set(k_rope, mode="drop"))

        if lane is None or lane.fresh:
            # per head, over the tokens at hand
            with jax.named_scope(SCOPE_MLA_KV_PROJ):
                kv = jnp.dot(c_kv, w_kvb).reshape(B, T, H, dn + dv)
                k_nope, v = kv[..., :dn], kv[..., dn:]
            with jax.named_scope(SCOPE_MLA_ATTN):
                att = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                                  preferred_element_type=jnp.float32)
                       + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope,
                                    preferred_element_type=jnp.float32)
                       ) * m.softmax_scale
                visible = jnp.tril(jnp.ones((T, T), bool))[None, None]
                if mask is not None:
                    visible = visible & mask.astype(bool)[:, None, None, :]
                if segment_ids is not None:
                    visible = visible & (segment_ids[:, None, :, None]
                                         == segment_ids[:, None, None, :])
                att = jnp.where(visible, att, jnp.finfo(jnp.float32).min)
                att = jax.nn.softmax(att, axis=-1).astype(cfg.dtype)
                y = jnp.einsum("bhqk,bkhd->bqhd", att, v)
        else:
            # absorbed, over the latent where it lies
            w = w_kvb.reshape(r, H, dn + dv)
            with jax.named_scope(SCOPE_MLA_ABSORB):
                q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, w[..., :dn])
            if lane.plan is not None:
                # one query token: each lane's live blocks, each once,
                # out of the stacked leaves where they lie
                from deepspeed_tpu.ops.pallas.latent_decode_attention import \
                    latent_decode_attention

                with jax.named_scope(SCOPE_MLA_ATTN):
                    o_lat = latent_decode_attention(
                        q_lat[:, 0], q_rope[:, 0], lane.latent,
                        lane.rope_key, lane.plan, cache_layer,
                        scale=m.softmax_scale)[:, None]
            else:
                with jax.named_scope(SCOPE_KV_CACHE_READ):
                    lat_all = jax.lax.dynamic_index_in_dim(
                        lane.latent, cache_layer, 0, keepdims=False)
                    rk_all = jax.lax.dynamic_index_in_dim(
                        lane.rope_key, cache_layer, 0, keepdims=False)
                    visible = (jnp.arange(cfg.n_positions)[None, None, :]
                               <= pos[:, :, None]) & lane.valid[:, None, :]
                with jax.named_scope(SCOPE_MLA_ATTN):
                    att = (jnp.einsum("bqhr,bkr->bhqk", q_lat, lat_all,
                                      preferred_element_type=jnp.float32)
                           + jnp.einsum("bqhd,bkd->bhqk", q_rope, rk_all,
                                        preferred_element_type=jnp.float32)
                           ) * m.softmax_scale
                    att = jnp.where(visible[:, None], att,
                                    jnp.finfo(jnp.float32).min)
                    att = jax.nn.softmax(att, axis=-1).astype(cfg.dtype)
                    o_lat = jnp.einsum("bhqk,bkr->bqhr", att, lat_all)
            with jax.named_scope(SCOPE_MLA_ABSORB):
                y = jnp.einsum("bqhr,rhd->bqhd", o_lat, w[..., dn:])
        with jax.named_scope(SCOPE_MLA_OUT_PROJ):
            y = dense(C, "c_proj")(y.reshape(B, T, H * dv))
        return y if lane is None else (y, lane)


class KindLatentAttention(nn.Module):
    """Latent attention as the mixer of ONE KIND of layer, in a stack whose
    attention layers differ by kind (``GPTConfig.latent_kinds``; the kind
    is ``GPTConfig.attention_kind``'s ``AttentionKind`` with its
    ``LatentKind``): the layer above with the KIND's heads, ranks, widths
    and rotary base, and beside it what a kind may declare:

    * a window, exact by position (``0 <= i - j < window``), over a RING of
      latents: ``AttentionKind.ring`` rows whatever ``n_positions`` is,
      position ``p`` at row ``p % ring`` beside ``slot_pos``, as
      models/kind_attention.py keeps keys and values;
    * an indexer whose queries come from the query latent ``c_q`` and whose
      chosen rows are LATENTS (models/indexer.py; ``cached_index_key`` and
      the three leaves a step leaves of its choice);
    * one sigmoid gate a head (``c_gate`` ``[C, heads]`` on the layer's
      normalised input) on the heads' outputs before ``c_proj``;
    * both normed latents times ``(n_embd / rank) ** 0.5``.

    The parameters keep ``LatentAttention``'s names. The cache is the
    kind's own, kept, written and read back by models/kind_attention.py
    ``KindCache`` as that module's keys and values are, and stacked over
    the kind's layers by ``KindStackedBlocks`` (``cache_layer``: this
    layer's place among them): ``cached_latent``
    ``[B, rows, kv_rank]`` (after its norm and scale), ``cached_rope_key``
    ``[B, rows, rope_dim]`` (after rotary), ``valid``, ``cache_index`` and,
    of a ring, ``slot_pos``. Whoever runs the layers makes the stack before
    the first pass, so EVERY call on a cache takes the absorbed form:

    * one query token: ``mla_decode_attn`` over the blocks between a
      lane's first and last visible row (``mask_plan``: a ring's ``valid &``
      what the window holds), or, under an indexer that has a choice to
      make, ops/indexed_attention.py ``latent_decode_step``;
    * more tokens (the passes of a prefill, at most
      ``AttentionKind.pass_tokens`` over a ring): two einsums over the
      kind's rows, or under an indexer ``attend_tiled`` over the key tiles
      up to the pass's last row (select first, then attend: no ``[heads,
      pass, positions]`` scores exist).

    A call without a cache decompresses keys and values per head."""
    config: Any
    kind: Any

    @nn.compact
    def __call__(self, x, *, mask=None, segment_ids=None, decode=False,
                 cache_layer=None):
        from deepspeed_tpu.models import indexer as ixm
        from deepspeed_tpu.models.kind_attention import KindCache
        from deepspeed_tpu.models.transformer_lm import step_kernel
        from deepspeed_tpu.ops import indexed_attention as ia
        from deepspeed_tpu.ops.pallas import latent_decode_attention as lda
        from deepspeed_tpu.ops.rotary import apply_rotary_pos_emb
        from deepspeed_tpu.telemetry.scopes import (
            SCOPE_LATENT_INDEX,
            SCOPE_LATENT_SELECT,
        )

        cfg, kind = self.config, self.kind
        lk = kind.latent
        m, ix = lk.mla, lk.indexer
        B, T, C = x.shape
        H, dn, dr, dv, r = (lk.n_head, m.nope_dim, m.rope_dim, m.v_dim,
                            m.kv_rank)
        if segment_ids is not None:
            raise NotImplementedError(
                "packed-sequence segment_ids with attention layers that "
                "differ by kind: a window would run across documents")

        up = lk.up_init(C)      # of the matrices that read a normed latent

        def dense(width, name, kernel_init=nn.initializers.lecun_normal()):
            return nn.Dense(width, use_bias=False, dtype=cfg.dtype,
                            param_dtype=cfg.param_dtype,
                            kernel_init=kernel_init, name=name)

        def normed(name, t, rank):
            t = nn.RMSNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                           param_dtype=cfg.param_dtype, name=name)(t)
            return t * (C / rank) ** 0.5 if lk.rank_rescale else t

        def rope(t, pos):
            out = apply_rotary_pos_emb(
                t.astype(jnp.float32), pos, base=lk.rope_theta,
                inv_freq=m.inv_freq(lk.rope_theta))
            if m.rope_mscale != 1.0:
                out = out * m.rope_mscale
            return out.astype(cfg.dtype)

        written = (jnp.ones((B, T), jnp.bool_) if mask is None
                   else mask.astype(jnp.bool_))
        S = kind.ring or cfg.n_positions
        selects = ix is not None and ix.engaged(cfg)
        cache = None
        if decode:
            rows = {CACHED_LATENT: ((r,), cfg.dtype),
                    CACHED_ROPE_KEY: ((dr,), cfg.dtype)}
            step = {}
            if ix is not None:
                rows[ixm.CACHED_INDEX_KEY] = ((ix.head_dim,), cfg.dtype)
            if selects:
                step = {
                    ixm.CHOSEN_ROWS: ((min(ix.topk, S),), -1, jnp.int32),
                    ixm.CHOICE_QUERY: ((ix.n_heads, ix.head_dim), 0,
                                       cfg.dtype),
                    ixm.CHOICE_WEIGHTS: ((ix.n_heads,), 0, jnp.float32)}
            cache = KindCache(self, kind, B, T, rows, cache_layer, step)
        pos = cache.pos if decode \
            else jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))

        with jax.named_scope(SCOPE_MLA_Q_PROJ):
            c_q = normed("q_a_norm", dense(m.q_rank, "q_a")(x), m.q_rank)
            # held as a value before the per-head view, as in the layer
            # above (the comment there says why)
            q = jax.lax.optimization_barrier(
                dense(H * (dn + dr), "q_b", up)(c_q)).reshape(
                    B, T, H, dn + dr)
            q_nope, q_rope = q[..., :dn], rope(q[..., dn:], pos)
        with jax.named_scope(SCOPE_MLA_KV_PROJ):
            ckr = dense(r + dr, "kv_a")(x)
            c_kv = normed("kv_a_norm", ckr[..., :r], r).astype(cfg.dtype)
            k_rope = rope(ckr[..., None, r:], pos)[:, :, 0]
        w_kvb = _Kernel((r, H * (dn + dv)), cfg.param_dtype, up,
                        name="kv_b")().astype(cfg.dtype)
        gate = dense(H, "c_gate")(x) if lk.head_gate else None
        if ix is not None:
            q_idx, k_idx, w_idx = ixm.Indexer(
                cfg, latent=lk, name="indexer")(x, pos, q_in=c_q)
        keep = ix is not None \
            and self.is_mutable_collection("intermediates")

        def close(y):
            """The heads' outputs ``[B, T, H, dv]`` under the heads' gate,
            through the output projection."""
            if gate is not None:
                y = y * jax.nn.sigmoid(gate)[..., None]
            with jax.named_scope(SCOPE_MLA_OUT_PROJ):
                return dense(C, "c_proj")(y.reshape(B, T, H * dv))

        if not decode:
            # no cache: per head over the tokens at hand, row t position t
            ahead = pos[0][:, None] - pos[0][None, :]            # i - j
            visible = (ahead >= 0) if kind.window is None \
                else (ahead >= 0) & (ahead < kind.window)
            visible = visible[None] & written[:, None, :]       # [B, T, T]
            if ix is not None:
                with jax.named_scope(SCOPE_LATENT_INDEX):
                    scores = ia.index_scores(q_idx, k_idx, w_idx)
                with jax.named_scope(SCOPE_LATENT_SELECT):
                    visible = ia.chosen_mask(scores, visible, ix.topk)
                if keep:
                    self.sow("intermediates", "chosen", visible)
            with jax.named_scope(SCOPE_MLA_KV_PROJ):
                kv = jnp.dot(c_kv, w_kvb).reshape(B, T, H, dn + dv)
            with jax.named_scope(kind.scope):
                att = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, kv[..., :dn],
                                  preferred_element_type=jnp.float32)
                       + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope,
                                    preferred_element_type=jnp.float32)
                       ) * m.softmax_scale
                att = jnp.where(visible[:, None], att,
                                jnp.finfo(jnp.float32).min)
                att = jax.nn.softmax(att, axis=-1).astype(cfg.dtype)
                y = jnp.einsum("bhqk,bkhd->bqhd", att, kv[..., dn:])
            return close(y)

        new = {CACHED_LATENT: c_kv, CACHED_ROPE_KEY: k_rope}
        if ix is not None:
            new[ixm.CACHED_INDEX_KEY] = k_idx
        cache.write(new, written)
        visible = cache.visible()                           # [B, T, S]

        w_kvb = w_kvb.reshape(r, H, dn + dv)
        with jax.named_scope(SCOPE_MLA_ABSORB):
            q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_kvb[..., :dn])
        chosen = None
        if T == 1 and step_kernel() and selects:
            o_lat, picked, ok = ia.latent_decode_step(
                q_lat[:, 0], q_rope[:, 0], q_idx[:, 0], w_idx[:, 0],
                cache.stacked(CACHED_LATENT), cache.stacked(CACHED_ROPE_KEY),
                cache.stacked(ixm.CACHED_INDEX_KEY), cache_layer,
                visible[:, 0], ix.topk, m.softmax_scale, cfg.dtype)
            o_lat = o_lat[:, None]
            with jax.named_scope(SCOPE_KV_CACHE_WRITE):
                for name, val in ((ixm.CHOSEN_ROWS, jnp.where(ok, picked, -1)),
                                  (ixm.CHOICE_QUERY, q_idx[:, 0]),
                                  (ixm.CHOICE_WEIGHTS, w_idx[:, 0])):
                    cache.put(name, (Ellipsis,), val)
            if keep:
                chosen = jnp.zeros((B, S), jnp.bool_).at[
                    jnp.arange(B)[:, None], picked].max(ok)[:, None]
        elif T == 1 and step_kernel():
            # one query token: the blocks between a lane's first and last
            # visible row, out of the stacked leaves where they lie
            with jax.named_scope(kind.scope):
                o_lat = lda.latent_decode_attention(
                    q_lat[:, 0], q_rope[:, 0], cache.stacked(CACHED_LATENT),
                    cache.stacked(CACHED_ROPE_KEY), lda.mask_plan(
                        visible[:, 0], lda.block_positions(
                            S, r, jnp.dtype(cfg.dtype).itemsize)),
                    cache_layer, scale=m.softmax_scale)[:, None]
        else:
            with jax.named_scope(SCOPE_KV_CACHE_READ):
                lat_all, rk_all = (cache.leaf(CACHED_LATENT),
                                   cache.leaf(CACHED_ROPE_KEY))
            if selects:
                # select first, then attend over the chosen rows of the
                # key tiles up to the pass's last row: one "KV head" whose
                # key is [latent | rotary key] and whose value the latent
                o_lat, chosen = ia.attend_tiled(
                    jnp.concatenate([q_lat, q_rope], -1),
                    jnp.concatenate([lat_all, rk_all], -1)[:, :, None],
                    lat_all[:, :, None], q_idx,
                    cache.leaf(ixm.CACHED_INDEX_KEY), w_idx, pos,
                    cache.leaf("valid"), ix.topk, ix.q_chunk, ix.kv_chunk,
                    m.softmax_scale, cfg.dtype, keep_mask=keep,
                    live_tiles=jnp.max(pos) // min(ix.kv_chunk, S) + 1,
                    scopes=(SCOPE_LATENT_INDEX, SCOPE_LATENT_SELECT,
                            kind.scope))
            else:
                with jax.named_scope(kind.scope):
                    att = (jnp.einsum("bqhr,bkr->bhqk", q_lat, lat_all,
                                      preferred_element_type=jnp.float32)
                           + jnp.einsum("bqhd,bkd->bhqk", q_rope, rk_all,
                                        preferred_element_type=jnp.float32)
                           ) * m.softmax_scale
                    att = jnp.where(visible[:, None], att,
                                    jnp.finfo(jnp.float32).min)
                    att = jax.nn.softmax(att, axis=-1).astype(cfg.dtype)
                    o_lat = jnp.einsum("bhqk,bkr->bqhr", att, lat_all)
        if keep:
            self.sow("intermediates", "chosen",
                     visible if chosen is None else chosen)
        with jax.named_scope(SCOPE_MLA_ABSORB):
            y = jnp.einsum("bqhr,rhd->bqhd", o_lat, w_kvb[..., dn:])
        return close(y)
