"""The lightning indexer that sits beside ``CausalSelfAttention``
(``GPTConfig.indexer``, an ``IndexerConfig``; Keye-VL-2.0's ``sa_config``,
after DeepSeek-V3.2-Exp's published indexer). With ``h`` the block's
normalised input:

    qI = h W_qI  -> n_heads x head_dim        kI = LayerNorm(h W_kI)
    w  = h W_w * n_heads^-1/2 * head_dim^-1/2                (float32)
    qI, kI rotated by the block's own positions (all ``head_dim``
    dimensions, halves convention, the model's base)
    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])         (float32)

and a query attends over the ``topk`` positions ``s <= t`` of largest
``I`` alone (ops/indexed_attention.py). ``kI`` is ONE key a position,
shared by the indexer's heads: ``cached_index_key`` ``[layers, B, S,
head_dim]`` in the compute dtype, the third leaf a lane keeps beside
``cached_key`` and ``cached_value``, written with them and under their
clock and ``valid``. Beside it a lane keeps the decode program's own
account of its LAST decode query's selection, which every decode step
overwrites and nothing reads back, for whoever reads the cache afterwards
(a check, a counter): ``chosen_rows`` ``[layers, B, topk]`` int32, the rows
it attended over (-1 where it saw fewer than ``topk``), and the ``qI`` and
``w`` it scored them with (``choice_query`` ``[layers, B, n_heads,
head_dim]``, ``choice_weights`` ``[layers, B, n_heads]``).

The same module is the indexer of ONE KIND of layer in a stack whose
attention layers are latent attention by kind (``LatentKind.indexer``;
models/latent_attention.py ``KindLatentAttention``): the kind hands itself
(``latent``: its sizes, its rotary base, how a reader of its query latent
is born; the scope is ``latent_index``), the queries come from the
layer's QUERY LATENT (``q_in``) and rotary turns the first ``rope_dim``
dimensions of each head alone (DeepSeek-V3.2-Exp's form); the rows chosen are latents, attended over in the absorbed form
(ops/indexed_attention.py ``latent_decode_step``, ``attend_tiled``), and
the leaves and what a step leaves of its choice are the same three.
"""
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.telemetry.scopes import (
    SCOPE_DSA_INDEX_PROJ,
    SCOPE_KV_CACHE_READ,
    SCOPE_KV_CACHE_WRITE,
    SCOPE_LATENT_INDEX,
)

CACHED_INDEX_KEY = "cached_index_key"
CHOSEN_ROWS = "chosen_rows"
CHOICE_QUERY = "choice_query"
CHOICE_WEIGHTS = "choice_weights"


class Indexer(nn.Module):
    config: "GPTConfig"  # noqa: F821  (models/transformer_lm.py)
    # the kind whose indexer this is (``LatentKind``: its ``indexer``,
    # ``rope_theta``, and how a reader of its query latent is born), timed
    # under ``latent_index``; None = the whole model's ``GPTConfig.indexer``
    # and ``rope_theta``, under ``dsa_index_proj``
    latent: Any = None

    @nn.compact
    def __call__(self, x, pos, q_in=None):
        """``(qI [B, T, n_heads, head_dim], kI [B, T, head_dim], w [B, T,
        n_heads] float32)`` of ``x [B, T, C]`` at positions ``pos`` (``[B,
        T]``, or ``[3, B, T]`` under a sectioned rotary); the queries from
        ``q_in [B, T, .]`` where the layer hands one (DeepSeek-V3.2-Exp:
        the query latent), else from ``x`` like the key and the weights."""
        from deepspeed_tpu.ops.rotary import apply_rotary_pos_emb

        cfg = self.config
        lk = self.latent
        ix = cfg.indexer if lk is None else lk.indexer
        B, T, _ = x.shape

        def dense(width, name, **kw):
            return nn.Dense(width, use_bias=False, dtype=cfg.dtype,
                            param_dtype=cfg.param_dtype, name=name, **kw)

        def rope(t):
            return apply_rotary_pos_emb(
                t, pos, base=cfg.rope_theta if lk is None else lk.rope_theta,
                rotary_dim=ix.rope_dim, sections=ix.sections(cfg))

        with jax.named_scope(SCOPE_DSA_INDEX_PROJ if lk is None
                             else SCOPE_LATENT_INDEX):
            born = {} if lk is None \
                else {"kernel_init": lk.up_init(x.shape[-1])}
            q = dense(ix.n_heads * ix.head_dim, "wq", **born)(
                x if q_in is None else q_in).reshape(
                B, T, ix.n_heads, ix.head_dim)
            k = nn.LayerNorm(
                epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="k_norm")(
                    dense(ix.head_dim, "wk")(x))
            w = dense(ix.n_heads, "weights_proj")(x).astype(jnp.float32) \
                * ix.weight_scale
            q = rope(q)
            k = rope(k[:, :, None, :])[:, :, 0].astype(cfg.dtype)
        return q, k, w


def attend_chosen(module, heads, index, pos, valid_now, stored):
    """Attention of ``CausalSelfAttention`` (``module``) over the rows its
    indexer chooses, ``[B, T, H, D]``, for ``heads = (q, k, v)`` and
    ``index = (qI, kI, w)`` of the tokens at hand; the form is told from
    the call:

    * ``stored`` None (a pass without a cache, or the one that makes it):
      the queries choose among the ``T`` tokens at hand, ``valid_now [B,
      T]`` of them real;
    * one query token on a cache that exists, ``stored = (cache, leaf, put,
      cache_layer)`` (the module's cache variables, its reader of a layer's
      slice and its writer into one, this call's layer of the stacked
      leaves or None) at rows ``pos``: scores over the layer's index keys,
      ``top_k``'s set of them by a threshold and no sort (``indexed_
      attention.chosen_set``), and the chosen rows out of the stacked key
      and value leaves, gathered or read as the lanes' live blocks under
      the chosen mask by the dense path's decode kernel, whichever is
      cheaper for what the lanes hold this step (``indexed_attention.
      reads_blocks``); rows (ascending), query and weights are left in the
      cache;
    * more query tokens on a cache that exists (a continuation,
      verification): the tiled form over the layer's slices.

    Where tests ask for it (a mutable ``intermediates`` collection) the
    chosen positions are sown as ``chosen``: ``[B, T, S]`` bool."""
    from deepspeed_tpu.ops import indexed_attention as ia

    cfg = module.config
    ix = cfg.indexer
    (q, k, v), (q_idx, k_idx, w) = heads, index
    B, T, H, D = q.shape
    scale = 1.0 / np.sqrt(D)
    keep = module.is_mutable_collection("intermediates")
    chosen = None
    if stored is None:
        y, chosen = ia.attend_tiled(
            q, k, v, q_idx, k_idx, w,
            jnp.broadcast_to(jnp.arange(T)[None, :], (B, T)), valid_now,
            ix.topk, ix.q_chunk, ix.kv_chunk, scale, cfg.dtype,
            keep_mask=keep)
    elif T == 1:
        cache, leaf, put, cache_layer = stored
        with jax.named_scope(SCOPE_KV_CACHE_READ):
            S = cfg.n_positions
            visible = (jnp.arange(S)[None, :] <= pos) & leaf("valid")
        y, rows, ok = ia.decode_step(
            q[:, 0], q_idx[:, 0], w[:, 0], cache["cached_key"].value,
            cache["cached_value"].value, cache[CACHED_INDEX_KEY].value,
            cache_layer, visible, pos[:, 0], ix.topk, cfg.dtype)
        y = y[:, None]
        with jax.named_scope(SCOPE_KV_CACHE_WRITE):
            put(CHOSEN_ROWS, (Ellipsis,), jnp.where(ok, rows, -1))
            put(CHOICE_QUERY, (Ellipsis,), q_idx[:, 0])
            put(CHOICE_WEIGHTS, (Ellipsis,), w[:, 0])
        if keep:
            chosen = jnp.zeros((B, S), jnp.bool_).at[
                jnp.arange(B)[:, None], rows].max(ok)[:, None]
    else:
        leaf = stored[1]
        with jax.named_scope(SCOPE_KV_CACHE_READ):
            k_all, v_all = leaf("cached_key"), leaf("cached_value")
            ki_all, k_valid = leaf(CACHED_INDEX_KEY), leaf("valid")
        y, chosen = ia.attend_tiled(
            q, k_all, v_all, q_idx, ki_all, w, pos, k_valid, ix.topk,
            ix.q_chunk, ix.kv_chunk, scale, cfg.dtype, keep_mask=keep)
    if keep:
        module.sow("intermediates", "chosen", chosen)
    return y
