"""TPU-first BERT encoder for the pretraining benchmark path.

Capability counterpart of the reference's BERT story (BASELINE config 1;
reference docs/_tutorials/bert-pretraining.md, tests/unit/modeling.py HF copy).
Idiomatic JAX encoder: bf16 compute, einsum attention, scan-over-layers,
MLM head tied to the token embedding.
"""

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.transformer_lm import VocabEmbed
from deepspeed_tpu.telemetry.scopes import SCOPE_ATTN_CORE, SCOPE_MLM_HEAD


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    layer_norm_eps: float = 1e-12   # HF BERT default
    approximate_gelu: bool = True   # tanh gelu; HF BERT uses exact erf gelu
    use_mlm_bias: bool = False      # HF cls.predictions.bias on the decoder
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    # "full" recomputes everything; "selective" saves the non-batched
    # param matmul outputs (attention einsums are still recomputed — the
    # policy's flash-attention checkpoint names only exist in the GPT
    # trunk; see transformer_lm._remat_policy)
    remat_policy: str = "full"
    scan_layers: bool = True
    # a SparsityConfig (ops.sparse_attention): restricts attention to the
    # config's block layout (default impl: static K/V-block gather + MXU
    # einsums; "kernel": "pallas" selects the streaming kernel). Populated
    # from the DeepSpeed "sparse_attention" config block by
    # sparse_attention_utils.apply_sparse_attention.
    sparse_attention: Any = None
    # stochastic transformer (reference op_builder/stochastic_transformer.py):
    # whole-layer stochastic depth driven by the engine's PLD schedule; see
    # transformer_lm.GPTConfig.stochastic_mode for the key/remat story
    stochastic_mode: bool = False

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


BERT_SIZES = {
    "bert-base": dict(hidden_size=768, num_hidden_layers=12,
                      num_attention_heads=12, intermediate_size=3072),
    "bert-large": dict(hidden_size=1024, num_hidden_layers=24,
                       num_attention_heads=16, intermediate_size=4096),
}


def bert_config(name: str, **overrides) -> BertConfig:
    base = dict(BERT_SIZES[name])
    base.update(overrides)
    return BertConfig(**base)


class BertSelfAttention(nn.Module):
    config: BertConfig

    @nn.compact
    def __call__(self, x, mask=None, deterministic=True):
        cfg = self.config
        B, T, C = x.shape
        H, D = cfg.num_attention_heads, cfg.head_dim
        qkv = nn.Dense(3 * C, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                       name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, H, D)
        k = k.reshape(B, T, H, D)
        v = v.reshape(B, T, H, D)
        if cfg.sparse_attention is not None:
            # block-sparse path (SparseSelfAttention; default "gather" impl
            # materializes [B, H, nL, block, W*block] score buffers).
            # Attention-probability dropout is not applied on this path —
            # the layout already drops most of the attention matrix; output
            # dropout below still applies.
            from deepspeed_tpu.ops.sparse_attention import SparseSelfAttention

            sa = SparseSelfAttention(
                cfg.sparse_attention,
                max_seq_length=cfg.max_position_embeddings)
            kpm = None
            if mask is not None:
                kpm = jnp.where(mask, 0.0, jnp.finfo(jnp.float32).min)
            with jax.named_scope(SCOPE_ATTN_CORE):
                y = sa(q, k, v, key_padding_mask=kpm).reshape(B, T, C)
        else:
            with jax.named_scope(SCOPE_ATTN_CORE):
                att = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
                if mask is not None:
                    att = jnp.where(mask[:, None, None, :], att,
                                    jnp.finfo(att.dtype).min)
                att = jax.nn.softmax(
                    att.astype(jnp.float32), axis=-1).astype(cfg.dtype)
                att = nn.Dropout(cfg.dropout)(
                    att, deterministic=deterministic)
                y = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(B, T, C)
        y = nn.Dense(C, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     name="output")(y)
        y = nn.Dropout(cfg.dropout)(y, deterministic=deterministic)
        return y


class BertLayer(nn.Module):
    config: BertConfig

    @nn.compact
    def __call__(self, x, mask=None, deterministic=True, pld_keep=None):
        cfg = self.config
        x_in = x
        # Post-LN like original BERT
        a = BertSelfAttention(cfg, name="attention")(x, mask, deterministic)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype, name="ln_attn")(x + a)
        h = nn.Dense(cfg.intermediate_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="intermediate")(x)
        h = nn.gelu(h, approximate=cfg.approximate_gelu)
        h = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="output")(h)
        h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype, name="ln_out")(x + h)
        if cfg.stochastic_mode and pld_keep is not None and not deterministic:
            # whole-layer stochastic depth (PLD identity skip; same
            # remat-exact per-layer key story as transformer_lm.Block)
            gate = jax.random.bernoulli(self.make_rng("dropout"), pld_keep)
            x = jnp.where(gate, x, x_in)
        return x


class BertEncoder(nn.Module):
    config: BertConfig

    @nn.compact
    def __call__(self, x, mask=None, deterministic=True, pld_theta=None):
        cfg = self.config
        L = cfg.num_hidden_layers
        use_pld = (cfg.stochastic_mode and pld_theta is not None
                   and not deterministic)

        def keep_of(layer_idx):
            if not use_pld:
                return None
            from deepspeed_tpu.models.transformer_lm import \
                pld_keep_probability

            return pld_keep_probability(layer_idx, L, pld_theta)

        if cfg.scan_layers:
            layer_cls = BertLayer
            if cfg.remat:
                from deepspeed_tpu.models.transformer_lm import _remat_policy

                layer_cls = nn.remat(BertLayer, prevent_cse=False,
                                     policy=_remat_policy(cfg.remat_policy))

            def body(layer, carry, layer_idx):
                x, mask = carry
                return (layer(x, mask, deterministic,
                              keep_of(layer_idx)), mask), None

            scanned = nn.scan(
                body,
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=0,
                length=L,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )
            (x, _), _ = scanned(layer_cls(cfg, name="layer"), (x, mask),
                                jnp.arange(L))
            return x
        layer_cls = BertLayer
        if cfg.remat:
            from deepspeed_tpu.models.transformer_lm import _remat_policy

            layer_cls = nn.remat(BertLayer, prevent_cse=False,
                                 policy=_remat_policy(cfg.remat_policy))
        for i in range(cfg.num_hidden_layers):
            x = layer_cls(cfg, name=f"layer_{i}")(x, mask, deterministic,
                                                  keep_of(i))
        return x


def bert_tp_rules(path: str, shape):
    """Megatron-style TP specs for BERT params (see gpt_tp_rules)."""
    from jax.sharding import PartitionSpec

    ndim = len(shape)

    def dim(i):
        spec = [None] * ndim
        spec[i] = "tp"
        return PartitionSpec(*spec)

    if path.endswith(("attention/qkv/kernel", "attention/qkv/bias",
                      "intermediate/kernel", "intermediate/bias")):
        return dim(-1)  # column parallel
    if path.endswith("output/kernel"):  # both attention/output and FFN output
        return dim(-2)  # row parallel
    if path.endswith("word_embeddings/embedding"):
        return dim(0)
    return None


class BertForPreTraining(nn.Module):
    """BERT with MLM head (tied embeddings). ``__call__`` returns masked-LM
    loss when ``labels`` given (-100 = ignore), else logits."""

    config: BertConfig

    tp_rules = staticmethod(bert_tp_rules)

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 labels=None, deterministic=True, pld_theta=None):
        cfg = self.config
        B, T = input_ids.shape
        tok = VocabEmbed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="word_embeddings")
        pos = nn.Embed(cfg.max_position_embeddings, cfg.hidden_size,
                       dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                       name="position_embeddings")
        typ = nn.Embed(cfg.type_vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype, name="token_type_embeddings")
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        x = tok(input_ids) + pos(jnp.arange(T)[None, :]) + typ(token_type_ids)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype, name="embeddings_ln")(x)
        x = nn.Dropout(cfg.dropout)(x, deterministic=deterministic)

        x = BertEncoder(cfg, name="encoder")(x, attention_mask, deterministic,
                                             pld_theta=pld_theta)

        # MLM transform + tied decoder (and the loss) under one scope
        with jax.named_scope(SCOPE_MLM_HEAD):
            h = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="mlm_dense")(x)
            h = nn.gelu(h, approximate=cfg.approximate_gelu)
            h = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                             name="mlm_ln")(h)
            # bf16 operands + fp32 accumulation: full MXU rate on the vocab
            # projection (fp32 matmul would run ~8x slower)
            logits = jax.lax.dot_general(
                h.astype(cfg.dtype), tok.embedding.astype(cfg.dtype),
                (((h.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if cfg.use_mlm_bias:
                logits = logits + self.param(
                    "mlm_bias", nn.initializers.zeros, (cfg.vocab_size,),
                    cfg.param_dtype).astype(logits.dtype)
            if labels is None:
                return logits
            return masked_lm_loss(logits, labels)


def masked_lm_loss(logits, labels):
    """Mean CE over positions where labels != -100."""
    logits = logits.astype(jnp.float32)
    valid = labels != -100
    safe_labels = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, safe_labels[..., None], axis=-1)[..., 0]
    m = valid.astype(jnp.float32)
    return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
