"""TPU-first decoder-only transformer LM (GPT-2 family).

This is the in-repo model zoo counterpart of the reference's transformer stack
(reference ``csrc/transformer/`` fused training kernel +
``deepspeed/ops/transformer/transformer.py:459`` DeepSpeedTransformerLayer).
Design is idiomatic JAX, not a translation:

* bf16 compute / fp32 params (mixed precision by dtype policy, not patching)
* einsum attention — XLA fuses bias/gelu/residual into the MXU matmuls,
  which is what the reference's hand-fused CUDA kernels exist to do
* optional ``lax.scan`` over layers: O(1) compile time and natural remat
* static shapes only; causal mask via iota comparison (no dynamic slicing)
* weights carry stable path names so parallelism rules (TP/FSDP specs,
  see deepspeed_tpu/runtime/zero/sharding.py) can address them by regex
"""

import dataclasses
import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.moe.layer import MOE_STATS
from deepspeed_tpu.runtime.zero.gather import (
    current_plan, gather_tree, gathered_on_use, layers_ahead, layers_per_turn)
from deepspeed_tpu.telemetry.scopes import (
    SCOPE_ATTN_CORE,
    SCOPE_CONV_STATE_CARRY,
    SCOPE_FULL_ATTN,
    SCOPE_KV_CACHE_CARRY,
    SCOPE_KV_CACHE_READ,
    SCOPE_KV_CACHE_WRITE,
    SCOPE_LM_HEAD,
    SCOPE_LM_HEAD_CE,
    SCOPE_RET_STATE_CARRY,
    SCOPE_SPARSE_LATENT_ATTN,
    SCOPE_SSM_STATE_CARRY,
    SCOPE_WINDOW_ATTN,
    SCOPE_WINDOW_LATENT_ATTN,
)


@dataclasses.dataclass(frozen=True)
class CacheLeaf:
    """One leaf of the decode cache that holds what a lane keeps: declared
    once, by the mixer that writes it (``GPTConfig.cache_leaves``), and
    read from there by whatever has to know a lane cache's layout
    (inference/lane_cache.py; the scope table's carry tags)."""
    name: str            # in the ``cache`` collection
    rank: int            # of one layer's ``[B, ...]`` leaf
    # "position": a row a position, so the cache can be cut at a prefix
    # and stepped back; "recurrent": a state, which can be neither;
    # "step": what a lane's last decode step left for whoever reads the
    # cache afterwards (every step overwrites it, nothing reads it back,
    # so it is neither cut nor stepped back)
    kind: str
    # which sums of ``kv_cache_stats`` the leaf's bytes enter beside
    # ``resident_bytes``: "state", "conv", "norm", "latent"; "sideband"
    # (an int8 store's scales) enters none, and no unquantised twin
    counted_as: Tuple[str, ...]
    # the scope table's tag of an instruction that no scope owns and whose
    # result is this whole leaf (the stacked one, and one layer's slice of
    # it where ``slice_is_whole``)
    carry_tag: str
    slice_is_whole: bool = True
    dtype: Any = None    # as stored, where the leaf has a dtype of its own
    unset: Any = 0       # what an empty cache holds
    # the kind of layer that keeps the leaf (an entry of
    # ``GPTConfig.layer_types``); None = every layer
    held_by: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """The Mamba-2 (state-space duality) mixer that a block runs beside its
    attention, on the same normalised input (models/mamba2.py; Falcon-H1's
    ``mamba_*`` keys). ``n_heads * d_head`` is the mixer's inner width,
    whatever the model's; ``multipliers`` are the muP factors on the input
    projection's segments ``[z | x | B | C | dt]``. One form, the
    published one: a bias on the convolution and none on the projections,
    the gate first and then an RMS norm inside each group."""
    n_heads: int
    d_head: int
    d_state: int
    n_groups: int = 1
    d_conv: int = 4
    chunk: int = 128
    in_multiplier: float = 1.0
    out_multiplier: float = 1.0
    multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    # the recurrent state's storage dtype in the decode cache; the
    # arithmetic is float32 either way
    state_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.n_heads % self.n_groups:
            raise ValueError(
                f"ssm n_heads ({self.n_heads}) must be divisible by "
                f"n_groups ({self.n_groups})")
        if len(self.multipliers) != 5:
            raise ValueError("ssm multipliers are five: z, x, B, C, dt")

    def cache_leaves(self, cfg) -> Tuple[CacheLeaf, ...]:
        """(models/mamba2.py) One layer's convolution tail is what the
        convolution itself concatenates in front of its input (kilobytes a
        lane), so only the stacked tail counts as a whole leaf."""
        from deepspeed_tpu.models.mamba2 import CONV_TAIL, SSM_STATE

        return (CacheLeaf(SSM_STATE, 4, "recurrent", ("state",),
                          SCOPE_SSM_STATE_CARRY, dtype=self.state_dtype),
                CacheLeaf(CONV_TAIL, 3, "recurrent", ("conv",),
                          SCOPE_SSM_STATE_CARRY, slice_is_whole=False,
                          dtype=cfg.dtype))

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state

    @property
    def in_proj_dim(self) -> int:
        return self.d_inner + self.conv_dim + self.n_heads


@dataclasses.dataclass(frozen=True)
class RetentionConfig:
    """Power retention IN PLACE OF attention as a block's token mixer
    (models/power_retention.py over ops/power_retention.py): gated linear
    attention with the kernel ``(q . k)^2``. The heads, their size,
    rotary and the norm's epsilon are the model's own (``n_head``,
    ``n_kv_head``, ``head_dim``, ``rope_theta``, ``layer_norm_epsilon``);
    each head of q and k is RMS-normalised before rotary. The state ``S``
    (per KV head the symmetric square of the keys by ``d``) and its
    normaliser ``z`` are all a lane's cache holds: no keys, no values."""
    # tokens in a chunk of a pass over many (prefill); changes no value
    chunk: int = 128
    # added to the normaliser phi(q) . z
    eps: float = 1e-6
    # storage dtype of S and z in the decode cache; the arithmetic is
    # float32 either way
    state_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.chunk < 1:
            raise ValueError(f"retention chunk must be >= 1; {self.chunk}")

    def cache_leaves(self, cfg) -> Tuple[CacheLeaf, ...]:
        """One layer's slice of the normaliser has the shape of ``phi(k)``
        itself, so only the stacked one counts as a whole leaf."""
        from deepspeed_tpu.models.power_retention import RET_NORM, RET_STATE

        return (CacheLeaf(RET_STATE, 4, "recurrent", ("state",),
                          SCOPE_RET_STATE_CARRY, dtype=self.state_dtype),
                CacheLeaf(RET_NORM, 3, "recurrent", ("state", "norm"),
                          SCOPE_RET_STATE_CARRY, slice_is_whole=False,
                          dtype=self.state_dtype))


@dataclasses.dataclass(frozen=True)
class ShortConvConfig:
    """The gated short convolution that a layer of kind ``"conv"`` runs as
    its token mixer (models/short_conv.py; LFM2's ``conv`` layers): one
    input projection to three parts ``[B | C | z]`` of the model's width,
    a depthwise causal convolution of ``width`` taps over ``B * z`` with
    neither bias nor activation, the gate ``C`` on its output, one output
    projection. A lane keeps the convolution's last ``width - 1`` inputs
    a layer, the leaf the Mamba-2 mixer's convolution keeps
    (``conv_tail``), and nothing per position."""
    width: int = 3          # conv_L_cache: taps, the current token's last

    def __post_init__(self):
        if self.width < 2:
            raise ValueError(f"a short convolution has >= 2 taps; "
                             f"{self.width}")

    def cache_leaves(self, cfg) -> Tuple[CacheLeaf, ...]:
        """(models/short_conv.py) One layer's tail is what the convolution
        concatenates in front of its input, so only the stacked tail
        counts as a whole leaf."""
        from deepspeed_tpu.models.mamba2 import CONV_TAIL

        return (CacheLeaf(CONV_TAIL, 3, "recurrent", ("conv",),
                          SCOPE_CONV_STATE_CARRY, slice_is_whole=False,
                          dtype=cfg.dtype, held_by=KIND_CONV),)


# the kinds of layer a stack can mix (``GPTConfig.layer_types``), each
# named for its token mixer
KIND_ATTENTION = "attention"
KIND_CONV = "conv"
KIND_WINDOW = "window"


class MixedCacheError(ValueError):
    """A feature that assumes every layer keeps every position was asked
    of a model whose window layers keep a ring beside the other layers'
    dense cache (``GPTConfig.layer_types`` with ``"window"`` layers)."""

    def __init__(self, feature: str, why: str):
        super().__init__(
            f"{feature} cannot serve a model whose window layers keep a "
            f"ring of rows beside a dense cache (slot_pos: "
            f"GPTConfig.sliding_window): {why}")
        self.feature = feature


@dataclasses.dataclass(frozen=True)
class AttentionKind:
    """What an attention layer of one kind is, in a stack whose attention
    layers differ by kind (``GPTConfig.attention_kind``; run by
    models/kind_attention.py): the positions a query sees, what a lane
    keeps of the layer, whether q and k are rotated, and the scope its
    attention is timed under."""
    # the newest positions a query attends over, itself included; None =
    # every earlier position
    window: Optional[int]
    # rows of keys and values a lane keeps of the layer: a ring of
    # ``window + window_slack`` rows written at ``position % rows`` beside
    # ``slot_pos``, or (None) ``n_positions`` rows written at the position
    ring: Optional[int]
    rotary: bool
    scope: str
    # the kind's own mixer where it is latent attention (``LatentKind``:
    # heads, ranks and widths, ``rope_theta``, indexer, gate), run by
    # models/latent_attention.py ``KindLatentAttention``; None = grouped-
    # query attention at the model's one head count and width
    latent: Optional["LatentKind"] = None

    @property
    def pass_tokens(self) -> Optional[int]:
        """The tokens one pass over a cache of this kind writes before its
        queries read (None: any number). A pass of ``T`` tokens from
        position ``p`` overwrites the row of position ``p + T - 1 - ring``
        at the latest, and its first query still sees ``p - window + 1``:
        ``T <= ring - window + 1`` (models/kind_attention.py ``KindCache``
        refuses more). Of that bound the largest EVEN number, so that
        passes tile a prompt's bucket: a window of 4,096 in a ring of 4,352
        gives passes of 256, not 257; a window of 513 in a ring of 1,024
        gives 512."""
        if self.ring is None:
            return None
        most = self.ring - self.window + 1
        return max(1, most - most % 2)


def attention_cache_leaves(cfg=None) -> Tuple[CacheLeaf, ...]:
    """What ``CausalSelfAttention`` keeps: keys and values per head and,
    in an int8 store (``GPTConfig.kv_cache_dtype``), a scale per
    (position, KV head) beside each. ``cfg`` None is a module that
    declares nothing, taken to keep keys and values."""
    leaves = tuple(CacheLeaf(name, 4, "position", (), SCOPE_KV_CACHE_CARRY)
                   for name in ("cached_key", "cached_value"))
    if getattr(cfg, "kv_cache_dtype", None) == "int8":
        leaves += tuple(
            CacheLeaf(leaf.name + "_scale", 3, "position", ("sideband",),
                      SCOPE_KV_CACHE_CARRY, dtype=jnp.float32)
            for leaf in leaves)
    return leaves


def window_cache_leaves() -> Tuple[CacheLeaf, ...]:
    """What an attention layer of kind ``"window"`` keeps: keys and values
    of the rows its ring holds (``AttentionKind.ring``), counted apart
    (``window_bytes_per_lane``)."""
    return tuple(
        dataclasses.replace(leaf, counted_as=("window",),
                            held_by=KIND_WINDOW)
        for leaf in attention_cache_leaves())


def declared_cache_leaves(config) -> Tuple[CacheLeaf, ...]:
    """``config.cache_leaves``, or attention's for a module whose
    configuration declares nothing."""
    leaves = getattr(config, "cache_leaves", None)
    return attention_cache_leaves() if leaves is None else leaves


class LatentCacheError(ValueError):
    """A feature that assumes keys and values per head was asked of a
    model whose cache is latent attention's (``GPTConfig.mla``): one
    compressed latent and one rotary key a position."""

    def __init__(self, feature: str, why: str):
        super().__init__(
            f"{feature} cannot serve a model with a latent cache "
            f"(cached_latent, cached_rope_key: GPTConfig.mla): {why}")
        self.feature = feature


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention IN PLACE OF ``CausalSelfAttention`` as a
    block's token mixer (models/latent_attention.py; DeepSeek-V2,
    arXiv:2405.04434): queries and keys/values come through low-rank
    projections, a token's keys and values of ALL heads are decompressed
    from one ``kv_rank``-wide latent, and position enters through a
    decoupled ``rope_dim``-wide rotary part, whose key is one vector a
    token, shared by the heads. A lane's cache holds that latent (after its
    norm) and that rotary key (after rotary) per position and layer,
    ``kv_rank + rope_dim`` values, and nothing per head. The heads, the
    norm's epsilon and ``rope_theta`` are the model's own. One form, the
    published one: a norm on both latents, no bias."""
    q_rank: int             # q_lora_rank
    kv_rank: int            # kv_lora_rank
    nope_dim: int           # qk_nope_head_dim
    rope_dim: int           # qk_rope_head_dim
    v_dim: int              # v_head_dim
    # YaRN on the rotary part (ops/rotary.py); factor 1 = plain rotary.
    # ``yarn_mscale_all_dim`` enters the softmax scale squared, the ratio
    # of the two mscales multiplies cos and sin
    yarn_factor: float = 1.0
    yarn_original_positions: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    def __post_init__(self):
        if self.rope_dim % 2:
            raise ValueError(f"rope_dim must be even; got {self.rope_dim}")

    def cache_leaves(self, cfg) -> Tuple[CacheLeaf, ...]:
        """(models/latent_attention.py) A latent and a rotary key a
        position, no heads: part of ``kv_bytes`` and, apart,
        ``latent_bytes_per_lane``."""
        from deepspeed_tpu.models.latent_attention import (
            CACHED_LATENT,
            CACHED_ROPE_KEY,
        )

        return tuple(CacheLeaf(name, 3, "position", ("latent",),
                               SCOPE_KV_CACHE_CARRY)
                     for name in (CACHED_LATENT, CACHED_ROPE_KEY))

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    @property
    def softmax_scale(self) -> float:
        from deepspeed_tpu.ops.rotary import yarn_mscale

        m = yarn_mscale(self.yarn_factor, self.yarn_mscale_all_dim)
        return self.qk_dim ** -0.5 * m * m

    @property
    def rope_mscale(self) -> float:
        from deepspeed_tpu.ops.rotary import yarn_mscale

        return yarn_mscale(self.yarn_factor, self.yarn_mscale) \
            / yarn_mscale(self.yarn_factor, self.yarn_mscale_all_dim)

    def inv_freq(self, theta: float):
        """The rotary part's inverse frequencies, None for the plain
        ladder."""
        from deepspeed_tpu.ops.rotary import yarn_inv_freq

        if self.yarn_factor <= 1:
            return None
        return yarn_inv_freq(self.rope_dim, theta, self.yarn_factor,
                             self.yarn_original_positions,
                             self.yarn_beta_fast, self.yarn_beta_slow)


class IndexKeyError(ValueError):
    """A feature that assumes a lane keeps keys and values alone was asked
    of a model that keeps an index key beside them
    (``GPTConfig.indexer``)."""

    def __init__(self, feature: str, why: str):
        super().__init__(
            f"{feature} cannot serve a model that keeps an index key a "
            f"position (cached_index_key: GPTConfig.indexer): {why}")
        self.feature = feature


@dataclasses.dataclass(frozen=True)
class IndexerConfig:
    """A lightning indexer beside ``CausalSelfAttention`` (models/
    indexer.py over ops/indexed_attention.py; DeepSeek-V3.2-Exp's sparse
    attention, Keye-VL-2.0's ``sa_config``): ``n_heads`` small query heads
    and ONE key of ``head_dim`` a position score every cached position for
    a query, the ``topk`` best are chosen (all of them while a query sees
    no more) and attention runs over those rows alone. The key, after its
    norm and rotary, is the third thing a lane keeps a position. A cache
    of at most ``topk`` positions chooses all of them whatever the scores,
    and the model then runs plain attention."""
    n_heads: int            # indexer_num_heads
    head_dim: int           # indexer_head_dim
    topk: int
    # the tiling of a pass of many queries; change no value
    q_chunk: int = 512
    kv_chunk: int = 512
    # how many of each index head's dimensions rotary turns, the first
    # ones (DeepSeek-V3.2-Exp: its ``qk_rope_head_dim`` of 128); None = all
    rope_dim: Optional[int] = None

    def __post_init__(self):
        if self.head_dim % 2 or min(self.n_heads, self.topk, self.q_chunk,
                                    self.kv_chunk) < 1 or (
                self.rope_dim is not None
                and not 0 < self.rope_dim <= self.head_dim):
            raise ValueError(f"no indexer has these sizes: {self}")

    def cache_leaves(self, cfg) -> Tuple[CacheLeaf, ...]:
        """One key a position and layer, no heads, in the compute dtype:
        part of ``kv_bytes`` and, apart, ``index_key_bytes_per_lane``;
        and, where a choice is made, what the decode program says of its
        own selection, a layer: the rows a lane's last decode query
        attended over (``chosen_rows`` ``[B, topk]`` int32, -1 where it
        saw fewer) and the indexer's query and weights it scored them
        with (``choice_query`` ``[B, n_heads, head_dim]``,
        ``choice_weights`` ``[B, n_heads]`` float32)."""
        from deepspeed_tpu.models import indexer

        leaves = (CacheLeaf(indexer.CACHED_INDEX_KEY, 3, "position",
                            ("index",), SCOPE_KV_CACHE_CARRY),)
        if self.engaged(cfg):
            leaves += (
                CacheLeaf(indexer.CHOSEN_ROWS, 2, "step", (),
                          SCOPE_KV_CACHE_CARRY, dtype=jnp.int32, unset=-1),
                CacheLeaf(indexer.CHOICE_QUERY, 3, "step", (),
                          SCOPE_KV_CACHE_CARRY),
                CacheLeaf(indexer.CHOICE_WEIGHTS, 2, "step", (),
                          SCOPE_KV_CACHE_CARRY, dtype=jnp.float32))
        return leaves

    def sections(self, cfg) -> Optional[Tuple[int, ...]]:
        """The model's rotary sections (``GPTConfig.mrope_section``) in the
        indexer's own ladder of ``head_dim / 2`` frequencies: the same
        proportions."""
        if cfg.mrope_section is None:
            return None
        out = tuple(s * self.head_dim // cfg.head_dim
                    for s in cfg.mrope_section)
        if sum(out) != self.head_dim // 2:
            raise ValueError(
                f"mrope_section {cfg.mrope_section} of a head of "
                f"{cfg.head_dim} has no whole counterpart in an indexer "
                f"head of {self.head_dim}")
        return out

    def engaged(self, cfg) -> bool:
        """Whether a cache of ``cfg.n_positions`` can hold more than
        ``topk`` positions, so that the choice is one."""
        return cfg.n_positions > self.topk

    @property
    def weight_scale(self) -> float:
        return self.n_heads ** -0.5 * self.head_dim ** -0.5


@dataclasses.dataclass(frozen=True)
class LatentKind:
    """Latent attention as ONE KIND of layer's mixer, in a stack whose
    attention layers differ by kind (``GPTConfig.latent_kinds``, read back
    through ``GPTConfig.attention_kind``; run by models/latent_attention.py
    ``KindLatentAttention``): everything the whole-model fields ``n_head``,
    ``mla``, ``rope_theta`` and ``indexer`` say of a stack of one kind, said
    of the kind, so that 128 heads over a 512-wide latent read through an
    indexer can stand beside 64 heads over a 1,024-wide latent that sees a
    window. The window itself (``sliding_window``, ``window_slack``) stays
    where the ``"window"`` kind has it."""
    n_head: int
    mla: MLAConfig
    rope_theta: float
    # a lightning indexer whose queries come from the QUERY LATENT ``c_q``
    # (DeepSeek-V3.2-Exp) and whose chosen rows are latents; None = every
    # position the kind sees
    indexer: Optional[IndexerConfig] = None
    # one sigmoid gate a head on the heads' outputs before ``c_proj``, from
    # the layer's normalised input through ``c_gate`` ``[C, n_head]``
    head_gate: bool = False
    # the two normed latents times ``(n_embd / rank) ** 0.5``, each with
    # its own rank (LongCat-Flash's ``mla_scale_q_lora`` / ``_kv_lora``)
    rank_rescale: bool = False

    def up_init(self, n_embd: int):
        """How the matrices that read one of the kind's normed latents are
        born (``q_b``, ``kv_b``, the indexer's ``wq``). A RESCALED latent
        (``rank_rescale``) is ``(n_embd / rank) ** 0.5`` times a unit one,
        the size a projection of the hidden state has, so its readers are
        born at the hidden size's scale, as readers of the hidden state
        are; at their own fan-in's scale the scores' spread is ``n_embd /
        rank`` too wide and the softmax a near-argmax that bf16 rounding
        flips (PERF.md, PR 59: the readings at both)."""
        return nn.initializers.normal(n_embd ** -0.5) if self.rank_rescale \
            else nn.initializers.lecun_normal()

    def cache_leaves(self, cfg, held_by, window: bool
                     ) -> Tuple[CacheLeaf, ...]:
        """The kind's latent and rotary key (a ring's rows counted as
        ``window`` too) and, with an indexer, its key and what a step
        leaves of its selection, each held by ``held_by``."""
        counted = ("latent", "window") if window else ("latent",)
        leaves = tuple(dataclasses.replace(leaf, counted_as=counted)
                       for leaf in self.mla.cache_leaves(cfg))
        if self.indexer is not None:
            leaves += self.indexer.cache_leaves(cfg)
        return tuple(dataclasses.replace(leaf, held_by=held_by)
                     for leaf in leaves)


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    mlp_ratio: int = 4
    layer_norm_epsilon: float = 1e-5  # HF GPT-2 default
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # --- architecture family knobs (GPT-2 defaults) ------------------------
    # GPT-J/NeoX/OPT/LLaMA-family variants are the same block with these
    # toggled; the HF injection policies (module_inject/hf.py) set them
    intermediate_size: Optional[int] = None  # None -> mlp_ratio * n_embd
    norm: str = "layernorm"            # "layernorm" | "rmsnorm" (LLaMA)
    activation: str = "gelu_tanh"      # "gelu_tanh"|"gelu"|"relu"|"silu"
                                       # |"quick_gelu" (CLIP)
    causal: bool = True                # False = bidirectional (encoders)
    gated_mlp: bool = False            # SwiGLU: act(gate) * up (LLaMA)
    use_bias: bool = True              # biases on dense + norm layers
    attn_bias: Optional[bool] = None   # override for attention projections
                                       # (GPT-J: biasless attn, biased MLP)
    alibi: bool = False                # ALiBi attention bias (BLOOM)
    embed_layernorm: bool = False      # LN right after wte (BLOOM)
    rotary: bool = False               # rotary embeddings (ops/rotary.py)
    rotary_pct: float = 1.0            # fraction of head_dim rotated (NeoX)
    rotary_interleaved: bool = False   # GPT-J even/odd pairing
    rope_theta: float = 10000.0
    learned_positions: bool = True     # wpe table (off for rotary models)
    tie_word_embeddings: bool = True
    lm_head_bias: bool = False         # GPT-J's untied head carries a bias
    parallel_residual: bool = False    # x + attn(ln_1 x) + mlp(ln_2 x)
    n_kv_head: Optional[int] = None    # grouped-query attention; None = MHA
    qk_norm: Any = False               # RMSNorm over the whole q and k
                                       # projections, before rotary (OLMoE);
                                       # "head": over each head (Qwen3)
    remat: bool = False
    # "full" recomputes everything (min memory); "selective" saves matmul
    # outputs and recomputes only elementwise ops — the TPU sweet spot:
    # MXU work is saved, cheap VPU work is redone
    remat_policy: str = "full"
    scan_layers: bool = True
    # Pallas flash kernel path (ops/pallas). True | False | "auto" —
    # auto picks per shape from the measured crossover: XLA einsum wins at
    # short seq (the whole [T,T] score matrix tiles well), flash wins from
    # FLASH_AUTO_MIN_SEQ up (benchmarks/flash_sweep.py: GPT-2 125M on one
    # v5e chip — seq 128: 56 vs 45 TFLOPS for XLA; 512: 49 vs 45 flash;
    # 2048: 47 vs 25; 4096: 48 vs 12)
    use_flash_attention: Any = False
    # chunked online-softmax attention (ops/chunked_attention.py): bounded
    # O(T * chunk) score memory in plain XLA — the long-context path where
    # the flash kernel's VMEM ceiling binds (seq > 8192 on the current
    # toolchain). An int sets the KV chunk size and takes precedence over
    # the flash path; None disables.
    attention_chunk: Optional[int] = None
    # ZeRO-Infinity parameter tier (ops/streaming.py): layer-stack params
    # live in host memory; the scan streams one layer into HBM per step.
    # Pair with ds_config zero_optimization.offload_param (engine places
    # the shardings in pinned_host). Requires scan_layers.
    param_offload: bool = False
    # sequence/context parallelism over the sp mesh axis
    # (parallel/sequence.py): "none" | "ring" | "ulysses"
    sequence_parallel: str = "none"
    # fused LM-head + cross entropy (ops/cross_entropy.py
    # fused_linear_cross_entropy): never materializes the [tokens, vocab]
    # logits. True | False | "auto". The chunked head scan has a real
    # cost — measured on one v5e chip: ~0.7% at seq 1024 (1.3B A/B) and
    # 1.5x step time at seq 16k/125M where full remat + chunked attention
    # mean logits were not the binding buffer anyway — so "auto" engages
    # only when the slab (tokens x vocab x itemsize, global batch) reaches
    # 4 GB. There it WINS: a 256k-vocab model (seq 4096) measures 2.5%
    # faster at micro 2 (4.3 GB slab) and 7% at micro 4 (8.6 GB) than the
    # dense head, with identical losses.
    # An int >= 1 forces it with that token chunk size (default 2048);
    # 0/False disable.
    fused_head_ce: Any = "auto"
    # block-sparse attention (ops.sparse_attention): a SparsityConfig
    # restricting attention to its block layout — causality is enforced
    # on top regardless of the layout's symmetry. Populated from the
    # DeepSpeed "sparse_attention" config block (see models/bert.py for
    # the encoder-side story).
    sparse_attention: Any = None
    # layout-aware KV cache for decode: window(+leading-global) layouts
    # retain only the G + (w+1)*block slots the layout can ever attend
    # (a block-granular ring), reproducing the TRAINING sparse math
    # exactly while cutting cache memory n_positions/(G+(w+1)*block)-fold.
    # "auto" engages when the layout is expressible (sliding-window,
    # leading-global longformer) and the ring is smaller than the dense
    # cache; True demands it (ValueError if the layout cannot express
    # it — e.g. BigBird's random links); False always decodes dense.
    sparse_kv_cache: Any = "auto"
    # weight-only int8 serving (reference int8 GEMM inference kernels,
    # csrc/transformer/inference/csrc/pt_binding.cpp:1535): block matmul
    # kernels are STORED as {"q": int8, "scale": f32[out]} and dequantized
    # per layer INSIDE the scan body (nn.map_variables), where XLA fuses
    # the convert into the consuming dots — per-token HBM weight traffic
    # stays int8. Dequantizing the whole stacked [L, ...] tree outside the
    # layer scan instead materializes a full bf16 copy per decode step
    # (measured 2x SLOWER than bf16 at 1.3B). Inference-only flag, set by
    # init_inference(dtype="int8"); composes with tp>1 (the {q, scale}
    # leaves shard like the dense kernel they replace, see
    # runtime/zero/sharding.py _quantized_leaf_spec).
    quantized_weights: bool = False
    # int8 KV cache for decode (serving capacity lever, see
    # serving/disagg.py): cache leaves are STORED int8 with one f32 scale
    # per (row, slot, kv-head) — the same symmetric blockwise format as
    # the compressed wire (ops/quantizer.quantize_blockwise, block =
    # head_dim) — and dequantized on read inside the attention einsum.
    # Per-slot HBM drops from 2*D*2 bytes (bf16) to 2*(D + 4) bytes,
    # ~1.94x more lanes at D=128 under the same budget (~3.88x vs fp32).
    # None keeps the cache in the compute dtype; "int8" quantizes. The
    # cache PROTOCOL (leaf shapes minus dtype, splice axes, slot clocks)
    # is unchanged, so the scheduler's jitted _splice and the prefix
    # cache work as-is.
    kv_cache_dtype: Any = None
    # extra STORAGE blocks in the ring KV cache beyond the w_blk + 1 the
    # window visibility needs (sparse_attention_utils.ring_storage_len).
    # Semantically invisible — visibility is positional — but >= 1 makes
    # the speculative-decode verify pass (an unaligned multi-token
    # mid-stream write) exact; the continuous-batching scheduler demands
    # it when spec decoding a ring model.
    kv_cache_slack_blocks: int = 0
    # stochastic transformer (reference op_builder/stochastic_transformer.py,
    # ops/transformer/transformer.py:110 stochastic_mode): whole-block
    # stochastic depth. When training under a progressive-layer-drop
    # schedule the engine feeds ``pld_theta`` (computed IN-GRAPH from the
    # step counter — no per-step host transfer) and each layer i survives
    # with p_i = 1 - (i/L)(1 - theta), gated by an explicit per-layer key
    # from the scan's split rng stream. ``jax.remat`` replays the same key
    # at recompute, so gradients stay exact — the determinism the CUDA
    # kernel's stochastic mode gives up, for free.
    stochastic_mode: bool = False
    # MoE (reference deepspeed/moe/): 0 experts = dense MLP everywhere
    moe_num_experts: int = 0
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.0
    moe_eval_capacity_factor: float = 1.0
    moe_min_capacity: int = 4
    moe_drop_tokens: bool = True
    moe_aux_loss_coef: float = 0.01
    moe_noisy_gate_policy: Optional[str] = None
    moe_use_rts: bool = True
    moe_gated_experts: bool = False  # SwiGLU experts (Mixtral-style)
    # the dropless path (moe/layer.py: moe_top_k > 2 or not
    # moe_drop_tokens): whether the k weights are divided by their sum
    moe_norm_topk_prob: bool = False
    # the coefficient of the router z-loss, beside moe_aux_loss_coef
    moe_z_loss_coef: float = 0.0
    # the dropless path, as DeepSeek-V2's expert layer has it (moe/layer.py
    # says what each does): the experts' width where it is not the dense
    # MLP's; shared experts beside the routed ones; the choice limited to
    # the best ``moe_topk_group`` of ``moe_n_group`` consecutive groups; a
    # factor on the weights; and which of the ``moe_num_experts`` that the
    # router scores this program HOLDS, ``(first, count)``: one device's
    # share of an expert-parallel layer, whose routed part it computes
    # alone (None = all)
    moe_intermediate_size: Optional[int] = None
    moe_n_shared: int = 0
    moe_n_group: int = 1
    moe_topk_group: int = 1
    moe_routed_scale: float = 1.0
    moe_experts_held: Optional[Tuple[int, int]] = None
    # the first ``first_k_dense`` blocks hold a dense MLP (``ffn_dim``)
    # whatever ``moe_num_experts`` says; under ``scan_layers`` they run
    # before the scanned stack of the rest
    first_k_dense: int = 0
    # the dropless path's scoring: "softmax" over all experts, or
    # "sigmoid" of each expert's own logit (moe/sharded_moe.py
    # ``topk_routing``); and a per-expert bias, a parameter of the layer,
    # that is added to the scores for the CHOICE alone (the weights are the
    # uncorrected scores'), drawn at ``init`` from a normal of this
    # standard deviation (0.0: zeros, as a model is published before its
    # balancing has moved it)
    moe_scoring: str = "softmax"
    moe_expert_bias: bool = False
    moe_expert_bias_init: float = 0.0
    # what sigmoid scoring adds to the chosen scores' sum before it
    # divides by it; None = the 1e-6 of ``topk_routing``
    moe_renorm_eps: Optional[float] = None
    # what the router's logits are computed from: "mlp", what the experts
    # read (``ln_2``'s output), or "block", the block's own input, before
    # its first norm and its mixer (a router placed before attention)
    moe_router_input: str = "mlp"
    # the experts' activation by name (``activation``'s names); None = gelu,
    # or silu where the experts are gated
    moe_expert_activation: Optional[str] = None
    # --- which token mixer a layer runs --------------------------------------
    # Declared once, here, and read by ``Block`` (its ``mixer`` field), by
    # whoever runs the layers and by ``cache_leaves``. ``layer_types`` None
    # is a stack of ONE kind, chosen by the whole-model fields below
    # (``mla``, ``retention``, else attention, with ``ssm`` beside it and
    # ``indexer`` inside it) and run by ``ScannedBlocks``. A tuple of
    # ``n_layer`` kinds ("attention" | "conv" | "window") is a stack that
    # MIXES kinds (models/kind_stacks.py ``KindStackedBlocks``): parameters
    # and cache leaves are stacked per kind, so an attention layer keeps
    # keys and values and no tail, a convolution layer a tail and no keys
    layer_types: Optional[Tuple[str, ...]] = None
    # the "conv" kind's mixer: a gated short convolution (LFM2)
    short_conv: Optional[ShortConvConfig] = None
    # the "window" kind: attention over the newest ``sliding_window``
    # positions, the query's own included, exact by position (not the block
    # layout of ``sparse_attention``). A stack that has the kind runs its
    # "window" AND its "attention" layers through models/kind_attention.py
    # (``attention_kind``): a lane keeps ``sliding_window + window_slack``
    # rows of a window layer whatever ``n_positions`` is, and every
    # position of an "attention" layer. The slack is what a pass of several
    # tokens on a cache needs: ``window_slack + 1`` tokens at most evict no
    # row that one of them still attends over. ``rotary_kinds`` names the
    # kinds whose q and k are rotated (None: all, where ``rotary``)
    sliding_window: Optional[int] = None
    window_slack: int = 0
    rotary_kinds: Optional[Tuple[str, ...]] = None
    # such a stack's attention output times the sigmoid of a projection
    # ``c_gate`` of the layer's normalised input, before ``c_proj``
    attn_output_gate: bool = False
    # such a stack's attention layers as LATENT attention, each kind with
    # its own heads, ranks, widths, ``rope_theta`` and indexer: ``((kind,
    # LatentKind), ...)`` for "attention" and "window" both (``n_head``,
    # ``mla``, ``indexer`` and ``rope_theta`` then say nothing of a layer).
    # Read back through ``attention_kind`` alone
    latent_kinds: Optional[Tuple[Tuple[str, LatentKind], ...]] = None
    # a norm on the mixer's output and one on the MLP's, each before its
    # residual sum (``ln_1_post``, ``ln_2_post``): four norms a layer
    post_norms: bool = False
    # --- hybrid blocks (Falcon-H1) -----------------------------------------
    # a Mamba-2 mixer beside attention in every block, both on ln_1's
    # output, summed into one residual; None = attention alone. Its
    # recurrent state and convolution tail live in the decode cache beside
    # keys and values and cannot be rewound to a shorter prefix
    ssm: Optional[SSMConfig] = None
    # --- attention-free blocks (Brumby) -------------------------------------
    # power retention in place of attention as the token mixer; None =
    # attention. A lane's cache is then the retention's state alone
    retention: Optional[RetentionConfig] = None
    # --- latent attention (DeepSeek-V2) --------------------------------------
    # multi-head latent attention in place of attention; None = attention.
    # A lane's cache is then one compressed latent and one rotary key a
    # position and layer, nothing per head
    mla: Optional[MLAConfig] = None
    # --- attention over a chosen few positions (Keye-VL-2.0) ----------------
    # a lightning indexer beside attention; None = every position. A lane
    # then keeps an index key a position beside keys and values
    indexer: Optional[IndexerConfig] = None
    # sectioned rotary (``mrope_section``): of the rotary frequencies the
    # first ``s[0]`` take their angle from the temporal position, the next
    # ``s[1]`` from the height, the rest from the width, where a call
    # hands ``positions [3, B, T]``; a decode call's positions are the
    # lane's clock, every stream's alike (text)
    mrope_section: Optional[Tuple[int, ...]] = None
    # attention head size when it is not n_embd // n_head
    attn_head_dim: Optional[int] = None
    # muP multipliers, each applied where the published model applies it;
    # 1.0 leaves the program as it is
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    mlp_gate_multiplier: float = 1.0
    mlp_down_multiplier: float = 1.0
    # a decode-path apply (prefill, decode step) computes the head at the
    # last ``num_logits_to_keep`` positions only; None = every position
    num_logits_to_keep: Optional[int] = None

    def __post_init__(self):
        if self.sequence_parallel not in ("none", "ring", "ulysses"):
            raise ValueError(
                f"sequence_parallel must be 'none', 'ring', or 'ulysses'; "
                f"got {self.sequence_parallel!r}")
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"unknown norm {self.norm!r}")
        if self.activation not in _ACTIVATIONS:
            raise ValueError(f"unknown activation {self.activation!r}")
        if self.n_kv_head is not None and self.n_head % self.n_kv_head:
            raise ValueError(
                f"n_head ({self.n_head}) must be divisible by n_kv_head "
                f"({self.n_kv_head})")
        if self.param_offload and not self.scan_layers:
            raise ValueError(
                "param_offload streams layer slices out of the scan; it "
                "requires scan_layers=True")
        if self.use_flash_attention not in (True, False, "auto"):
            raise ValueError(
                f"use_flash_attention must be True, False or 'auto'; got "
                f"{self.use_flash_attention!r}")
        if self.sparse_attention is not None and self.alibi:
            raise ValueError(
                "sparse_attention does not compose with alibi (the "
                "block-sparse path has no positional-bias hook); a silent "
                "dense fallback would change the model's math, so this is "
                "rejected up front")
        if self.attention_chunk is not None and (
                not isinstance(self.attention_chunk, int)
                or self.attention_chunk <= 0):
            raise ValueError(
                f"attention_chunk must be a positive int or None; got "
                f"{self.attention_chunk!r}")
        if self.kv_cache_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_cache_dtype must be None or 'int8'; got "
                f"{self.kv_cache_dtype!r}")
        if not isinstance(self.kv_cache_slack_blocks, int) or \
                self.kv_cache_slack_blocks < 0:
            raise ValueError(
                f"kv_cache_slack_blocks must be a non-negative int; got "
                f"{self.kv_cache_slack_blocks!r}")
        if self.sparse_kv_cache not in ("auto", True, False):
            raise ValueError(
                f"sparse_kv_cache must be 'auto', True or False; got "
                f"{self.sparse_kv_cache!r}")
        if self.sparse_kv_cache is True:
            from deepspeed_tpu.ops.sparse_attention.sparse_attention_utils \
                import ring_decode_params

            if (self.sparse_attention is None
                    or ring_decode_params(self.sparse_attention) is None):
                raise ValueError(
                    "sparse_kv_cache=True needs a ring-expressible layout "
                    "(causal sliding-window, or longformer with leading "
                    "global blocks); BigBird's random links cannot be "
                    "served from a bounded ring — use 'auto' to fall back "
                    "to the dense cache")

        if self.retention is not None and (
                not self.rotary or self.learned_positions or self.alibi
                or self.sparse_attention is not None or not self.causal
                or self.ssm is not None):
            raise ValueError(
                "a retention block is causal, takes its positions from "
                "rotary alone and has no second mixer")

        if self.mla is not None:
            if (not self.rotary or self.learned_positions or self.alibi
                    or self.sparse_attention is not None or not self.causal
                    or self.retention is not None or self.ssm is not None
                    or self.sequence_parallel != "none"):
                raise ValueError(
                    "a latent-attention block is causal, takes its "
                    "positions from its rotary part alone and has no "
                    "second mixer")
            if self.kv_cache_dtype is not None:
                raise LatentCacheError(
                    f"kv_cache_dtype={self.kv_cache_dtype!r}",
                    "the int8 format keeps one scale per (position, KV "
                    "head) over a head's values, and a latent has no "
                    "heads: the latent is the model's own compression of "
                    "the cache, and a format for it would be another")
        if self.indexer is not None:
            if (not self.rotary or self.learned_positions or self.alibi
                    or self.sparse_attention is not None or not self.causal
                    or self.retention is not None or self.mla is not None
                    or self.sequence_parallel != "none"
                    or self.rotary_interleaved):
                raise ValueError(
                    "an indexer sits beside causal attention with keys "
                    "and values per head and plain rotary positions")
            self.indexer.sections(self)     # raises for sections it lacks
        if self.moe_router_input not in ("mlp", "block"):
            raise ValueError(
                f"moe_router_input must be 'mlp' or 'block'; got "
                f"{self.moe_router_input!r}")
        if self.moe_expert_activation not in (None, *_ACTIVATIONS):
            raise ValueError(
                f"unknown moe_expert_activation "
                f"{self.moe_expert_activation!r}")
        if self.moe_scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                f"moe_scoring must be 'softmax' or 'sigmoid'; got "
                f"{self.moe_scoring!r}")
        if self.layer_types is not None:
            kinds = set(self.layer_types)
            if len(self.layer_types) != self.n_layer \
                    or not kinds <= {KIND_ATTENTION, KIND_CONV, KIND_WINDOW}:
                raise ValueError(
                    f"layer_types names a kind ({KIND_ATTENTION!r} | "
                    f"{KIND_CONV!r} | {KIND_WINDOW!r}) for each of the "
                    f"{self.n_layer} layers; got {self.layer_types!r}")
            if KIND_CONV in kinds and self.short_conv is None:
                raise ValueError("a 'conv' layer needs short_conv")
            if KIND_WINDOW in kinds:
                self._check_window_kind(kinds)
            unmixable = [name for name in (
                "mla", "retention", "ssm", "indexer", "sparse_attention")
                if getattr(self, name) is not None] + [
                name for name in ("quantized_weights", "param_offload",
                                  "stochastic_mode", "parallel_residual")
                if getattr(self, name)]
            if unmixable or not self.scan_layers:
                raise ValueError(
                    "a stack that mixes kinds of layer "
                    "(models/kind_stacks.py) runs attention and "
                    "convolution layers with their weights as stored, "
                    f"scanned; not with {unmixable or 'scan_layers=False'}")
        if (KIND_WINDOW not in (self.layer_types or ())
                and (self.sliding_window is not None or self.window_slack
                     or self.rotary_kinds is not None
                     or self.attn_output_gate
                     or self.latent_kinds is not None)):
            raise ValueError(
                "sliding_window, window_slack, rotary_kinds, "
                "attn_output_gate and latent_kinds belong to a stack with "
                f"layers of kind {KIND_WINDOW!r} (layer_types)")
        if self.qk_norm not in (False, True, "head"):
            raise ValueError(
                f"qk_norm must be False, True or 'head'; got "
                f"{self.qk_norm!r}")
        if not 0 <= self.first_k_dense <= self.n_layer:
            raise ValueError(
                f"first_k_dense ({self.first_k_dense}) must lie in 0.."
                f"n_layer ({self.n_layer})")
        if self.moe_experts_held is not None:
            first, count = self.moe_experts_held
            if not (0 <= first and count >= 1
                    and first + count <= self.moe_num_experts):
                raise ValueError(
                    f"moe_experts_held {self.moe_experts_held} is no run "
                    f"of the {self.moe_num_experts} experts")

    def _check_window_kind(self, kinds):
        """What a stack with ``"window"`` layers has to say, and what
        models/kind_attention.py does not run."""
        if not isinstance(self.sliding_window, int) \
                or self.sliding_window < 1 or self.window_slack < 0:
            raise ValueError(
                f"a {KIND_WINDOW!r} layer needs sliding_window >= 1 and "
                f"window_slack >= 0; got {self.sliding_window!r}, "
                f"{self.window_slack!r}")
        if self.rotary_kinds is not None and not (
                self.rotary and set(self.rotary_kinds) <= kinds):
            raise ValueError(
                f"rotary_kinds {self.rotary_kinds!r} names kinds of "
                f"{sorted(kinds)} whose q and k the model's rotary turns")
        unbuilt = [name for name, on in (
            ("alibi", self.alibi), ("causal=False", not self.causal),
            ("learned_positions", self.learned_positions),
            ("mrope_section", self.mrope_section is not None),
            ("qk_norm=True", self.qk_norm is True),
            ("sequence_parallel", self.sequence_parallel != "none"),
            ("dropout", self.dropout > 0)) if on]
        if unbuilt:
            raise ValueError(
                "window layers beside full ones "
                "(models/kind_attention.py) are causal, with positions "
                f"from rotary or from none; not built over them: {unbuilt}")
        if self.kv_cache_dtype is not None:
            raise MixedCacheError(
                f"kv_cache_dtype={self.kv_cache_dtype!r}",
                "the int8 format keeps one scale a (position, KV head) "
                "beside rows that stay where they were written; a ring's "
                "rows are overwritten in turn and models/kind_attention.py "
                "stores and reads the compute dtype alone")
        if self.latent_kinds is not None:
            declared = dict(self.latent_kinds)
            attending = kinds & {KIND_ATTENTION, KIND_WINDOW}
            if set(declared) != attending or len(declared) != len(
                    self.latent_kinds) or not all(
                    isinstance(k, LatentKind) for k in declared.values()):
                raise ValueError(
                    "latent_kinds declares a LatentKind for each kind of "
                    f"attention layer the stack has ({sorted(attending)}), "
                    f"once; got {self.latent_kinds!r}")
            taken = [name for name, on in (
                ("rotary=False", not self.rotary),
                ("rotary_kinds", self.rotary_kinds is not None),
                ("attn_output_gate", self.attn_output_gate),
                ("qk_norm", self.qk_norm), ("n_kv_head", self.n_kv_head),
                ("rotary_interleaved", self.rotary_interleaved)) if on]
            if taken:
                raise ValueError(
                    "latent attention by kind (models/latent_attention.py "
                    "KindLatentAttention) takes its positions from each "
                    "kind's rotary part, gates a head where the kind says "
                    f"so and has no keys or values per head; not: {taken}")
            if KIND_WINDOW in declared and \
                    declared[KIND_WINDOW].indexer is not None:
                raise ValueError(
                    "an indexer chooses among every cached position; a "
                    f"{KIND_WINDOW!r} layer's ring holds its window alone")

    def attention_kind(self, mixer: Optional[str]) -> Optional[AttentionKind]:
        """What the attention of a layer of kind ``mixer`` is, in a stack
        whose attention layers differ by kind (one that has ``"window"``
        layers); None everywhere else: the whole-model fields say it and
        ``CausalSelfAttention`` runs it. The one place that knows which
        kind has a window, a ring or rotary and, where the kinds are latent
        attention (``latent_kinds``), the kind's heads, ranks, widths,
        ``rope_theta`` and indexer."""
        if self.layer_types is None or KIND_WINDOW not in self.layer_types \
                or mixer not in (KIND_WINDOW, KIND_ATTENTION):
            return None
        rotary = self.rotary and (self.rotary_kinds is None
                                  or mixer in self.rotary_kinds)
        latent = dict(self.latent_kinds or ()).get(mixer)
        if mixer == KIND_WINDOW:
            return AttentionKind(
                self.sliding_window, self.sliding_window + self.window_slack,
                rotary, SCOPE_WINDOW_ATTN if latent is None
                else SCOPE_WINDOW_LATENT_ATTN, latent)
        return AttentionKind(None, None, rotary, SCOPE_FULL_ATTN
                             if latent is None else SCOPE_SPARSE_LATENT_ATTN,
                             latent)

    @property
    def pass_tokens(self) -> Optional[int]:
        """The most tokens one pass over a cache may take in (None: any
        number): a window layer's ring holds ``window_slack`` rows beyond
        the window, so a longer pass would overwrite rows that its own
        first query still attends over; and a pass of that length holds
        ``[pass, n_positions]`` scores a head, never ``[T, T]``
        (inference/engine.py ``prefill_chunk_spans``)."""
        kind = self.attention_kind(KIND_WINDOW)
        return None if kind is None else kind.pass_tokens

    @property
    def cache_leaves(self) -> Tuple[CacheLeaf, ...]:
        """What a lane keeps in the decode cache, as the model's mixers
        declare it: attention's keys and values, or latent attention's
        latent and rotary key, or none where retention is the mixer, and
        the state of the mixers that keep one. The one place that knows
        which mixers a block runs."""
        if self.layer_types is not None:
            # by kind of layer: each leaf says which kind holds it
            kinds = set(self.layer_types)
            if self.latent_kinds is not None:
                attention = tuple(
                    leaf for mixer, kind in self.latent_kinds
                    for leaf in kind.cache_leaves(
                        self, mixer, window=mixer == KIND_WINDOW))
            else:
                attention = tuple(
                    dataclasses.replace(leaf, held_by=KIND_ATTENTION)
                    for leaf in attention_cache_leaves(self)
                    if KIND_ATTENTION in kinds) + (
                    window_cache_leaves() if KIND_WINDOW in kinds else ())
            return attention + (self.short_conv.cache_leaves(self)
                                if KIND_CONV in kinds else ())
        attention = (self.mla.cache_leaves(self) if self.mla is not None
                     else () if self.retention is not None
                     else attention_cache_leaves(self))
        return attention + tuple(
            leaf for mixer in (self.indexer, self.ssm, self.retention)
            if mixer is not None for leaf in mixer.cache_leaves(self))

    def layers_holding(self, leaf: CacheLeaf) -> int:
        """How many layers keep ``leaf`` (one of ``cache_leaves``)."""
        if leaf.held_by is None or self.layer_types is None:
            return self.n_layer
        return self.layer_types.count(leaf.held_by)

    @property
    def position_leaves(self) -> Tuple[Tuple[str, int], ...]:
        """``(name, rank)`` of the leaves that hold a model's own values
        per position (keys and values, or latents; no sideband)."""
        return tuple((leaf.name, leaf.rank) for leaf in self.cache_leaves
                     if leaf.kind == "position"
                     and "sideband" not in leaf.counted_as)

    @property
    def recurrent_leaves(self) -> Tuple[CacheLeaf, ...]:
        return tuple(leaf for leaf in self.cache_leaves
                     if leaf.kind == "recurrent")

    @property
    def prefill_bucket_dependence(self) -> Optional[str]:
        """What may make the ``[1, T]`` prefill from an empty cache another
        program at another ``T`` than the same operations over other
        shapes, None where nothing does: then ONE tracing with the token
        count a symbol serves every prompt bucket (inference/engine.py
        ``plan_prefill``), else each bucket is traced. None for dense
        causal attention over a head's own keys and values with a dense
        feed-forward, the one kind of model whose program from one tracing
        has been held to each bucket's own to the bit and measured
        (PERF.md section 6, PR 62); every other mixer is refused outright,
        one clause each, with the shape decision it takes from the token
        count in Python where that is known. A prefill that comes to hold a
        Pallas call needs a number where it has ``T`` (the kernel's grid,
        its cost estimate): say so here, and it is traced a bucket."""
        for declared, why in (
                (self.sparse_attention,
                 "a sparse layout's blocks and its ring's length against T "
                 "decide the passes"),
                (self.is_moe or None,
                 "the experts' capacity and their grouped-matmul kernel's "
                 "rows are numbers computed from T"),
                (self.layer_types,
                 "layers of more than one kind: window layers prefill in "
                 "passes of one size, a short convolution keeps a tail"),
                (self.ssm,
                 "ssd_chunked_scan pads T to whole chunks and scans their "
                 "number: a loop of another length at another T"),
                (self.retention,
                 "retention_chunked takes min(chunk, T) and branches on "
                 "the padding in Python"),
                (self.indexer,
                 "the indexer's choice of topk among T keys is another "
                 "form below and above T = topk"),
                (self.mla,
                 "latent attention's program from one tracing has not been "
                 "held to a bucket's own")):
            if declared is not None:
                return why
        return None

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.n_embd // self.n_head

    @property
    def kv_heads(self) -> int:
        return self.n_kv_head or self.n_head

    @property
    def ffn_dim(self) -> int:
        return self.intermediate_size or self.mlp_ratio * self.n_embd

    @property
    def rotary_dim(self) -> int:
        rd = round(self.rotary_pct * self.head_dim)
        return rd - rd % 2

    @property
    def is_moe(self) -> bool:
        return self.moe_num_experts > 0

    @property
    def moe_ffn_dim(self) -> int:
        return self.moe_intermediate_size or self.ffn_dim


# GPT-2 sizes (reference benchmarks target 125M / 1.3B; BASELINE.md configs 2-5)
GPT2_SIZES = {
    "gpt2-125m": dict(n_embd=768, n_layer=12, n_head=12),
    "gpt2-350m": dict(n_embd=1024, n_layer=24, n_head=16),
    "gpt2-760m": dict(n_embd=1536, n_layer=24, n_head=16),
    "gpt2-1.3b": dict(n_embd=2048, n_layer=24, n_head=16),
    "gpt2-2.7b": dict(n_embd=2560, n_layer=32, n_head=32),
    "gpt2-6.7b": dict(n_embd=4096, n_layer=32, n_head=32),
}


def gpt2_config(name: str, **overrides) -> GPTConfig:
    base = dict(GPT2_SIZES[name])
    base.update(overrides)
    return GPTConfig(**base)


def scaled(x, multiplier):
    """``x`` times a muP multiplier, the product taken in float32 and
    rounded once: a bare Python float would be rounded to ``x``'s dtype
    first (0.2% of a bf16 multiplier), and the published model multiplies
    in float32."""
    if multiplier == 1.0:
        return x
    return (x.astype(jnp.float32) * multiplier).astype(x.dtype)


def _norm(cfg, name):
    if cfg.norm == "rmsnorm":
        return nn.RMSNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                          param_dtype=cfg.param_dtype, name=name)
    return nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, use_bias=cfg.use_bias,
                        name=name)


_ACTIVATIONS = {
    "gelu_tanh": lambda x: nn.gelu(x, approximate=True),
    "gelu": lambda x: nn.gelu(x, approximate=False),
    "relu": nn.relu,
    "silu": nn.silu,
    # CLIP's x * sigmoid(1.702 x)
    "quick_gelu": lambda x: x * nn.sigmoid(1.702 * x),
}


class VocabEmbed(nn.Embed):
    """``nn.Embed`` with an explicit vocab-parallel lookup when the table is
    tensor-parallel vocab-sharded.

    A row-gather over a tp-sharded operand (and the scatter-add in its
    backward) cannot be partitioned by GSPMD — it falls back to
    "involuntary full rematerialization", replicating the table every step.
    The fix is the Megatron VocabParallelEmbedding masked-lookup+allreduce
    (reference analogue ``deepspeed/module_inject/replace_module.py:18``
    slices the same weights at inference), expressed as a ``shard_map``
    island: each tp shard gathers from its LOCAL vocab slice, zeroes rows
    it does not own, and one psum merges — O(B*T*C) memory, no ``[B, T,
    vocab]`` one-hot buffer (earlier rounds paid ~0.8 GB per micro batch
    at 50k vocab for that lowering), and the backward is a LOCAL
    scatter-add per shard, exactly the partitioning GSPMD could not infer.
    Replicated tables keep the native gather.
    """

    def __call__(self, inputs):
        from deepspeed_tpu.parallel.mesh import get_default_topology

        topo = get_default_topology()
        tp = topo.size("tp")
        if tp > 1 and self.num_embeddings % tp == 0:
            if topo.size("pp") == 1:
                return _vocab_parallel_lookup(inputs, self.embedding, topo,
                                              self.dtype)
            # pipeline stages jit over per-stage SUB-meshes; a shard_map
            # bound to the full topology mesh cannot run there. Fall back
            # to the one-hot contraction, which GSPMD partitions cleanly
            # on whatever mesh the stage runs (Megatron masked-lookup
            # expressed as a dot; [B, T, vocab] operand is the cost)
            onehot = jax.nn.one_hot(inputs, self.num_embeddings,
                                    dtype=self.dtype)
            return jnp.dot(onehot, self.embedding.astype(self.dtype))
        # tp == 1, or an indivisible vocab dim (sharding rules strip the
        # spec, the table stays replicated): native gather partitions fine
        return super().__call__(inputs)


def _island_batch_axes(topo, batch: int):
    """The mesh axes a ``shard_map`` island may split a batch of ``batch``
    rows over (a ``PartitionSpec`` entry). shard_map needs the dim evenly
    divisible by its axes; when it is not (e.g. batch-1 serving on a dp>1
    mesh, where the array is replicated anyway) the dim stays unsharded."""
    b0 = topo.batch_spec()[0]
    b_axes = b0 if isinstance(b0, tuple) else ((b0,) if b0 else ())
    b_size = int(np.prod([topo.size(a) for a in b_axes])) if b_axes else 1
    return b0 if batch % b_size == 0 else None


def _vocab_parallel_lookup(ids, embedding, topo, dtype):
    """Masked local-gather + psum over the tp axis (shard_map island)."""
    from jax.sharding import PartitionSpec as P

    tp = topo.size("tp")
    vocab, _ = embedding.shape
    shard = vocab // tp
    b0 = _island_batch_axes(topo, ids.shape[0])
    # mirror engine._put_batch: the sequence dim rides sp when it divides
    sp = topo.size("sp")
    t_ax = "sp" if (sp > 1 and ids.shape[1] % sp == 0) else None

    def lookup(ids_l, emb_l):
        lo = jax.lax.axis_index("tp") * shard
        local = ids_l - lo
        valid = (local >= 0) & (local < shard)
        rows = jnp.take(emb_l, jnp.where(valid, local, 0), axis=0)
        rows = jnp.where(valid[..., None], rows.astype(dtype),
                         jnp.zeros((), dtype))
        # exactly one shard owns each id, so the bf16 psum is exact
        return jax.lax.psum(rows, "tp")

    return jax.shard_map(
        lookup, mesh=topo.mesh,
        in_specs=(P(b0, t_ax), P("tp", None)),
        out_specs=P(b0, t_ax, None),
        check_vma=False,
    )(ids, embedding)


def _mesh_flash_attention(q, k, v, segment_ids, *, causal, window=None):
    """The Pallas flash kernel as a ``shard_map`` island over the mesh's
    batch and head axes.

    GSPMD cannot partition a Mosaic custom call: left bare inside the
    jitted step it gathers q/k/v and every chip runs the kernel on the
    whole global batch. Attention is independent per (row, head), so each
    device runs it on its local ``[B/b, T, H/tp, D]`` block with no
    collective. Dims the mesh does not divide stay unsharded (batch-1
    serving on a dp>1 mesh, where the array is replicated anyway).
    ``window`` and K / V of fewer heads than q (whole groups a device) are
    the kernel's own."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.parallel.mesh import get_default_topology

    topo = get_default_topology()
    B, _, H, _ = q.shape
    b0 = _island_batch_axes(topo, B)
    tp = topo.size("tp")
    h_ax = "tp" if (tp > 1 and H % tp == 0
                    and k.shape[2] % tp == 0) else None

    def local(q, k, v, seg=None):
        return flash_attention(q, k, v, causal=causal, segment_ids=seg,
                               window=window)

    args = (q, k, v) if segment_ids is None else (q, k, v, segment_ids)
    if (b0 is None and h_ax is None) or topo.size("pp") > 1:
        # nothing to split, or a pipeline stage jitted over a sub-mesh
        # (a shard_map bound to the full mesh cannot run there)
        return local(*args)
    qkv = P(b0, None, h_ax, None)
    return jax.shard_map(
        local, mesh=topo.mesh,
        in_specs=(qkv, qkv, qkv, P(b0, None))[:len(args)], out_specs=qkv,
        check_vma=False)(*args)


def decode_attention_block(cfg, T: int = 1):
    """How a decode call of ``T`` query tokens per lane attends over its
    cache, told from what the call and the cache's layout show: the
    positions in a block of the block-skipping kernel, which reads of each
    lane only the blocks between its first valid row and its clock; or
    None where the call keeps the two einsums over every position. The
    kernel takes one query token over dense storage: keys and values per
    head (ops/pallas/decode_attention.py) or, for latent attention, the
    one latent and rotary key a position that all heads share
    (ops/pallas/latent_decode_attention.py: another body over the same
    grid, with a block rule of its own, which need not divide the cache).
    More tokens at once (prefill, chunked continuation, speculative
    verification), the ring cache of a window layout, int8 storage
    (dequantised whole on read) and ALiBi (a bias on every position) stay
    on the einsums, and so do heads sharded over ``tp``: GSPMD cannot
    partition a Mosaic call. A model whose indexer chooses among the
    cached positions (``IndexerConfig.engaged``) is answered None too: it
    reads its chosen rows, gathered or as live blocks under the chosen
    mask (ops/indexed_attention.py). The scheduler asks the same question
    for its counter (``kv_blocks_read_share``)."""
    from deepspeed_tpu.ops.pallas.decode_attention import block_positions
    from deepspeed_tpu.ops.sparse_attention.sparse_attention_utils import \
        ring_engaged
    from deepspeed_tpu.parallel.mesh import get_default_topology

    if (T != 1 or cfg.kv_cache_dtype == "int8" or cfg.alibi
            or ring_engaged(cfg) is not None
            or get_default_topology().size("tp") > 1
            or (cfg.indexer is not None and cfg.indexer.engaged(cfg))):
        return None
    itemsize = jnp.dtype(cfg.dtype).itemsize
    full = cfg.attention_kind(KIND_ATTENTION)
    latent = getattr(full, "latent", None)
    if latent is not None:
        # latent attention by kind: the layers that keep every position
        # answer (a window kind's ring is read whole once it has turned)
        if latent.indexer is not None and latent.indexer.engaged(cfg):
            return None
    mla = cfg.mla if latent is None else latent.mla
    if mla is not None:
        from deepspeed_tpu.ops.pallas import latent_decode_attention

        return latent_decode_attention.block_positions(
            cfg.n_positions, mla.kv_rank, itemsize)
    return block_positions(cfg.n_positions, cfg.kv_heads, cfg.head_dim,
                           itemsize)


def kv_lane_pack(cfg) -> int:
    """How many KV heads share one row of the cached keys and values. The
    TPU lays a leaf's last axis along 128 lanes, so a head of 64 stored as
    ``[.., Hkv, 64]`` is padded to 128: twice the cache, in memory and in
    every read (seen compiling the 2,944-position cache of a 64-wide head
    for a described v5e: ``2.0x expansion``). Heads narrower than a lane
    row are therefore stored side by side, ``[B, S, Hkv / pack, pack * D]``
    (the same bytes in the same order), where whole rows come out: ``pack
    * D == 128`` and ``pack`` divides the KV heads; 1 (a head a row)
    everywhere else, for an int8 store (a scale a head) and beside an
    indexer (whose gathers take a head's rows)."""
    D, pack = cfg.head_dim, 128 // max(cfg.head_dim, 1)
    if (pack < 2 or pack * D != 128 or cfg.kv_heads % pack
            or cfg.kv_cache_dtype == "int8" or cfg.indexer is not None):
        return 1
    return pack


def step_kernel() -> bool:
    """Whether a recurrent mixer's decode step of one token runs the kernel
    that walks the state where it lies (ops/pallas/retention_step.py,
    ops/pallas/ssd_step.py), told from what the call shows: not where heads
    are sharded over ``tp``, because GSPMD cannot partition a Mosaic call;
    the mixer's plain one-token form on a slice there."""
    from deepspeed_tpu.parallel.mesh import get_default_topology

    return get_default_topology().size("tp") == 1


class CausalSelfAttention(nn.Module):
    config: GPTConfig

    @nn.compact
    def __call__(self, x, *, mask=None, segment_ids=None, positions=None,
                 deterministic=True, decode=False, cache_layer=None):
        cfg = self.config
        B, T, C = x.shape
        H, D = cfg.n_head, cfg.head_dim
        Hkv = cfg.kv_heads
        bias = cfg.use_bias if cfg.attn_bias is None else cfg.attn_bias

        # packed-sequence masking (deepspeed_tpu/data/): position i attends
        # j iff j <= i AND seg[i] == seg[j]. Supported on the flash and
        # einsum paths; the others either cannot express the per-row block
        # structure (sparse layouts, ALiBi's absolute-position bias) or do
        # not see it yet (sp/chunked fall through to einsum below).
        if segment_ids is not None:
            if decode:
                raise NotImplementedError(
                    "packed-sequence segment_ids are a training-path "
                    "feature; decode caches are per-sequence")
            if cfg.sparse_attention is not None:
                raise NotImplementedError(
                    "segment_ids with a block-sparse layout would silently "
                    "change the layout's visibility; unpack the batch or "
                    "disable sparse_attention")
            if cfg.alibi:
                raise NotImplementedError(
                    "ALiBi's absolute-position bias is not segment-aware; "
                    "packed batches require rotary or learned positions")

        qkv = nn.Dense((H + 2 * Hkv) * D, use_bias=bias,
                       dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                       name="c_attn")(x)
        q = qkv[..., : H * D].reshape(B, T, H, D)
        k = qkv[..., H * D:(H + Hkv) * D].reshape(B, T, Hkv, D)
        v = qkv[..., (H + Hkv) * D:].reshape(B, T, Hkv, D)
        k = scaled(k, cfg.key_multiplier)
        if cfg.qk_norm:
            def normed(t, name):
                # over the whole projection, or ("head") over each head
                rows = t if cfg.qk_norm == "head" else t.reshape(B, T, -1)
                return nn.RMSNorm(
                    epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, name=name)(
                        rows).reshape(t.shape)

            q, k = normed(q, "q_norm"), normed(k, "k_norm")

        def rope(t, positions):
            from deepspeed_tpu.ops.rotary import apply_rotary_pos_emb

            return apply_rotary_pos_emb(
                t, positions, base=cfg.rope_theta,
                rotary_dim=cfg.rotary_dim,
                interleaved=cfg.rotary_interleaved,
                sections=cfg.mrope_section)

        def repeat_kv(t):
            return (t if Hkv == H
                    else jnp.repeat(t, H // Hkv, axis=2))

        if decode:
            if not cfg.causal:
                raise NotImplementedError(
                    "decode path requires a causal model")
            # KV-cache append + attend (the reference's softmax_context
            # kernel with its inference_context.h cache management,
            # csrc/transformer/inference/). Chunk-aware: prefill writes T
            # tokens at once, decode steps write one. Ragged batches:
            # LEFT-padded prompts pass ``mask``, and a per-slot validity
            # cache excludes pad slots from every later step's attention
            # (reference inference_context.h masked decode). Left padding
            # keeps valid keys physically contiguous, so rotary (relative
            # offsets) and ALiBi (row-constant shift under softmax) stay
            # exact without per-sequence position bookkeeping here.
            #
            # int8 KV cache (GPTConfig.kv_cache_dtype): values are stored
            # quantized with per-(row, slot, kv-head) f32 scales and
            # dequantized on read — XLA fuses the int8->f32 convert +
            # scale multiply into the attention einsums, so per-step HBM
            # cache traffic stays int8
            kv_int8 = cfg.kv_cache_dtype == "int8"
            # layout-aware compact KV cache: when the sparse layout is a
            # causal window (+ leading globals), decode retains ONLY the
            # slots the layout can ever attend — a block-granular ring —
            # and reproduces the TRAINING block-sparse visibility exactly
            # (the dense cache attends strictly more keys than a
            # window-trained model saw). See GPTConfig.sparse_kv_cache.
            from deepspeed_tpu.ops.sparse_attention. \
                sparse_attention_utils import ring_engaged, ring_storage_len

            ring = ring_engaged(cfg)
            if ring is None:
                S = cfg.n_positions
            else:
                w_blk, g_tok, blk = ring
                ring_len = ring_storage_len(cfg, ring)
                S = g_tok + ring_len
            # leaf -> (shape, virgin value, dtype)
            # (narrow heads side by side in a row of 128: kv_lane_pack)
            pack = kv_lane_pack(cfg)
            spec = dict.fromkeys(
                ("cached_key", "cached_value"),
                ((B, S, Hkv // pack, D * pack), 0,
                 jnp.int8 if kv_int8 else cfg.dtype))
            if kv_int8:
                spec.update(dict.fromkeys(
                    ("cached_key_scale", "cached_value_scale"),
                    ((B, S, Hkv), 0, jnp.float32)))
            ix = cfg.indexer
            if ix is not None:
                from deepspeed_tpu.models import indexer

                spec[indexer.CACHED_INDEX_KEY] = (
                    (B, S, ix.head_dim), 0, cfg.dtype)
                if ix.engaged(cfg):
                    # what a decode step leaves of its choice; none yet
                    spec[indexer.CHOSEN_ROWS] = ((B, ix.topk), -1, jnp.int32)
                    spec[indexer.CHOICE_QUERY] = (
                        (B, ix.n_heads, ix.head_dim), 0, cfg.dtype)
                    spec[indexer.CHOICE_WEIGHTS] = (
                        (B, ix.n_heads), 0, jnp.float32)
            spec["valid"] = ((B, S), False, jnp.bool_)
            if ring is not None:
                spec["slot_pos"] = ((B, S), -1, jnp.int32)  # nothing cached
            spec["cache_index"] = ((B,), 0, jnp.int32)
            # whether this call makes the cache (a prefill: every row a
            # lane holds is among the tokens at hand) or finds it
            fresh = not self.has_variable("cache", "cache_index")
            cache = {name: self.variable("cache", name, jnp.full, *leaf_spec)
                     for name, leaf_spec in spec.items()}

            # Under ScannedBlocks the leaves are the stacked
            # [n_layer, B, S, ...] buffers that the layer loop carries,
            # and this call is layer ``cache_layer`` of them: it writes
            # its new rows at [cache_layer, row, slot] and reads its
            # [B, S, ...] slice, so no whole leaf is produced but by the
            # in-place row update. Without a layer (scan_layers=False) a
            # leaf is this layer's own.
            def leaf(name):
                v = cache[name].value
                return v if cache_layer is None else \
                    jax.lax.dynamic_index_in_dim(v, cache_layer, 0,
                                                 keepdims=False)

            def put(name, index, val):
                if cache_layer is not None:
                    index = (cache_layer,) + index
                cache[name].value = cache[name].value.at[index].set(
                    val, mode="drop")

            # PER-ROW write index (and, in the ring, slot positions):
            # continuous-batching admissions splice a freshly prefilled
            # [1, ...] cache into one batch lane, so every row carries its
            # own clock (lockstep generate just advances them together)
            idx = leaf("cache_index")                       # [B]
            pos = idx[:, None] + jnp.arange(T)[None, :]     # [B, T]
            if cfg.rotary:
                # rotate before the cache write: cached keys are
                # position-baked, exactly like the reference's KV cache
                # after its apply_rotary_pos_emb kernel
                q, k = rope(q, pos), rope(k, pos)
            if ix is not None:
                # (qI, kI, w): the index key is cached beside k and v
                index = indexer.Indexer(cfg, name="indexer")(x, pos)
            if ring is None:
                slot_sets = (pos,)
            else:
                if T > ring_len:
                    raise ValueError(
                        f"ring KV prefill got {T} tokens in one pass but "
                        f"the ring retains only {ring_len} positions: keys "
                        "a mid-prompt query still needs would be evicted "
                        "before it attends, and the corrupted attention "
                        "outputs would poison every later layer's cache "
                        "(and with it every generated token). Prefill long "
                        "prompts in block-aligned chunks instead — "
                        "InferenceEngine.generate and the continuous-"
                        "batching scheduler do this automatically "
                        "(inference/engine.py prefill_chunk_spans).")
                # every token of a (guarded, <= ring_len) pass lands in its
                # ring slot; leading-global tokens ALSO land in their
                # dedicated slot (the ring copy is masked out of
                # visibility below, so nothing double-counts)
                slot_sets = (g_tok + pos % ring_len,
                             jnp.where(pos < g_tok, pos, S))  # S -> dropped
            rows = jnp.arange(B)[:, None]
            write_valid = (mask.astype(jnp.bool_) if mask is not None
                           else jnp.ones((B, T), jnp.bool_))
            with jax.named_scope(SCOPE_KV_CACHE_WRITE):
                new = {"valid": write_valid}
                for name, t in (("cached_key", k), ("cached_value", v)):
                    if kv_int8:
                        from deepspeed_tpu.ops.quantizer import \
                            quantize_blockwise

                        # scales [B, T, Hkv, 1] -> [B, T, Hkv]
                        new[name], scale = quantize_blockwise(t, D)
                        new[name + "_scale"] = scale[..., 0]
                    elif pack > 1:
                        new[name] = t.astype(cfg.dtype).reshape(
                            B, T, Hkv // pack, D * pack)
                    else:
                        new[name] = t.astype(cfg.dtype)
                if ix is not None:
                    new[indexer.CACHED_INDEX_KEY] = index[1]
                if ring is not None:
                    new["slot_pos"] = pos
                for slots in slot_sets:
                    for name, val in new.items():
                        put(name, (rows, slots), val)
                put("cache_index", (Ellipsis,), idx + T)
            if ix is not None and ix.engaged(cfg):
                # attention over the rows the indexer chooses: of the
                # tokens at hand where this call makes the cache, of the
                # cached rows where it finds one (one query token reads
                # its rows out of the stacked leaves where they lie)
                stored = None if fresh else (cache, leaf, put, cache_layer)
                y = indexer.attend_chosen(
                    self, (q, k, v), index, pos, write_valid, stored)
                return nn.Dense(C, use_bias=bias, dtype=cfg.dtype,
                                param_dtype=cfg.param_dtype,
                                name="c_proj")(y.reshape(B, T, H * D))
            kernel_block = decode_attention_block(cfg, T)
            if kernel_block is not None:
                # one query token over dense storage: each lane reads the
                # blocks between its first valid row and its clock, out
                # of the stacked leaf where it lies (no layer's slice is
                # made); same mask, valid & (position <= clock)
                from deepspeed_tpu.ops.pallas.decode_attention import \
                    decode_attention

                with jax.named_scope(SCOPE_KV_CACHE_READ):
                    valid = leaf("valid")
                with jax.named_scope(SCOPE_ATTN_CORE):
                    q1 = q[:, 0]
                    if pack > 1:
                        # a query head in its KV head's lanes of the row,
                        # zeros in the others': the same scores and, in
                        # those lanes of the output, the same sums
                        lanes = jax.nn.one_hot(
                            jnp.arange(H) // (H // Hkv) % pack, pack,
                            dtype=cfg.dtype)[None, :, :, None]
                        q1 = (q1[:, :, None] * lanes).reshape(B, H, pack * D)
                    y = decode_attention(
                        q1, cache["cached_key"].value,
                        cache["cached_value"].value, valid, idx,
                        cache_layer, block=kernel_block,
                        scale=1.0 / np.sqrt(D))
                    if pack > 1:
                        y = jnp.sum(y.reshape(B, H, pack, D) * lanes, axis=2)
                return nn.Dense(C, use_bias=bias, dtype=cfg.dtype,
                                param_dtype=cfg.param_dtype,
                                name="c_proj")(y.reshape(B, T, H * D))
            q_pos = pos[:, :, None]                         # [B, T, 1]
            k_pos = jnp.arange(S)[None, :]                  # [1, S]
            with jax.named_scope(SCOPE_KV_CACHE_READ):
                k_all, v_all = leaf("cached_key"), leaf("cached_value")
                if pack > 1:
                    k_all = k_all.reshape(B, S, Hkv, D)
                    v_all = v_all.reshape(B, S, Hkv, D)
                if kv_int8:
                    from deepspeed_tpu.ops.quantizer import \
                        dequantize_blockwise

                    k_all = dequantize_blockwise(
                        k_all, leaf("cached_key_scale"), cfg.dtype)
                    v_all = dequantize_blockwise(
                        v_all, leaf("cached_value_scale"), cfg.dtype)
                if ring is None:
                    visible = k_pos[None] <= q_pos          # [B, T, S]
                else:
                    ps = leaf("slot_pos")[:, None, :]       # [B, 1, S]
                    in_window = (ps // blk) >= (q_pos // blk) - w_blk
                    visible = ((ps >= 0) & (ps <= q_pos)
                               & ((k_pos[None] < g_tok)
                                  | (in_window & (ps >= g_tok))))
                visible = (visible[:, None, None]           # [B,1,1,T,S]
                           & leaf("valid")[:, None, None, None, :])

            # grouped attention: query heads contract directly against the
            # un-repeated KV cache ([B, S, Hkv, D] stays in place — no
            # [B, S, H, D] repeat materializes per step)
            G = H // Hkv
            qg = q.reshape(B, T, Hkv, G, D)
            scale = 1.0 / np.sqrt(D)
            with jax.named_scope(SCOPE_ATTN_CORE):
                att = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_all) * scale
                if cfg.alibi:
                    slopes = jnp.asarray(alibi_slopes(H)).reshape(Hkv, G)
                    att = att + (slopes[:, :, None, None]
                                 * k_pos[None].astype(att.dtype))
                att = jnp.where(visible, att, jnp.finfo(att.dtype).min)
                # ring, NaN-safe: an all-pad chunk row (ragged left-padded
                # batch) has an empty visible set; its output is masked
                # out later but must not produce NaN
                att = jax.nn.softmax(
                    att.astype(jnp.float32), axis=-1,
                    where=None if ring is None else visible
                ).astype(cfg.dtype)
                y = jnp.einsum("bhgqk,bkhd->bqhgd", att, v_all)
            y = y.reshape(B, T, H * D)
            return nn.Dense(C, use_bias=bias, dtype=cfg.dtype,
                            param_dtype=cfg.param_dtype, name="c_proj")(y)

        if cfg.rotary:
            # packed batches pass per-segment-reset positions so each
            # document sees the same rotary phases it would alone
            pos = (positions if positions is not None
                   else jnp.arange(T)[None, :])
            q = rope(q, pos)
            k = rope(k, pos)
        if cfg.indexer is not None:
            # no cache: the indexer chooses among this call's own tokens
            from deepspeed_tpu.models import indexer

            if segment_ids is not None:
                raise NotImplementedError(
                    "packed-sequence segment_ids with an indexer: the "
                    "choice would run across documents")
            y = indexer.attend_chosen(
                self, (q, k, v),
                indexer.Indexer(cfg, name="indexer")(x, pos), None,
                jnp.ones((B, T), jnp.bool_) if mask is None
                else mask.astype(jnp.bool_), None)
            return nn.Dense(C, use_bias=bias, dtype=cfg.dtype,
                            param_dtype=cfg.param_dtype,
                            name="c_proj")(y.reshape(B, T, H * D))
        k = repeat_kv(k)
        v = repeat_kv(v)

        # block-sparse path (explicit opt-in; wins over sp/chunked/flash).
        # Taken UNCONDITIONALLY when configured — a silent dense fallback
        # would change the model's math between configs. Attention-prob
        # dropout does not exist on this path (the layout already drops
        # most of the matrix; output dropout below still applies), and
        # ALiBi is rejected at config time.
        if cfg.sparse_attention is not None:
            from deepspeed_tpu.ops.sparse_attention import SparseSelfAttention

            sa = SparseSelfAttention(cfg.sparse_attention,
                                     max_seq_length=cfg.n_positions)
            kpm = None
            if mask is not None:
                kpm = jnp.where(mask, 0.0, jnp.finfo(jnp.float32).min)
            with jax.named_scope(SCOPE_ATTN_CORE):
                y = sa(q, k, v, key_padding_mask=kpm, causal=cfg.causal)
            y = y.reshape(B, T, H * D)
            y = nn.Dense(C, use_bias=bias, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="c_proj")(y)
            return nn.Dropout(cfg.dropout)(y, deterministic=deterministic)

        # like the flash path, sp attention has no attention-prob dropout
        # (and no ALiBi bias hook)
        if (cfg.sequence_parallel != "none" and mask is None
                and segment_ids is None and not cfg.alibi
                and (cfg.dropout == 0.0 or deterministic)):
            from deepspeed_tpu.parallel.mesh import get_default_topology
            from deepspeed_tpu.parallel.sequence import (
                ring_attention,
                ulysses_attention,
            )

            if get_default_topology().size("sp") > 1:
                attn_fn = {"ring": ring_attention,
                           "ulysses": ulysses_attention}[cfg.sequence_parallel]
                with jax.named_scope(SCOPE_ATTN_CORE):
                    y = attn_fn(q, k, v, causal=cfg.causal)
                y = y.reshape(B, T, H * D)
                y = nn.Dense(C, use_bias=bias, dtype=cfg.dtype,
                             param_dtype=cfg.param_dtype, name="c_proj")(y)
                return nn.Dropout(cfg.dropout)(y, deterministic=deterministic)

        # chunked path: same gating as flash (no mask/ALiBi/attn-dropout),
        # divisibility by the chunk instead of 128-alignment. Selected by
        # explicit attention_chunk (wins over flash) or by "auto" past the
        # flash kernel's VMEM ceiling (FLASH_MAX_SEQ).
        auto_chunk = None
        if cfg.use_flash_attention == "auto" and T > FLASH_MAX_SEQ:
            # largest standard chunk that divides T (an odd long T still
            # routes here rather than into the flash VMEM wall)
            auto_chunk = next(
                (c for c in (CHUNKED_AUTO_CHUNK, 512, 256, 128)
                 if T % c == 0), None)
        eff_chunk = cfg.attention_chunk or auto_chunk
        if (eff_chunk and mask is None and segment_ids is None
                and not cfg.alibi
                and (cfg.dropout == 0.0 or deterministic)
                and T % eff_chunk == 0 and T > eff_chunk):
            from deepspeed_tpu.ops.chunked_attention import chunked_attention

            with jax.named_scope(SCOPE_ATTN_CORE):
                y = chunked_attention(q, k, v, causal=cfg.causal,
                                      chunk=eff_chunk)
            y = y.reshape(B, T, H * D)
            y = nn.Dense(C, use_bias=bias, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="c_proj")(y)
            return nn.Dropout(cfg.dropout)(y, deterministic=deterministic)

        # flash path needs 128-aligned seq (TPU tile constraint), no padding
        # mask, and no attention dropout (the kernel has none). "auto"
        # selects by the measured seq-length crossover (see GPTConfig).
        # "auto" never picks flash past its VMEM ceiling (FLASH_MAX_SEQ) —
        # an un-chunkable long T falls through to einsum rather than
        # compiling the kernel into the wall
        want_flash = (FLASH_AUTO_MIN_SEQ <= T <= FLASH_MAX_SEQ
                      if cfg.use_flash_attention == "auto"
                      else cfg.use_flash_attention)
        use_flash = (want_flash and mask is None
                     and T % 128 == 0 and not cfg.alibi
                     and (cfg.dropout == 0.0 or deterministic))
        with jax.named_scope(SCOPE_ATTN_CORE):
            if use_flash:
                y = _mesh_flash_attention(q, k, v, segment_ids,
                                          causal=cfg.causal)
            else:
                scale = 1.0 / np.sqrt(D)
                att = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
                if cfg.alibi:
                    # bias slopes_h * k_pos (HF BLOOM formula; equivalent to
                    # slopes * (k - q) under softmax's row-shift invariance)
                    slopes = jnp.asarray(alibi_slopes(H))
                    att = att + (slopes[None, :, None, None]
                                 * jnp.arange(T, dtype=att.dtype)[None, None,
                                                                  None, :])
                if cfg.causal:
                    tri = jnp.tril(jnp.ones((T, T), dtype=bool))
                    att = jnp.where(tri[None, None, :, :], att,
                                    jnp.finfo(att.dtype).min)
                if mask is not None:
                    att = jnp.where(mask[:, None, None, :], att,
                                    jnp.finfo(att.dtype).min)
                if segment_ids is not None:
                    # NaN-safe: the causal diagonal is always same-segment, so
                    # no row's visible set is ever empty
                    same = (segment_ids[:, None, :, None]
                            == segment_ids[:, None, None, :])
                    att = jnp.where(same, att, jnp.finfo(att.dtype).min)
                att = jax.nn.softmax(
                    att.astype(jnp.float32), axis=-1).astype(cfg.dtype)
                att = nn.Dropout(cfg.dropout)(
                    att, deterministic=deterministic)
                y = jnp.einsum("bhqk,bkhd->bqhd", att, v)
        y = y.reshape(B, T, H * D)
        y = nn.Dense(C, use_bias=bias, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="c_proj")(y)
        y = nn.Dropout(cfg.dropout)(y, deterministic=deterministic)
        return y


class MLP(nn.Module):
    config: GPTConfig

    @nn.compact
    def __call__(self, x, *, deterministic=True):
        cfg = self.config
        act = _ACTIVATIONS[cfg.activation]
        h = nn.Dense(cfg.ffn_dim, use_bias=cfg.use_bias, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="c_fc")(x)
        if cfg.gated_mlp:
            # SwiGLU (LLaMA family): act(gate) * up — both column-parallel
            g = nn.Dense(cfg.ffn_dim, use_bias=cfg.use_bias, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="c_gate")(x)
            h = act(scaled(g, cfg.mlp_gate_multiplier)) * h
        else:
            h = act(h)
        h = nn.Dense(cfg.n_embd, use_bias=cfg.use_bias, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="c_proj")(h)
        h = scaled(h, cfg.mlp_down_multiplier)
        h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
        return h


class Block(nn.Module):
    """Pre-LN transformer block; MLP becomes an expert-parallel MoE layer when
    the config asks for experts (reference moe/layer.py MoE drop-in).
    Returns ``(x, l_aux)`` — l_aux is the layer's auxiliary losses with
    their coefficients, 0 for the dense path.

    The token mixer is the block's ``mixer`` field, the layer's entry of
    ``GPTConfig.layer_types`` as whoever runs the layers hands it over;
    None (a stack of one kind) is the mixer the whole-model fields choose:
    latent attention, retention, or attention with the Mamba-2 mixer
    beside it and the indexer inside it."""

    config: GPTConfig
    # one of the leading ``first_k_dense`` blocks: a dense MLP whatever
    # the configuration's experts
    dense_mlp: bool = False
    mixer: Optional[str] = None

    @nn.compact
    def __call__(self, x, *, mask=None, segment_ids=None, positions=None,
                 deterministic=True, decode=False, pld_keep=None,
                 cache_layer=None, lane=None):
        """``lane`` is a latent-attention model's lane cache on a decode
        call (models/latent_attention.py ``LaneCache``: its owner carries
        it through the layers as a value), and the block then returns
        ``(x, l_aux, lane)``."""
        cfg = self.config
        x_in = x
        u = _norm(cfg, "ln_1")(x)
        if cfg.recurrent_leaves and segment_ids is not None:
            raise NotImplementedError(
                "packed-sequence segment_ids with a recurrent mixer: "
                "the state would run across documents")
        if self.mixer == KIND_CONV:
            from deepspeed_tpu.models.short_conv import ShortConv

            a = ShortConv(cfg, name="conv")(
                u, mask=mask, decode=decode, cache_layer=cache_layer)
        elif cfg.mla is not None:
            from deepspeed_tpu.models.latent_attention import LatentAttention

            a = LatentAttention(cfg, name="attn")(
                u, mask=mask, segment_ids=segment_ids, positions=positions,
                lane=lane, cache_layer=cache_layer)
            if lane is not None:
                a, lane = a
        elif cfg.retention is not None:
            # the token mixer is retention, not attention: no KV cache
            from deepspeed_tpu.models.power_retention import PowerRetention

            a = PowerRetention(cfg, name="attn")(
                u, mask=mask, decode=decode, cache_layer=cache_layer)
        elif (kind := cfg.attention_kind(self.mixer)) is not None:
            # attention layers that differ by kind: a window and rotary,
            # or neither, as the layer's kind declares
            if kind.latent is not None:
                from deepspeed_tpu.models.latent_attention import \
                    KindLatentAttention as KindAttention
            else:
                from deepspeed_tpu.models.kind_attention import KindAttention

            a = KindAttention(cfg, kind, name="attn")(
                u, mask=mask, segment_ids=segment_ids, decode=decode,
                cache_layer=cache_layer)
        else:
            a = CausalSelfAttention(cfg, name="attn")(
                scaled(u, cfg.attention_in_multiplier),
                mask=mask, segment_ids=segment_ids, positions=positions,
                deterministic=deterministic, decode=decode,
                cache_layer=cache_layer)
            a = scaled(a, cfg.attention_out_multiplier)
        if cfg.ssm is not None:
            # the hybrid block: the mixer reads what attention reads and
            # the two are summed into the one residual
            from deepspeed_tpu.models.mamba2 import Mamba2Mixer

            a = a + Mamba2Mixer(cfg, name="mamba")(
                u, mask=mask, decode=decode, cache_layer=cache_layer)
        if cfg.post_norms:
            a = _norm(cfg, "ln_1_post")(a)
        if cfg.parallel_residual:
            # GPT-J/NeoX form: attention and MLP both read the pre-residual
            # stream; GPT-J's single shared LN is expressed by loading
            # identical weights into ln_1/ln_2 (module_inject/hf.py)
            h = _norm(cfg, "ln_2")(x)
        else:
            x = x + a
            h = _norm(cfg, "ln_2")(x)
        if cfg.is_moe and not self.dense_mlp:
            from deepspeed_tpu.moe.layer import MoE

            y, l_aux, l_z, _ = MoE(
                d_model=cfg.n_embd,
                d_hidden=cfg.moe_ffn_dim,
                num_experts=cfg.moe_num_experts,
                k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor,
                eval_capacity_factor=cfg.moe_eval_capacity_factor,
                min_capacity=cfg.moe_min_capacity,
                noisy_gate_policy=cfg.moe_noisy_gate_policy,
                drop_tokens=cfg.moe_drop_tokens,
                use_rts=cfg.moe_use_rts,
                gated_experts=cfg.moe_gated_experts,
                expert_activation=_ACTIVATIONS.get(cfg.moe_expert_activation),
                norm_topk_prob=cfg.moe_norm_topk_prob,
                n_shared=cfg.moe_n_shared,
                n_group=cfg.moe_n_group,
                topk_group=cfg.moe_topk_group,
                routed_scale=cfg.moe_routed_scale,
                experts_held=cfg.moe_experts_held,
                scoring=cfg.moe_scoring,
                expert_bias=cfg.moe_expert_bias,
                expert_bias_init=cfg.moe_expert_bias_init,
                renorm_eps=cfg.moe_renorm_eps,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                name="mlp",
            )(h, deterministic=deterministic,
              router_input=x_in if cfg.moe_router_input == "block" else None)
            l_aux = cfg.moe_aux_loss_coef * l_aux
            if cfg.moe_z_loss_coef:
                l_aux = l_aux + cfg.moe_z_loss_coef * l_z
        else:
            y = MLP(cfg, name="mlp")(h, deterministic=deterministic)
            l_aux = jnp.float32(0.0)
        if cfg.post_norms:
            y = _norm(cfg, "ln_2_post")(y)
        x = x + y + a if cfg.parallel_residual else x + y
        if cfg.stochastic_mode and pld_keep is not None and not deterministic:
            # whole-block stochastic depth (PLD form: identity skip, no
            # 1/keep rescale — inference uses all layers unscaled). The
            # gate key comes from the per-layer split "dropout" stream, so
            # remat recompute reproduces the same draw exactly.
            gate = jax.random.bernoulli(self.make_rng("dropout"), pld_keep)
            x = jnp.where(gate, x, x_in)
            l_aux = jnp.where(gate, l_aux, jnp.zeros_like(l_aux))
        if lane is not None:
            return x, l_aux, lane
        return x, l_aux


# measured crossover for use_flash_attention="auto"
# (benchmarks/flash_sweep.py, v5e chip): XLA einsum attention wins below
# this sequence length, the Pallas flash kernel at and above it
FLASH_AUTO_MIN_SEQ = 512
# above this "auto" falls back to the chunked online-softmax path
# (ops/chunked_attention.py) in ``CausalSelfAttention``: at 16384 the flash
# kernel's per-head working set exceeded the 16 MB a kernel gets unasked.
# Since PR 45 a launch asks for the VMEM its blocks need, and the kernels
# compile and run at 16384 x 128 with and without a window (PR 63, which
# the layers by kind use: models/kind_attention.py ``flash_takes``); what
# "auto" chooses here is left as it was measured
FLASH_MAX_SEQ = 8192
CHUNKED_AUTO_CHUNK = 1024


def alibi_slopes(n_head: int) -> np.ndarray:
    """Per-head ALiBi slopes (BLOOM; HF build_alibi_tensor math exactly,
    reference BLOOMLayerPolicy replace_policy.py:444 serves these models
    through its fused kernels)."""
    import math

    closest = 2 ** math.floor(math.log2(n_head))
    base = 2.0 ** (-(2.0 ** -(math.log2(closest) - 3)))
    slopes = [base ** i for i in range(1, closest + 1)]
    if closest != n_head:
        extra_base = 2.0 ** (-(2.0 ** -(math.log2(2 * closest) - 3)))
        n_extra = min(closest, n_head - closest)
        slopes += [extra_base ** i for i in range(1, 2 * n_extra, 2)]
    return np.asarray(slopes, np.float32)


def quantize_block_params(tree):
    """2-D ``kernel`` leaves -> {"q": int8, "scale": f32[out]} (symmetric
    per-output-column). The storage format of ``quantized_weights``; also
    the ``trans_out_fn`` that makes ``model.init`` produce this structure
    natively so shape/sharding trees stay consistent."""
    from collections.abc import Mapping

    from deepspeed_tpu.ops.quantizer import quantize_weight_per_column

    def walk(t):
        if isinstance(t, Mapping):   # plain dict OR flax FrozenDict
            out = {}
            for k, v in t.items():
                if (k == "kernel" and hasattr(v, "ndim")
                        and v.ndim in (2, 3)
                        and jnp.issubdtype(v.dtype, jnp.floating)):
                    if v.ndim == 2:
                        q, s = quantize_weight_per_column(v, num_bits=8)
                    else:  # scan-stacked [n_layer, in, out]
                        q, s = jax.vmap(lambda w: quantize_weight_per_column(
                            w, num_bits=8))(v)
                    out[k] = {"q": q, "scale": s}
                else:
                    out[k] = walk(v)
            return out
        return t

    return walk(tree)


def dequantize_block_params(tree, dtype):
    """Trace-level inverse of :func:`quantize_block_params`: runs INSIDE
    the layer scan on one layer's slice, so the int8->compute convert
    fuses into that layer's matmuls."""

    from collections.abc import Mapping

    def walk(t):
        if isinstance(t, Mapping):   # plain dict OR flax FrozenDict
            if set(t) == {"q", "scale"}:
                q, s = t["q"], t["scale"]
                sb = s[:, None, :] if q.ndim == 3 else s[None, :]
                return q.astype(dtype) * sb.astype(dtype)
            return {k: walk(v) for k, v in t.items()}
        return t

    return walk(tree)


def _maybe_quantized_block(block_cls, cfg):
    """Wrap a block class so its params live int8-at-rest (see
    GPTConfig.quantized_weights).

    init=False on purpose: with init=True, flax's map_variables runs the
    wrapped function ONCE with the raw (still-quantized) params whenever
    any other collection is mutable — i.e. on every KV-cache-creating
    decode apply — and Dense then chokes on the {q, scale} dict. The
    trade-off is that ``model.init`` cannot create params through the
    transform: initialize a dense twin (quantized_weights=False) and
    convert with :func:`quantize_block_params`, which is what
    ``InferenceEngine._materialize`` does."""
    if not cfg.quantized_weights:
        return block_cls
    import functools

    return nn.map_variables(
        block_cls, "params",
        trans_in_fn=functools.partial(dequantize_block_params,
                                      dtype=cfg.dtype))


# The leaves of a block that their consumer reads as stored: the MoE router
# multiplies and corrects its scores in float32 whatever the compute dtype
# (moe/layer.py).
_GATHERED_AS_STORED = ("mlp/gate/kernel", "mlp/expert_bias")


def _maybe_gathered_block(block_cls, cfg, path, stacked=None):
    """Under a ZeRO-3 step program over ``fsdp > 1`` (the engine's
    ``gather_context``, read at trace time), ``block_cls`` with one layer's
    weights cast and all-gathered where the layer reads them and their
    gradients reduce-scattered back (runtime/zero/gather.py); under
    ``nn.remat`` the backward pass gathers again, which is ZeRO-3's own
    re-gather. Anywhere else: ``block_cls`` itself."""
    return gathered_on_use(block_cls, path, cfg.dtype, stacked=stacked,
                           uses=2 if cfg.remat else 1,
                           keep_dtype=_GATHERED_AS_STORED)


def _maybe_layers_ahead(owner, block_cls, cfg, n_scanned, x, mask,
                        segment_ids, positions, deterministic, plain):
    """Under a ZeRO-3 step program over ``fsdp > 1`` whose two prefetch
    keys allow it, ``owner``'s scanned stack run with each layer's first
    weight gathered a turn early (runtime/zero/gather.py ``layers_ahead``):
    ``(x, l_aux)``. ``plain`` says that the call is one that loop can run:
    a pass over whole sequences with no cache, no lane and no layer drop.
    The loop recomputes a whole layer in its backward pass, so the
    configuration must ask for that (``remat`` with the ``full`` policy),
    and it slices the stack itself, so the parameters must be the plain
    floating leaves on the device. Anywhere else, and at ``init``: ``None``,
    and the caller builds the scan it always built."""
    if current_plan() is None or owner.is_initializing():
        return None
    stack = owner.get_variable("params", "block")
    ahead = layers_per_turn(
        stack, owner.path + ("block",),
        n_scanned, cfg.dtype, uses=2 if cfg.remat else 1,
        keep_dtype=_GATHERED_AS_STORED,
        may_hand_on=(plain and cfg.remat and cfg.remat_policy == "full"
                     and not cfg.param_offload
                     and not cfg.quantized_weights))
    if ahead is None:
        return None
    rngs = {name: owner.make_rng(name) for name in ("dropout", "gating")
            if owner.has_rng(name)}

    def layer(params, x, i, consts):
        mask, segment_ids, positions, rngs = consts
        return block_cls(cfg, parent=None).apply(
            {"params": params}, x, mask=mask, segment_ids=segment_ids,
            positions=positions, deterministic=deterministic,
            rngs={name: jax.random.fold_in(key, i)
                  for name, key in rngs.items()})

    x, l_aux = layers_ahead(
        layer, stack, x, (mask, segment_ids, positions, rngs), n_scanned,
        ahead)
    return x, jnp.sum(l_aux)


def _maybe_in_place_experts(body, owner, cfg, rows, decode):
    """``body`` (one turn of ``owner``'s layer scan) with the expert
    matrices read where they lie in the stacked parameters, where
    moe/experts.py ``expert_matrices`` says a call over ``rows`` sorted
    rows does: the stack is ``owner``'s own variable before the scan slices
    it, closed over (loop-invariant, no carry), and the turn's index into
    it what the scan counts less the leading dense blocks. The turn's
    sliced ``wi`` / ``wg`` / ``wo`` are then read by nothing and their
    slices leave the program: a training step (not ``decode``) still
    differentiates with respect to them, so the stack is closed over
    under ``stop_gradient`` and the matrices' gradient is stacked by the
    scan like every other leaf's. Anywhere else, at ``init`` (no leaf
    exists yet) and for a tree handed over in another dtype than the
    configuration declares: ``body`` itself."""
    from deepspeed_tpu.moe import experts

    if owner.is_initializing() \
            or experts.expert_matrices(cfg, rows) != "in_place":
        return body
    stacked = experts.stack_in_place(
        owner.get_variable("params", "block")["mlp"]["experts"], cfg.dtype,
        serving=decode)
    if stacked is None:
        return body

    # (under ``body``'s own name, which the scan gives its scope: the
    # operations' ``op_name`` paths stay what every reader knows)
    @functools.wraps(body)
    def in_place(block, carry, layer_idx):
        with experts.matrices_in_place(
                stacked, layer_idx - cfg.first_k_dense, serving=decode):
            return body(block, carry, layer_idx)

    return in_place


def pld_keep_probability(layer_idx, n_layer: int, theta):
    """Depth schedule for PLD stochastic depth: layer i survives with
    ``p_i = 1 - (i/L)(1 - theta)`` — deeper layers drop more. Shared by
    the GPT trunk (scan + loop forms) and the BERT encoder so the schedule
    cannot drift between them. ``layer_idx`` may be a python int or a
    traced scan counter; ``theta`` a float or traced scalar."""
    frac = (layer_idx.astype(jnp.float32)
            if hasattr(layer_idx, "astype") else float(layer_idx)) / n_layer
    return 1.0 - frac * (1.0 - theta)


def _remat_policy(name: str):
    import jax

    if name == "selective":
        # non-batched dots (the param matmuls) + flash-attention outputs:
        # saving o/lse (O(seq) memory) avoids re-running the fwd kernel to
        # rebuild backward residuals — attention probs are never saved
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names(
                "attn_out", "attn_lse"),
        )
    if name == "save_dots":
        # checkpoint every dot product (param matmuls AND attention
        # score/value einsums): the backward never re-runs a matmul, at
        # the cost of keeping the [T, T] attention dots live on the dense
        # path — the cheapest-recompute / highest-memory selective point
        return jax.checkpoint_policies.dots_saveable
    if name == "save_nothing_but_flash":
        # keep ONLY the flash kernel's o/lse residuals (O(seq) per layer,
        # tagged via checkpoint_name in ops/pallas/flash_attention.py) so
        # backward skips the fwd kernel re-run; everything else — all
        # param matmuls included — is recomputed. On the einsum path no
        # tensor carries these names, so it degenerates to `full`.
        return jax.checkpoint_policies.save_only_these_names(
            "attn_out", "attn_lse")
    if name == "full":
        return None  # save nothing, recompute all
    raise ValueError(f"unknown remat_policy {name!r}")


class ScannedBlocks(nn.Module):
    """All transformer blocks as one scanned module: params get a leading
    ``n_layer`` axis, compile time is layer-count independent, and remat
    applies per scan step (the activation-checkpointing sweet spot on TPU)."""

    config: GPTConfig

    @nn.compact
    def __call__(self, x, *, mask=None, segment_ids=None, positions=None,
                 deterministic=True, decode=False, pld_theta=None):
        cfg = self.config
        use_pld = (cfg.stochastic_mode and pld_theta is not None
                   and not deterministic)
        # A latent-attention model's lane cache is this module's own and
        # crosses the layers as a value (models/latent_attention.py), so
        # that the leading dense blocks and the scanned stack write their
        # layers of the same stacked leaves
        lane, lane_cache = None, None
        if cfg.mla is not None and decode:
            from deepspeed_tpu.models.latent_attention import open_lane_cache

            lane_cache = open_lane_cache(self, cfg, x.shape[0], x.shape[1],
                                         mask)
            lane = lane_cache.lane
        # the leading dense blocks, each a module of its own before the
        # scanned stack of the rest
        dense_aux = jnp.float32(0.0)
        for i in range(cfg.first_k_dense):
            out = _maybe_gathered_block(
                _maybe_quantized_block(Block, cfg), cfg,
                self.path + (f"dense_{i}",))(
                    cfg, dense_mlp=True, name=f"dense_{i}")(
                x, mask=mask, segment_ids=segment_ids, positions=positions,
                deterministic=deterministic, decode=decode,
                cache_layer=i if lane is not None else None, lane=lane)
            if lane is not None:
                x, aux_i, lane = out
            else:
                x, aux_i = out
            dense_aux = dense_aux + aux_i
        n_scanned = cfg.n_layer - cfg.first_k_dense
        # A decode call on a cache that exists carries it through the loop
        # as ONE stacked [n_layer, ...] buffer per leaf, which each turn
        # updates in place at its own layer index (CausalSelfAttention's
        # cache_layer). Scanned over like the params, every turn would
        # slice its layer's whole leaf out of the stack and write it back
        # whole: at 1.3B with 16 lanes that was 73% of the decode step's
        # device time (PERF.md, PR 25). A carried collection must have one
        # structure entering and leaving a turn, so the apply that CREATES
        # the cache (a prefill) takes the scanned form: each turn makes its
        # layer's leaves, at positions XLA knows statically, and the loop
        # stacks them, which measured faster than zeros made before the
        # loop and then carried (same entry of PERF.md).
        carried = decode and self.has_variable("cache", "block")

        def call_block(block, x, mask, segment_ids, positions, layer_idx,
                       lane=None):
            # deterministic/decode ride the closure so remat never sees
            # them as traced booleans
            pld_keep = (pld_keep_probability(layer_idx, cfg.n_layer,
                                             pld_theta) if use_pld else None)
            if lane is not None:
                return block(x, mask=mask, deterministic=deterministic,
                             decode=decode, cache_layer=layer_idx, lane=lane)
            return block(x, mask=mask, segment_ids=segment_ids,
                         positions=positions, deterministic=deterministic,
                         decode=decode, pld_keep=pld_keep,
                         cache_layer=layer_idx if carried else None)

        if cfg.remat:
            call_block = nn.remat(call_block, prevent_cse=False,
                                  policy=_remat_policy(cfg.remat_policy))

        def body(block, carry, layer_idx):
            # None entries are valid (empty) pytree leaves in the carry
            x, mask, segment_ids, positions = carry[:4]
            if len(carry) == 5:
                x, l_aux, lane = call_block(block, x, mask, segment_ids,
                                            positions, layer_idx, carry[4])
                return (x, mask, segment_ids, positions, lane), l_aux
            x, l_aux = call_block(block, x, mask, segment_ids, positions,
                                  layer_idx)
            return (x, mask, segment_ids, positions), l_aux

        # stored form outermost: streamed in from the host, gathered over
        # fsdp, dequantised
        block_cls = _maybe_gathered_block(
            _maybe_quantized_block(Block, cfg), cfg, self.path + ("block",),
            stacked=n_scanned)
        if cfg.param_offload:
            # ZeRO-Infinity param tier: the scan's per-iteration slice of
            # the (host-resident) layer stack is copied into HBM right
            # before use — one layer's working set in device memory at a
            # time (ops/streaming.py; reference partition_parameters.py:537
            # remote_device="cpu" + coordinator fetch_sub_module)
            from deepspeed_tpu.ops.streaming import stream_tree_to_device

            block_cls = nn.map_variables(
                block_cls, "params", trans_in_fn=stream_tree_to_device,
                init=True)  # composes: stream int8-at-rest, dequant inner

        ahead = _maybe_layers_ahead(
            self, block_cls, cfg, n_scanned, x, mask, segment_ids, positions,
            deterministic, plain=not (decode or use_pld or lane is not None))
        if ahead is not None:
            return ahead[0], ahead[1] + dense_aux
        scanned = nn.scan(
            _maybe_in_place_experts(
                body, self, cfg, x.shape[0] * x.shape[1] * cfg.moe_top_k,
                decode),
            variable_axes={"params": 0, MOE_STATS: 0} if carried
            else {"params": 0, "cache": 0, MOE_STATS: 0},
            variable_carry="cache" if carried else False,
            split_rngs={"params": True, "dropout": True, "gating": True},
            in_axes=0,
            length=n_scanned,
            metadata_params={nn.PARTITION_NAME: "layers"},
        )
        carry = (x, mask, segment_ids, positions)
        if lane is not None:
            carry += (lane,)
        carry, l_aux = scanned(
            block_cls(cfg, name="block"), carry,
            jnp.arange(cfg.first_k_dense, cfg.n_layer))
        if lane is not None:
            lane_cache.close(carry[4])
        if cfg.first_k_dense:
            return carry[0], jnp.sum(l_aux) + dense_aux
        return carry[0], jnp.sum(l_aux)


def gpt_tp_rules(path: str, shape) -> "PartitionSpec":
    """Megatron-style tensor-parallel PartitionSpecs for GPT params
    (reference delegates training TP to a user mpu, engine.py:189; inference
    TP slices the same weights in module_inject/replace_module.py:18 —
    column-parallel qkv/fc1, row-parallel proj/fc2, vocab-parallel embedding).
    Consumed by ZeroShardingRules; dims not divisible by the tp axis are
    stripped there."""
    from jax.sharding import PartitionSpec

    ndim = len(shape)

    def dim(i):
        spec = [None] * ndim
        spec[i] = "tp"
        return PartitionSpec(*spec)

    if path.endswith(("attn/c_attn/kernel", "mlp/c_fc/kernel",
                      "mlp/c_gate/kernel", "attn/c_gate/kernel",
                      "attn/c_attn/bias", "mlp/c_fc/bias",
                      "mlp/c_gate/bias",
                      # the short convolution is depthwise: its channels
                      # split as its input projection's columns do
                      "conv/in_proj/kernel", "conv/conv_kernel")):
        return dim(-1)  # column parallel
    if path.endswith(("attn/c_proj/kernel", "mlp/c_proj/kernel",
                      "conv/out_proj/kernel")):
        return dim(-2)  # row parallel
    if path.endswith("wte/embedding"):
        return dim(0)   # vocab parallel (logits shard over vocab)
    if path.endswith(("lm_head/kernel", "lm_head")):
        return dim(-1)  # vocab-parallel untied head
    # expert-parallel MoE params (ep axis + Megatron tp inside each expert)
    from deepspeed_tpu.moe.layer import moe_param_spec

    return moe_param_spec(path, shape)


class GPT(nn.Module):
    """Decoder-only LM. ``__call__(batch)`` returns mean cross-entropy loss
    when ``batch["labels"]`` is present, else logits — the model contract the
    engine trains against (see runtime/engine.py)."""

    config: GPTConfig

    # engine reads this for TP sharding (runtime/zero/sharding.py)
    tp_rules = staticmethod(gpt_tp_rules)

    def param_offload_filter(self, path: str) -> bool:
        """Which param leaves the engine may place in host memory: exactly
        the ones this model streams back per-layer — the scanned stack
        under ``h`` (runtime/engine.py offload_param)."""
        return self.config.param_offload and path.startswith("['h']")

    @nn.compact
    def __call__(self, input_ids, labels=None, attention_mask=None,
                 segment_ids=None, positions=None, deterministic=True,
                 decode=False, pld_theta=None):
        cfg = self.config
        B, T = input_ids.shape
        # ZeRO-3 over fsdp > 1: the leaves outside the layer loop are
        # gathered where this method reads them (runtime/zero/gather.py;
        # nothing is wrapped anywhere else)
        wte = gathered_on_use(VocabEmbed, self.path + ("wte",), cfg.dtype)(
            cfg.vocab_size, cfg.n_embd, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="wte")
        x = wte(input_ids)
        x = scaled(x, cfg.embedding_multiplier)
        if cfg.embed_layernorm:  # BLOOM word_embeddings_layernorm
            x = _norm(cfg, "ln_embed")(x)
        if cfg.learned_positions:
            wpe = gathered_on_use(nn.Embed, self.path + ("wpe",), cfg.dtype)(
                cfg.n_positions, cfg.n_embd, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="wpe")
            if decode:
                # per-sequence position counters tracked alongside the
                # per-layer KV caches: with LEFT-padded ragged prompts the
                # learned position of a token is its count of valid
                # predecessors, not its physical cache slot
                position = self.variable("cache", "position",
                                         lambda: jnp.zeros((B,), jnp.int32))
                if attention_mask is not None:
                    am = attention_mask.astype(jnp.int32)
                    offs = jnp.clip(jnp.cumsum(am, axis=1) - 1, 0)
                    pos = position.value[:, None] + offs
                    position.value = position.value + jnp.sum(am, axis=1)
                else:
                    pos = position.value[:, None] + jnp.arange(T)[None, :]
                    position.value = position.value + T
            else:
                # packed batches reset positions at each document start so
                # every document sees the embeddings it would alone
                pos = (positions if positions is not None
                       else jnp.arange(T)[None, :])
            x = x + wpe(pos)
        x = nn.Dropout(cfg.dropout)(x, deterministic=deterministic)

        if cfg.layer_types is not None:
            # layers of several kinds: scanned run by run over parameters
            # and cache leaves stacked per kind
            from deepspeed_tpu.models.kind_stacks import KindStackedBlocks

            x, l_aux = KindStackedBlocks(cfg, name="h")(
                x, mask=attention_mask, segment_ids=segment_ids,
                positions=positions, deterministic=deterministic,
                decode=decode)
        elif cfg.scan_layers:
            x, l_aux = ScannedBlocks(cfg, name="h")(
                x, mask=attention_mask, segment_ids=segment_ids,
                positions=positions, deterministic=deterministic,
                decode=decode, pld_theta=pld_theta)
        else:
            l_aux = jnp.float32(0.0)
            use_pld = (cfg.stochastic_mode and pld_theta is not None
                       and not deterministic)

            def call_block(block, x, mask, segment_ids, positions, pld_keep):
                # closure keeps deterministic/decode static under remat
                return block(x, mask=mask, segment_ids=segment_ids,
                             positions=positions,
                             deterministic=deterministic,
                             decode=decode, pld_keep=pld_keep)

            if cfg.remat:
                call_block = nn.remat(call_block, prevent_cse=False,
                                      policy=_remat_policy(cfg.remat_policy))
            loop_block_cls = _maybe_quantized_block(Block, cfg)
            # a latent-attention model's lane cache is the model's own
            # here, the same stacked leaves as under ``ScannedBlocks``
            lane_cache = None
            if cfg.mla is not None and decode:
                from deepspeed_tpu.models.latent_attention import \
                    open_lane_cache

                lane_cache = open_lane_cache(self, cfg, B, T, attention_mask)
                lane = lane_cache.lane
            for i in range(cfg.n_layer):
                keep = (pld_keep_probability(i, cfg.n_layer, pld_theta)
                        if use_pld else None)
                block_cls = _maybe_gathered_block(
                    loop_block_cls, cfg, self.path + (f"h_{i}",))
                block = block_cls(cfg, dense_mlp=i < cfg.first_k_dense,
                                  name=f"h_{i}")
                if lane_cache is not None:
                    x, aux_i, lane = block(
                        x, mask=attention_mask, deterministic=deterministic,
                        decode=True, cache_layer=i, lane=lane)
                else:
                    x, aux_i = call_block(block, x, attention_mask,
                                          segment_ids, positions, keep)
                l_aux = l_aux + aux_i
            if lane_cache is not None:
                lane_cache.close(lane)

        if decode and labels is None and cfg.num_logits_to_keep:
            # the published ``num_logits_to_keep``: a prefill needs the
            # head at its last position, not [T, vocab] logits
            x = x[:, -cfg.num_logits_to_keep:]
        x = _norm(cfg, "ln_f")(x)
        # LM head (tied to wte, or a separate lm_head when untied): bf16
        # operands + fp32 accumulation keeps the MXU at full rate (a plain
        # fp32 matmul here runs ~8x slower and is ~1/3 of the model's flops
        # at this vocab size)
        if cfg.tie_word_embeddings:
            head_w = gather_tree(
                wte.embedding, self.path + ("wte", "embedding"),
                cfg.dtype, site="lm_head").astype(cfg.dtype)  # [V, C]
            head_dims = (((x.ndim - 1,), (1,)), ((), ()))
        else:
            head_w = gather_tree(self.param(
                "lm_head",
                nn.initializers.normal(0.02), (cfg.n_embd, cfg.vocab_size),
                cfg.param_dtype), self.path + ("lm_head",),
                cfg.dtype).astype(cfg.dtype)    # [C, V]
            head_dims = (((x.ndim - 1,), (0,)), ((), ()))
        head_b = (self.param("lm_head_bias", nn.initializers.zeros,
                             (cfg.vocab_size,), cfg.param_dtype)
                  if cfg.lm_head_bias else None)
        if labels is None:
            with jax.named_scope(SCOPE_LM_HEAD):
                logits = jax.lax.dot_general(
                    x.astype(cfg.dtype), head_w, head_dims,
                    preferred_element_type=jnp.float32)
                if head_b is not None:
                    logits = logits + head_b.astype(logits.dtype)
                logits = scaled(logits, cfg.lm_head_multiplier)
            return logits
        # the training paths fold the head's multiplier into its input
        x = scaled(x, cfg.lm_head_multiplier)
        # training path: the shift is expressed by zero-weighting the last
        # position instead of slicing, which keeps every tensor tile-aligned
        # (a [b, t-1, V] slice forces padded-tile reductions and a copy)
        fused = cfg.fused_head_ce
        if fused == "auto":
            # NOTE: B*T here is whatever the model was TRACED with — the
            # global batch under plain pjit, but the per-shard batch when
            # applied inside a shard_map/pipeline stage. Losses match
            # either way; only the 4 GB engage point is topology-dependent
            # (per-device logits are 1/dp of this under pjit). Force
            # fused_head_ce=True/int to pin the behavior across topologies.
            logits_bytes = (B * T * cfg.vocab_size
                            * jnp.dtype(cfg.dtype).itemsize)
            fused = logits_bytes >= (4 << 30)
        if fused:
            # fused head+CE: [tokens, vocab] logits never materialize —
            # the head runs chunk-by-chunk inside the loss vjp
            from deepspeed_tpu.ops.cross_entropy import (
                fused_linear_cross_entropy)

            targets, wts = _shifted_targets(labels, attention_mask,
                                            segment_ids)
            flat = x.astype(cfg.dtype).reshape(-1, cfg.n_embd)
            # bool first: True is an int and would read as chunk=1
            chunk = (fused if isinstance(fused, int)
                     and not isinstance(fused, bool) else 2048)
            with jax.named_scope(SCOPE_LM_HEAD_CE):
                loss = fused_linear_cross_entropy(
                    cfg.tie_word_embeddings, chunk, flat, head_w, head_b,
                    targets.reshape(-1), wts.reshape(-1))
        else:
            # unfused: materialize compute-dtype logits, fused CE math
            # (f32 reductions inside the fusion, bf16 cotangent)
            with jax.named_scope(SCOPE_LM_HEAD_CE):
                logits = jax.lax.dot_general(
                    x.astype(cfg.dtype), head_w, head_dims)
                if head_b is not None:
                    logits = logits + head_b.astype(logits.dtype)
                loss = cross_entropy_loss(logits, labels, attention_mask,
                                          segment_ids)
        if cfg.is_moe:
            # the blocks' auxiliary losses (load balance and router z-loss,
            # each with its coefficient), averaged over layers (reference
            # adds the per-MoE-layer l_aux into the training loss)
            loss = loss + l_aux / cfg.n_layer
        return loss


def _shifted_targets(labels, mask=None, segment_ids=None):
    """Next-token targets + f32 weights: target for position i is
    labels[i+1]; the last position gets a dummy target with zero weight —
    all tensors stay tile-aligned (no [b, t-1] slicing).

    With ``segment_ids`` (packed batches, deepspeed_tpu/data/), a position
    whose next token belongs to a DIFFERENT segment — a document's last
    token predicting the next document's first, or any pad (segment 0)
    position — is zero-weighted too. This is the third leg of the packing
    exactness condition (docs/data.md): the weighted mean then equals the
    token-count-weighted mean of the per-document losses."""
    b, t = labels.shape
    targets = jnp.concatenate(
        [labels[:, 1:], jnp.zeros((b, 1), labels.dtype)], axis=1)
    if mask is not None:
        w = mask.astype(jnp.float32)
        w = jnp.concatenate(
            [w[:, 1:], jnp.zeros((b, 1), jnp.float32)], axis=1)
    else:
        w = jnp.concatenate(
            [jnp.ones((b, t - 1), jnp.float32),
             jnp.zeros((b, 1), jnp.float32)], axis=1)
    if segment_ids is not None:
        seg_next = jnp.concatenate(
            [segment_ids[:, 1:], jnp.zeros((b, 1), segment_ids.dtype)],
            axis=1)
        w = w * ((segment_ids == seg_next)
                 & (segment_ids != 0)).astype(jnp.float32)
    return targets, w


def cross_entropy_loss(logits, labels, mask=None, segment_ids=None):
    """Mean next-token cross entropy with shift (f32 reductions fused over
    compute-dtype logits; see ops/cross_entropy.py)."""
    from deepspeed_tpu.ops.cross_entropy import softmax_cross_entropy

    b, t = labels.shape
    targets, w = _shifted_targets(labels, mask, segment_ids)
    flat = logits.reshape(b * t, logits.shape[-1])
    return softmax_cross_entropy(flat, targets.reshape(b * t),
                                 w.reshape(b * t))


def _latent_kind_params(C: int, kind: LatentKind) -> int:
    """One layer's attention parameters of a latent kind, exactly."""
    m, H, ix = kind.mla, kind.n_head, kind.indexer
    n = (C * m.q_rank + m.q_rank + m.q_rank * H * m.qk_dim
         + C * (m.kv_rank + m.rope_dim) + m.kv_rank
         + m.kv_rank * H * (m.nope_dim + m.v_dim) + H * m.v_dim * C)
    if kind.head_gate:
        n += C * H
    if ix is not None:
        # wq from the query latent, wk and its LayerNorm, weights_proj
        n += (m.q_rank * ix.n_heads * ix.head_dim + C * ix.head_dim
              + 2 * ix.head_dim + C * ix.n_heads)
    return n


def num_params(config: GPTConfig) -> int:
    """Approximate parameter count (for flops accounting); tracks the
    architecture-family knobs (GQA, gated MLP, untied head, biases)."""
    cfg = config
    C, L, V = cfg.n_embd, cfg.n_layer, cfg.vocab_size
    D, H, Hkv, F = cfg.head_dim, cfg.n_head, cfg.kv_heads, cfg.ffn_dim
    b = 1 if cfg.use_bias else 0
    ab = b if cfg.attn_bias is None else (1 if cfg.attn_bias else 0)
    attn = C * (H + 2 * Hkv) * D + ab * (H + 2 * Hkv) * D + H * D * C + ab * C
    mlp = (3 if cfg.gated_mlp else 2) * C * F + b * (
        (2 if cfg.gated_mlp else 1) * F + C)
    norm_p = C * (2 if (cfg.norm == "layernorm" and cfg.use_bias) else 1)
    per_layer = attn + mlp + 2 * norm_p
    if cfg.layer_types is not None:
        # by the declaration: a convolution layer holds its two
        # projections and its taps where an attention layer holds q/k/v/o
        n_conv = cfg.layer_types.count(KIND_CONV)
        conv = 4 * C * C + cfg.short_conv.width * C if n_conv else 0
        if cfg.attn_output_gate:
            # the gate's projection, and (exact for these stacks) the two
            # per-head norm weights
            attn += C * H * D + (2 * D if cfg.qk_norm == "head" else 0)
        norms = (4 if cfg.post_norms else 2) * norm_p
        attending = (L - n_conv) * attn
        if cfg.latent_kinds is not None:
            attending = sum(cfg.layer_types.count(mixer)
                            * _latent_kind_params(C, kind)
                            for mixer, kind in cfg.latent_kinds)
        return (V * C + L * (mlp + norms) + n_conv * conv
                + attending + norm_p
                + (0 if cfg.tie_word_embeddings else C * V))
    if cfg.retention is not None:
        # the gate's kernel and bias, q's and k's per-head norm
        per_layer += C * Hkv + Hkv + 2 * D
    if cfg.ssm is not None:
        m = cfg.ssm
        # projections, convolution and its bias, A_log / dt_bias / D, norm
        per_layer += (C * m.in_proj_dim + m.d_inner * C
                      + (m.d_conv + 1) * m.conv_dim
                      + 3 * m.n_heads + m.d_inner)
    total = V * C + L * per_layer + norm_p
    if cfg.learned_positions:
        total += cfg.n_positions * C
    if not cfg.tie_word_embeddings:
        total += C * V
    if cfg.lm_head_bias:
        total += V
    return total


def train_flops_per_token(config: GPTConfig) -> float:
    """6N + attention flops per token (standard accounting)."""
    N = num_params(config) - config.vocab_size * config.n_embd  # non-embedding
    return 6.0 * N
