"""LFM2-MoE's stack (gated short convolutions with a grouped-query
attention layer every few of them, a sigmoid router corrected by a bias,
every expert held) served through ``init_inference`` ->
``serving.build_serving`` -> the continuous-batching scheduler, the entry
points the other serve cells use, with the plain reference beside it.
Sizes come from the configuration file's published keys."""
import numpy as np

from perfbench import lfm2_flops
from perfbench.builders import _common, deepseek_v2_serve

# the published names of the layers' kinds -> the program's
KINDS = {"conv": "conv", "full_attention": "attention"}


def model_config(config, section=None):
    """The program's ``GPTConfig`` for a configuration file's published
    keys, served as its ``serve`` section (or ``section``) says."""
    from deepspeed_tpu.models.transformer_lm import GPTConfig, ShortConvConfig

    from perfbench.reference import lfm2

    c, s = config, section or config["serve"]
    sizes = lfm2.sizes(c)       # raises for another form of the stack
    return GPTConfig(
        vocab_size=c["vocab_size"], n_positions=s["cache_positions"],
        n_embd=c["hidden_size"], n_layer=c["num_hidden_layers"],
        n_head=c["num_attention_heads"],
        n_kv_head=c["num_key_value_heads"],
        intermediate_size=c["intermediate_size"], norm="rmsnorm",
        layer_norm_epsilon=c["norm_eps"], activation="silu", gated_mlp=True,
        use_bias=False, rotary=True, rope_theta=sizes["theta"],
        learned_positions=False,
        tie_word_embeddings=c["tie_word_embeddings"], qk_norm="head",
        dtype=_common.dtype(s["compute_dtype"]),
        param_dtype=_common.dtype(s["param_dtype"]), scan_layers=True,
        use_flash_attention=False, num_logits_to_keep=1,
        layer_types=tuple(KINDS[k] for k in c["layer_types"]),
        short_conv=ShortConvConfig(width=c["conv_L_cache"]),
        first_k_dense=c["num_dense_layers"],
        moe_num_experts=c["num_experts"],
        moe_top_k=c["num_experts_per_tok"], moe_drop_tokens=False,
        moe_gated_experts=True, moe_norm_topk_prob=c["norm_topk_prob"],
        moe_intermediate_size=c["moe_intermediate_size"],
        moe_routed_scale=float(c["routed_scaling_factor"]),
        moe_scoring="sigmoid", moe_expert_bias=c["use_expert_bias"],
        moe_expert_bias_init=float(c["moe"]["expert_bias_std"]))


def layer_sizes(c):
    return dict(hidden=c["hidden_size"], taps=c["conv_L_cache"],
                n_heads=c["num_attention_heads"],
                n_kv_heads=c["num_key_value_heads"],
                head_dim=c["hidden_size"] // c["num_attention_heads"])


class KindsServeSystem(deepseek_v2_serve.LatentServeSystem):
    """``LatentServeSystem`` (the plan event, the live positions, the
    routers' load, the margins of the served tokens) whose reference is
    ``perfbench/reference/lfm2.py`` and whose lanes' "state" is what each
    kind of layer keeps: keys and values of every row a live lane's
    request wrote in the attention layers, the tails of the convolution
    layers."""

    _head = None

    def head(self):
        """The tied head as the reference's ``position_stats`` reads one,
        transposed once."""
        if self._head is None:
            from perfbench.reference import lfm2

            self._head = lfm2.head_of(self.reference_params())
        return self._head

    def live_lanes(self, count, rng):
        """``LatentServeSystem.live_lanes`` with each chosen lane's
        ``conv_tail`` ``[convolution layers, taps - 1, C]`` beside its
        ``cached_key`` / ``cached_value`` ``[attention layers, S, Hkv,
        D]`` and ``valid``: the per-kind stacks of the scheduler's lane
        cache, one lane of each."""
        kept = self.scheduler.lanes_at_exit
        lanes = super().live_lanes(count, rng)
        return [dict(lane, **kept.recurrent_state(lane["lane"]))
                for lane in lanes]

    def reference_pass(self, seq, offset=0):
        """``hidden_and_states`` of the plain reference over ``seq``, one
        float32 forward of the same parameters, right-padded with zeros to
        the lane cache's length so that every request has one shape, the
        first token at rotary position ``offset``."""
        from perfbench.reference import lfm2

        c = self.env.config
        if self._reference is None:
            self._reference = lfm2.sizes(c)
        ids = np.zeros((int(c["serve"]["cache_positions"]),), np.int32)
        ids[:len(seq)] = seq
        return ids, lfm2.hidden_and_states(
            self.reference_params(), ids, self._reference, length=len(seq),
            offset=offset)

    def decoded_stats(self, prompt, tokens):
        """``margin`` of every served token of a request under the
        reference teacher-forced over prompt + served tokens."""
        from perfbench.reference import lfm2

        seq = list(prompt) + [int(t) for t in tokens[:-1]]
        ids, (hidden, *_) = self.reference_pass(seq)
        at = list(range(len(prompt) - 1, len(seq)))
        return {"margin": lfm2.position_stats(
            self.head(), ids, self._reference, at, tokens, pad_to=128,
            states=hidden)["margin"].tolist()}

    def state_errors(self, prompt, lane):
        """For one of ``live_lanes``: the norm of the difference between
        what the lane keeps and the reference's for the same tokens (the
        prompt, then the lane's ``tokens``; rotary counting cache rows, as
        the program's does) over the norm of the reference's: ``by_layer``
        of keys and values together over the rows the request wrote, an
        entry an ATTENTION layer; ``by_head`` the same per KV head
        (``[attention layers][Hkv]``); ``tail_by_layer`` of the tails, an
        entry a CONVOLUTION layer. The rows must be exactly those
        ``valid`` marks."""
        import jax.numpy as jnp

        n = len(prompt) + len(lane["tokens"])
        bucket = self.scheduler.prompt_bucket
        first = -(-len(prompt) // bucket) * bucket - len(prompt)
        _, (_, k, v, tails) = self.reference_pass(
            list(prompt) + lane["tokens"], offset=first)
        valid = np.asarray(lane["valid"][0])
        if valid[first:first + n].sum() != n or valid.sum() != n:
            raise ValueError(
                f"lane {lane['lane']} marks {int(valid.sum())} rows valid, "
                f"its request wrote {n} from row {first}")

        def sums(got, ref, axes):
            diff = got.astype(jnp.float32) - ref
            return (np.asarray(jnp.sum(diff * diff, axes), np.float64),
                    np.asarray(jnp.sum(ref * ref, axes), np.float64))

        def rows(leaf, ref):
            # (two KV heads of 64 lie side by side in a stored row of 128)
            return leaf[:, first:first + n].reshape(ref[:, :n].shape)

        num, den = (a + b for a, b in zip(
            sums(rows(lane["cached_key"], k), k[:, :n], (1, 3)),
            sums(rows(lane["cached_value"], v), v[:, :n],
                 (1, 3))))                          # [attention layers, Hkv]
        t_num, t_den = sums(lane["conv_tail"], tails, (1, 2))
        return {"by_layer": np.sqrt(num.sum(1) / den.sum(1)).tolist(),
                "by_head": np.sqrt(num / den).tolist(),
                "tail_by_layer": np.sqrt(t_num / t_den).tolist()}


def build(env, plan):
    import deepspeed_tpu
    from deepspeed_tpu import serving
    from deepspeed_tpu.models.transformer_lm import GPT

    c, s = env.config, env.config["serve"]
    engine = deepspeed_tpu.init_inference(
        GPT(model_config(c)), dtype=s["dtype"],
        seed=_common.program_seed(env.seed))
    system = KindsServeSystem(env, engine, None, None)
    system.subscribe(system.on_bus)      # the plan, and the live positions
    system.scheduler = serving.build_serving(engine, dict(s["serving"]))
    system.scheduler.retain_lanes = True      # ``live_lanes`` reads them
    itemsize = 2 if s["dtype"] in ("bf16", "bfloat16") else 4
    kinds = [KINDS[k] for k in c["layer_types"]]
    slots, sizes = system.scheduler.slots, layer_sizes(c)
    system.info = {
        "slots": slots,
        "decode_program": "jit_decode_k",
        "weight_bytes": lfm2_flops.decode_weight_bytes(
            kinds, c["num_dense_layers"], c["vocab_size"],
            dense_width=c["intermediate_size"],
            expert_width=c["moe_intermediate_size"],
            n_experts=c["num_experts"], itemsize=itemsize, **sizes),
        "kv_bytes_per_position": lfm2_flops.kv_bytes_per_position(
            kinds.count("attention"), sizes["n_kv_heads"],
            sizes["head_dim"], itemsize),
        "state_layers": kinds.count("conv"),
        "state_bytes_per_lane": lfm2_flops.conv_tail_bytes(
            kinds.count("conv"), sizes["hidden"], sizes["taps"], itemsize),
        # every lane's token chooses top_k of the experts, all held
        "held_experts_step": dict(
            lfm2_flops.experts_step(
                slots * c["num_experts_per_tok"], c["hidden_size"],
                c["moe_intermediate_size"], c["num_experts"], itemsize),
            calls_per_step=len(kinds) - c["num_dense_layers"]),
    }
    return system
