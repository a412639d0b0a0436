"""DeepSeek-V2's block (latent attention, a leading dense layer, an expert
layer of which this chip holds one device group beside the shared experts)
served through ``init_inference`` -> ``serving.build_serving`` -> the
continuous-batching scheduler, the entry points the GPT cells use, with the
plain reference beside it. Sizes come from the configuration file's
published keys and its ``moe`` block."""
import time

import numpy as np

from perfbench import mla_flops
from perfbench.builders import _common, falcon_h1_serve


def model_config(config, section=None):
    """The program's ``GPTConfig`` for a configuration file's published
    keys, served as its ``serve`` section (or ``section``) says."""
    from deepspeed_tpu.models.transformer_lm import GPTConfig, MLAConfig

    from perfbench.reference import deepseek_v2

    c, s, sc = config, section or config["serve"], config["rope_scaling"]
    deepseek_v2.sizes(c)        # raises for another form of the block
    return GPTConfig(
        vocab_size=c["vocab_size"], n_positions=s["cache_positions"],
        n_embd=c["hidden_size"], n_layer=c["num_hidden_layers"],
        n_head=c["num_attention_heads"],
        intermediate_size=c["intermediate_size"], norm="rmsnorm",
        layer_norm_epsilon=c["rms_norm_eps"], activation=c["hidden_act"],
        gated_mlp=True, use_bias=False, rotary=True,
        rope_theta=float(c["rope_theta"]), learned_positions=False,
        tie_word_embeddings=c["tie_word_embeddings"],
        dtype=_common.dtype(s["compute_dtype"]),
        param_dtype=_common.dtype(s["param_dtype"]), scan_layers=True,
        use_flash_attention=False, num_logits_to_keep=1,
        mla=MLAConfig(
            q_rank=c["q_lora_rank"], kv_rank=c["kv_lora_rank"],
            nope_dim=c["qk_nope_head_dim"], rope_dim=c["qk_rope_head_dim"],
            v_dim=c["v_head_dim"], yarn_factor=float(sc["factor"]),
            yarn_original_positions=sc["original_max_position_embeddings"],
            yarn_beta_fast=float(sc["beta_fast"]),
            yarn_beta_slow=float(sc["beta_slow"]),
            yarn_mscale=float(sc["mscale"]),
            yarn_mscale_all_dim=float(sc["mscale_all_dim"])),
        first_k_dense=c["first_k_dense_replace"],
        moe_num_experts=c["moe"]["routed_over"],
        moe_top_k=c["num_experts_per_tok"], moe_drop_tokens=False,
        moe_gated_experts=True, moe_norm_topk_prob=c["norm_topk_prob"],
        moe_intermediate_size=c["moe_intermediate_size"],
        moe_n_shared=c["n_shared_experts"], moe_n_group=c["n_group"],
        moe_topk_group=c["topk_group"],
        moe_routed_scale=float(c["routed_scaling_factor"]),
        moe_experts_held=tuple(c["moe"]["experts_held"]))


def attention_sizes(c):
    return dict(n_heads=c["num_attention_heads"], q_rank=c["q_lora_rank"],
                kv_rank=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
                rope=c["qk_rope_head_dim"], v_dim=c["v_head_dim"])


class LatentServeSystem(falcon_h1_serve.HybridServeSystem):
    """``HybridServeSystem`` (the plan event, the margins of the served
    tokens) whose reference is ``perfbench/reference/deepseek_v2.py`` and
    whose lanes' "state" is their latent cache: the compressed latent and
    the rotary key of every position a live lane's request wrote."""

    _load = None

    def __init__(self, *args):
        super().__init__(*args)
        self.live_positions = []    # (monotonic time, the scheduler's sum)

    def on_bus(self, ev):
        super().on_bus(ev)
        if ev.get("kind") == "serve.stats" and "live_positions" in ev:
            self.live_positions.append((time.monotonic(),
                                        ev["live_positions"]))

    def mean_live_positions(self):
        """The mean over the window's scheduler iterations of the rows
        that the requests in the lanes have written (the program's
        ``serve.stats`` event counts them from the scheduler's clocks);
        None without such events."""
        lo, hi = self.env.t_open, self.env.t_close
        inside = [n for t, n in self.live_positions if lo <= t <= hi]
        return sum(inside) / len(inside) if inside else None

    def expert_load(self):
        """The program's ``moe.load`` event for one seeded batch of
        tokens at the served parameters: one forward pass of its own, made
        once per run and after the window."""
        if self._load is None:
            from deepspeed_tpu.moe.utils import publish_expert_load

            ids = np.random.default_rng([self.env.seed, 5]).integers(
                0, self.env.config["vocab_size"],
                size=tuple(self.env.config["serve"]["load_batch"]))
            self._load = publish_expert_load(
                self.engine.module, self.engine.params,
                {"input_ids": ids.astype(np.int32)})
        return self._load

    def live_lanes(self, count, rng):
        """Of ``count`` lanes (``rng`` chooses) that held a request when
        the run ended: ``{"lane", "request_id", "tokens"`` (all that the
        lane's cache has taken in after the prompt)``, "cached_latent"
        [layers, S, kv_rank], "cached_rope_key" [layers, S, rope], "valid"
        [1, S]}``, sliced out of the scheduler's own lane cache as the
        last decode step of the window left it. The cache is let go
        afterwards: the reference needs its room."""
        kept, self.scheduler.lanes_at_exit = \
            self.scheduler.lanes_at_exit, None
        if kept is None:
            return []
        lanes = sorted(kept.live)
        chosen = rng.choice(len(lanes), size=min(count, len(lanes)),
                            replace=False)
        return [dict(lane=lanes[i], request_id=kept.live[lanes[i]].request_id,
                     tokens=[int(t) for t in kept.live[lanes[i]].tokens],
                     **kept.positions(lanes[i])) for i in chosen]

    def reference_pass(self, seq, offset=0):
        """``hidden_and_states`` of the plain reference over ``seq``, one
        float32 forward of the same parameters, right-padded with zeros to
        the lane cache's length so that every request has one shape, the
        first token at position ``offset``."""
        from perfbench.reference import deepseek_v2

        c = self.env.config
        if self._reference is None:
            self._reference = deepseek_v2.sizes(c)
        ids = np.zeros((int(c["serve"]["cache_positions"]),), np.int32)
        ids[:len(seq)] = seq
        return ids, deepseek_v2.hidden_and_states(
            self.reference_params(), ids, self._reference, length=len(seq),
            offset=offset)

    def state_errors(self, prompt, lane):
        """For one of ``live_lanes``: the norm of the difference between
        the lane's latents ``c_kv`` over the rows its request wrote (the
        prompt, then the lane's ``tokens``; the bucket's left padding is
        not among them) and the reference's for the same tokens, over the
        norm of the reference's (rotary counting cache rows, as the
        program's does: the reference starts at the lane's first row),
        ``by_layer``; ``by_head`` the same again
        with one entry a layer (a latent has no heads; the kind reads the
        first layer's); ``tail_by_layer`` the same of the rotary keys
        ``k_rope``. The rows must be exactly those ``valid`` marks."""
        import jax.numpy as jnp

        n = len(prompt) + len(lane["tokens"])
        bucket = self.scheduler.prompt_bucket
        first = -(-len(prompt) // bucket) * bucket - len(prompt)
        _, (_, latent, rope_key) = self.reference_pass(
            list(prompt) + lane["tokens"], offset=first)
        valid = np.asarray(lane["valid"][0])
        if valid[first:first + n].sum() != n or valid.sum() != n:
            raise ValueError(
                f"lane {lane['lane']} marks {int(valid.sum())} rows valid, "
                f"its request wrote {n} from row {first}")

        def relative(got, ref):
            diff = got[:, first:first + n].astype(jnp.float32) - ref[:, :n]
            return np.sqrt(np.asarray(
                jnp.sum(diff * diff, (1, 2))
                / jnp.sum(ref[:, :n] * ref[:, :n], (1, 2))))

        by_layer = relative(lane["cached_latent"], latent).tolist()
        return {"by_layer": by_layer, "by_head": [[e] for e in by_layer],
                "tail_by_layer": relative(lane["cached_rope_key"],
                                          rope_key).tolist()}


def build(env, plan):
    import deepspeed_tpu
    from deepspeed_tpu import serving
    from deepspeed_tpu.models.transformer_lm import GPT

    c, s = env.config, env.config["serve"]
    engine = deepspeed_tpu.init_inference(
        GPT(model_config(c)), dtype=s["dtype"],
        seed=_common.program_seed(env.seed))
    system = LatentServeSystem(env, engine, None, None)
    system.subscribe(system.on_bus)      # the plan, and the live positions
    system.scheduler = serving.build_serving(engine, dict(s["serving"]))
    system.scheduler.retain_lanes = True      # ``live_lanes`` reads them
    itemsize = 2 if s["dtype"] in ("bf16", "bfloat16") else 4
    layers, slots = c["num_hidden_layers"], system.scheduler.slots
    first, held = c["moe"]["experts_held"]
    moe_layers = layers - c["first_k_dense_replace"]
    # the pairs a step routes to the held experts, by the routers' own
    # balance: every lane's token chooses top_k of routed_over
    rows = slots * c["num_experts_per_tok"] * held / c["moe"]["routed_over"]
    experts = mla_flops.held_experts_step(
        rows, c["hidden_size"], c["moe_intermediate_size"], held, itemsize)
    system.info = {
        "slots": slots,
        "decode_program": "jit_decode_k",
        "weight_bytes": mla_flops.decode_weight_bytes(
            layers, c["first_k_dense_replace"], c["vocab_size"],
            c["hidden_size"], c["intermediate_size"],
            c["moe_intermediate_size"], held, c["n_shared_experts"],
            c["moe"]["routed_over"], itemsize, **attention_sizes(c)),
        "kv_bytes_per_position": mla_flops.latent_bytes_per_position(
            layers, c["kv_lora_rank"], c["qk_rope_head_dim"], itemsize),
        "latent_attention": dict(
            layers=layers, itemsize=itemsize,
            **{k: v for k, v in attention_sizes(c).items()
               if k != "q_rank"}),
        "held_experts_step": dict(experts, calls_per_step=moe_layers),
    }
    return system
