"""A hybrid (Mamba-2 beside attention) decoder served through
``init_inference`` -> ``serving.build_serving`` -> the continuous-batching
scheduler, the entry points the GPT cells use, with the plain reference
beside it. Sizes come from the configuration file's published keys."""
import numpy as np

from perfbench import ssm_flops
from perfbench.builders import _common, gpt_serve


def model_config(config, section=None):
    """The program's ``GPTConfig`` for a configuration file's published
    keys, served as its ``serve`` section (or ``section``) says."""
    from deepspeed_tpu.models.transformer_lm import GPTConfig, SSMConfig

    from perfbench.reference import falcon_h1

    c, s = config, section or config["serve"]
    falcon_h1.sizes(c)      # raises for another form of the mixer
    gate_m, down_m = c["mlp_multipliers"]
    ssm = SSMConfig(
        n_heads=c["mamba_n_heads"], d_head=c["mamba_d_head"],
        d_state=c["mamba_d_state"], n_groups=c["mamba_n_groups"],
        d_conv=c["mamba_d_conv"], chunk=c["mamba_chunk_size"],
        in_multiplier=c["ssm_in_multiplier"],
        out_multiplier=c["ssm_out_multiplier"],
        multipliers=tuple(c["ssm_multipliers"]),
        state_dtype=_common.dtype(s["state_dtype"]))
    if c["mamba_d_ssm"] != ssm.d_inner:
        raise ValueError("mamba_d_ssm is not mamba_n_heads * mamba_d_head")
    return GPTConfig(
        vocab_size=c["vocab_size"], n_positions=s["cache_positions"],
        n_embd=c["hidden_size"], n_layer=c["num_hidden_layers"],
        n_head=c["num_attention_heads"],
        n_kv_head=c["num_key_value_heads"], attn_head_dim=c["head_dim"],
        intermediate_size=c["intermediate_size"], norm="rmsnorm",
        layer_norm_epsilon=c["rms_norm_eps"], activation=c["hidden_act"],
        gated_mlp=True, use_bias=False, rotary=True,
        rope_theta=float(c["rope_theta"]), learned_positions=False,
        tie_word_embeddings=c["tie_word_embeddings"],
        dtype=_common.dtype(s["compute_dtype"]),
        param_dtype=_common.dtype(s["param_dtype"]), scan_layers=True,
        use_flash_attention=s["use_flash_attention"], ssm=ssm,
        embedding_multiplier=c["embedding_multiplier"],
        lm_head_multiplier=c["lm_head_multiplier"],
        attention_in_multiplier=c["attention_in_multiplier"],
        attention_out_multiplier=c["attention_out_multiplier"],
        key_multiplier=c["key_multiplier"], mlp_gate_multiplier=gate_m,
        mlp_down_multiplier=down_m,
        num_logits_to_keep=c["num_logits_to_keep"])


def layer_sizes(c):
    return dict(width=c["intermediate_size"],
                n_heads=c["num_attention_heads"],
                n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                d_ssm=c["mamba_d_ssm"], n_groups=c["mamba_n_groups"],
                d_state=c["mamba_d_state"], ssm_heads=c["mamba_n_heads"],
                d_conv=c["mamba_d_conv"])


class HybridServeSystem(gpt_serve.ServeSystem):
    """``ServeSystem`` whose reference is ``perfbench/reference/
    falcon_h1.py`` and which can also judge the tokens after the first and
    the recurrent state that the window's last step left in the lanes."""

    cache_plan = None      # the program's ``serve.cache_plan`` event

    def on_bus(self, ev):
        if ev.get("kind") == "serve.cache_plan":
            self.cache_plan = ev

    def reference_params(self):
        """The weights the reference reads: the engine's own. A control
        that serves other weights than the seed made hands the seed's back
        from here."""
        return self.engine.params

    def live_lanes(self, count, rng):
        """Of ``count`` lanes (``rng`` chooses) that held a request when
        the run ended: ``{"lane", "request_id", "tokens"`` (all that the
        lane's cache has taken in after the prompt)``, "ssm_state" [layers,
        H, P, N], "conv_tail" [layers, K - 1, channels]}``, the last two
        sliced out of the scheduler's own lane cache as the last decode
        step of the window left it (``scheduler.lanes_at_exit``). The
        cache is let go afterwards: the reference needs its room."""
        kept, self.scheduler.lanes_at_exit = \
            self.scheduler.lanes_at_exit, None
        if kept is None:
            return []
        lanes = sorted(kept.live)
        chosen = rng.choice(len(lanes), size=min(count, len(lanes)),
                            replace=False)
        return [dict(lane=lanes[i], request_id=kept.live[lanes[i]].request_id,
                     tokens=[int(t) for t in kept.live[lanes[i]].tokens],
                     **kept.recurrent_state(lanes[i])) for i in chosen]

    def reference_pass(self, seq):
        """``hidden_and_states`` of the plain reference over ``seq``, one
        float32 forward of the same parameters, right-padded with zeros to
        the lane cache's length so that every request has one shape."""
        from perfbench.reference import falcon_h1

        c = self.env.config
        if self._reference is None:
            self._reference = falcon_h1.sizes(c)
        ids = np.zeros((int(c["serve"]["cache_positions"]),), np.int32)
        ids[:len(seq)] = seq
        return ids, falcon_h1.hidden_and_states(
            self.reference_params(), ids, self._reference, length=len(seq))

    def decoded_stats(self, prompt, tokens):
        """``margin`` of every served token of a request under the
        reference teacher-forced over prompt + served tokens: how far below
        the reference's largest logit at its position it lies, in units of
        that position's logit standard deviation."""
        from perfbench.reference import falcon_h1

        seq = list(prompt) + [int(t) for t in tokens[:-1]]
        ids, (hidden, _, _) = self.reference_pass(seq)
        at = list(range(len(prompt) - 1, len(seq)))
        return {"margin": falcon_h1.position_stats(
            self.reference_params(), ids, self._reference, at, tokens,
            pad_to=128, states=hidden)["margin"].tolist()}

    def state_errors(self, prompt, lane):
        """For one of ``live_lanes``: the norm of the difference between
        the lane's recurrent state and the reference's after the same
        tokens (the prompt, then the lane's ``tokens``) over the norm of
        the reference's, ``by_layer`` and ``by_head`` (``[layers][H]``),
        and the same of the convolution's tail, ``tail_by_layer``."""
        import jax.numpy as jnp

        _, (_, state, tail) = self.reference_pass(
            list(prompt) + lane["tokens"])

        def relative(got, ref, axes):
            diff = got.astype(jnp.float32) - ref
            return jnp.sum(diff * diff, axes), jnp.sum(ref * ref, axes)

        num, den = relative(lane["ssm_state"], state, (2, 3))
        t_num, t_den = relative(lane["conv_tail"], tail, (1, 2))
        return {"by_layer": np.sqrt(np.asarray(num.sum(1) / den.sum(1))
                                    ).tolist(),
                "by_head": np.sqrt(np.asarray(num / den)).tolist(),
                "tail_by_layer": np.sqrt(np.asarray(t_num / t_den)).tolist()}

    def first_token_margin(self, prompt, token):
        margin = self.decoded_stats(prompt, [token])["margin"][0]
        return {"margin": float(margin), "prompt_len": len(prompt),
                "tolerance": float(
                    self.env.config["serve"]["first_token_tolerance"]),
                "is_argmax": margin == 0.0}


def build(env, plan):
    import deepspeed_tpu
    from deepspeed_tpu import serving
    from deepspeed_tpu.models.transformer_lm import GPT

    c, s = env.config, env.config["serve"]
    engine = deepspeed_tpu.init_inference(
        GPT(model_config(c)), dtype=s["dtype"],
        seed=_common.program_seed(env.seed))
    system = HybridServeSystem(env, engine, None, None)
    system.subscribe(system.on_bus)      # the plan is published once
    system.scheduler = serving.build_serving(engine, dict(s["serving"]))
    system.scheduler.retain_lanes = True      # ``live_lanes`` reads them
    itemsize = 2 if s["dtype"] in ("bf16", "bfloat16") else 4
    state_itemsize = 4 if s["state_dtype"] in ("float32", "fp32") else 2
    layers = c["num_hidden_layers"]
    system.info = {
        "slots": system.scheduler.slots,
        "decode_program": "jit_decode_k",
        "weight_bytes": ssm_flops.decode_weight_bytes(
            layers, c["vocab_size"], c["hidden_size"], itemsize,
            **layer_sizes(c)),
        "kv_bytes_per_position": ssm_flops.kv_bytes_per_position(
            layers, c["num_key_value_heads"], c["head_dim"], itemsize),
        "state_layers": layers,
        "state_bytes_per_lane": layers * (
            ssm_flops.state_bytes(c["mamba_n_heads"], c["mamba_d_head"],
                                  c["mamba_d_state"], state_itemsize)
            + ssm_flops.conv_tail_bytes(
                c["mamba_d_ssm"], c["mamba_n_groups"], c["mamba_d_state"],
                c["mamba_d_conv"], itemsize)),
        "scan_step": {
            "bytes": ssm_flops.scan_step_bytes(
                system.scheduler.slots, c["mamba_n_heads"],
                c["mamba_d_head"], c["mamba_d_state"], c["mamba_n_groups"],
                state_itemsize),
            "flops": ssm_flops.scan_step_flops(
                system.scheduler.slots, c["mamba_n_heads"],
                c["mamba_d_head"], c["mamba_d_state"]),
            "calls_per_step": layers},
    }
    return system
