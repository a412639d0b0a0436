"""An attention-free decoder (power retention in place of attention)
served through ``init_inference`` -> ``serving.build_serving`` -> the
continuous-batching scheduler, the entry points the GPT cells use, with the
plain reference beside it. Sizes come from the configuration file's
published keys and its ``retention`` block."""
import numpy as np

from perfbench import retention_flops
from perfbench.builders import _common, falcon_h1_serve


def model_config(config, section=None):
    """The program's ``GPTConfig`` for a configuration file's published
    keys, served as its ``serve`` section (or ``section``) says."""
    from deepspeed_tpu.models.transformer_lm import GPTConfig, RetentionConfig

    from perfbench.reference import brumby

    c, s, r = config, section or config["serve"], config["retention"]
    brumby.sizes(c)         # raises for another form of the block
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
        use_flash_attention=False, num_logits_to_keep=1,
        retention=RetentionConfig(
            chunk=s["prefill_chunk"], eps=r["eps"],
            state_dtype=_common.dtype(s["state_dtype"])))


def layer_sizes(c):
    return dict(width=c["intermediate_size"],
                n_heads=c["num_attention_heads"],
                n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"])


def stored_order(head_dim):
    """For each entry of the reference's symmetric square (pairs ``a <= b``
    in lexicographic order), where the program stores it."""
    from deepspeed_tpu.ops.power_retention import sympow2_pairs

    from perfbench.reference import brumby

    a, b, live = sympow2_pairs(head_dim)
    at = {(min(i, j), max(i, j)): n
          for n, (i, j, counts) in enumerate(zip(a, b, live)) if counts}
    return np.asarray([at[pair] for pair in zip(*brumby.pairs(head_dim))])


class RetentionServeSystem(falcon_h1_serve.HybridServeSystem):
    """``HybridServeSystem`` (the plan event, the live lanes read out of
    the scheduler's own cache as whatever leaves the model declares, the
    margins of the served tokens: all generic over a state) whose
    reference is ``perfbench/reference/brumby.py`` and whose state is
    ``S`` with its normaliser ``z``."""

    def reference_pass(self, seq):
        """``hidden_and_states`` of the plain reference over ``seq``, one
        float32 forward of the same parameters, right-padded with zeros to
        the longest request's length so that every request has one
        shape."""
        from perfbench.reference import brumby

        c = self.env.config
        if self._reference is None:
            self._reference = brumby.sizes(c)
        ids = np.zeros((int(c["serve"]["cache_positions"]),), np.int32)
        ids[:len(seq)] = seq
        return ids, brumby.hidden_and_states(
            self.reference_params(), ids, self._reference, length=len(seq))

    def state_errors(self, prompt, lane):
        """For one of ``live_lanes`` (``"ret_state" [layers, Hkv, d, D]``,
        ``"ret_norm" [layers, Hkv, D]`` as stored): the norm of the
        difference between the lane's state ``S`` and the reference's
        after the same tokens (the prompt, then the lane's ``tokens``)
        over the norm of the reference's, ``by_layer`` and ``by_head``
        (``[layers][Hkv]``), and the same of the normaliser ``z``,
        ``tail_by_layer`` (the kind's third statistic reads whatever
        second leaf the builder names). The lane's leaves are brought into
        the reference's order of the symmetric square first."""
        import jax.numpy as jnp

        _, (_, state, norm) = self.reference_pass(
            list(prompt) + lane["tokens"])
        order = stored_order(int(self.env.config["head_dim"]))

        def relative(got, ref, axes):
            diff = got.astype(jnp.float32) - ref
            return jnp.sum(diff * diff, axes), jnp.sum(ref * ref, axes)

        # the program stores S with the symmetric square's axis minor
        num, den = relative(
            jnp.swapaxes(lane["ret_state"], -1, -2)[:, :, order], state,
            (2, 3))
        z_num, z_den = relative(lane["ret_norm"][:, :, order], norm, (1, 2))
        return {"by_layer": np.sqrt(np.asarray(num.sum(1) / den.sum(1))
                                    ).tolist(),
                "by_head": np.sqrt(np.asarray(num / den)).tolist(),
                "tail_by_layer": np.sqrt(np.asarray(z_num / z_den)).tolist()}


def build(env, plan):
    import deepspeed_tpu
    from deepspeed_tpu import serving
    from deepspeed_tpu.models.transformer_lm import GPT

    c, s = env.config, env.config["serve"]
    engine = deepspeed_tpu.init_inference(
        GPT(model_config(c)), dtype=s["dtype"],
        seed=_common.program_seed(env.seed))
    system = RetentionServeSystem(env, engine, None, None)
    system.subscribe(system.on_bus)      # the plan is published once
    system.scheduler = serving.build_serving(engine, dict(s["serving"]))
    system.scheduler.retain_lanes = True      # ``live_lanes`` reads them
    itemsize = 2 if s["dtype"] in ("bf16", "bfloat16") else 4
    state_itemsize = 4 if s["state_dtype"] in ("float32", "fp32") else 2
    layers, slots = c["num_hidden_layers"], system.scheduler.slots
    heads = (c["num_attention_heads"], c["num_key_value_heads"],
             c["head_dim"])
    system.info = {
        "slots": slots,
        "decode_program": "jit_decode_k",
        "weight_bytes": retention_flops.decode_weight_bytes(
            layers, c["vocab_size"], c["hidden_size"], itemsize,
            **layer_sizes(c)),
        "kv_bytes_per_position": 0.0,
        "state_layers": layers,
        "state_bytes_per_lane": layers * (
            retention_flops.state_bytes(*heads[1:], state_itemsize)
            + retention_flops.norm_bytes(*heads[1:], state_itemsize)),
        "retention_step": {
            "bytes": retention_flops.step_bytes(slots, *heads,
                                                state_itemsize),
            "flops": retention_flops.step_flops(slots, *heads),
            "calls_per_step": layers},
    }
    return system
