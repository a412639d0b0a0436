"""OLMoE training through ``deepspeed_tpu.initialize``: the program's
``GPT`` with the published configuration's keys (RMSNorm, SwiGLU experts,
rotary, qk-norm, untied head) and the dropless top-k routing."""
from perfbench import moe_flops
from perfbench.builders import _common


def model_config(config, section, n_positions):
    """The program's ``GPTConfig`` for a configuration file whose top level
    holds the published ``config.json`` keys."""
    from deepspeed_tpu.models.transformer_lm import GPTConfig

    c, m = config, config["model"]
    return GPTConfig(
        vocab_size=c["vocab_size"], n_positions=n_positions,
        n_embd=c["hidden_size"], n_layer=c["num_hidden_layers"],
        n_head=c["num_attention_heads"], n_kv_head=c["num_key_value_heads"],
        intermediate_size=c["intermediate_size"], norm="rmsnorm",
        layer_norm_epsilon=c["rms_norm_eps"], activation=c["hidden_act"],
        use_bias=c["attention_bias"], rotary=True,
        rope_theta=float(c["rope_theta"]), learned_positions=False,
        tie_word_embeddings=c["tie_word_embeddings"], qk_norm=m["qk_norm"],
        moe_num_experts=c["num_experts"], moe_top_k=c["num_experts_per_tok"],
        moe_drop_tokens=False, moe_gated_experts=True,
        moe_norm_topk_prob=c["norm_topk_prob"],
        moe_aux_loss_coef=m["router_aux_loss_coef"],
        moe_z_loss_coef=m["router_z_loss_coef"],
        dtype=_common.dtype(section["compute_dtype"]),
        param_dtype=_common.dtype(section["param_dtype"]),
        scan_layers=True, remat=section.get("remat", False),
        remat_policy=section.get("remat_policy", "full"),
        use_flash_attention=section["use_flash_attention"])


class MoETrainSystem(_common.TrainSystem):
    """A training system that can also say how its routers load the
    experts on the cell's batch."""

    def __init__(self, engine, info, batch):
        super().__init__(engine, info)
        self._batch = batch
        self._load = None

    def expert_load(self):
        """The program's ``moe.load`` event for the cell's batch at the
        parameters as they are now: one forward pass of its own, made once
        per run and after the window."""
        if self._load is None:
            from deepspeed_tpu.moe.utils import publish_expert_load

            self._load = publish_expert_load(
                self.engine.module, self.engine.params, self._batch)
        return self._load


def build(env, plan):
    import deepspeed_tpu
    from deepspeed_tpu.models.transformer_lm import GPT
    from deepspeed_tpu.parallel.mesh import MeshTopology

    c, t = env.config, env.config["train"]
    if plan.seq > c["max_position_embeddings"]:
        raise ValueError("the traffic's sequences exceed the model's "
                         "positions")
    cfg = model_config(c, t, c["max_position_embeddings"])
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT(cfg), config=_common.engine_config(env, plan),
        topology=MeshTopology(devices=list(env.devices)),
        seed=_common.program_seed(env.seed))
    micro = int(env.traffic["micro_batch_per_chip"])
    info = {
        "flops_per_token": moe_flops.moe_train_flops_per_token(
            c["num_hidden_layers"], c["hidden_size"], c["intermediate_size"],
            c["num_experts"], c["num_experts_per_tok"], c["vocab_size"],
            plan.seq),
        "tokens_per_step": plan.tokens_per_step,
        "step_program": "jit_train_step",
        "flash": {"bh": micro * c["num_attention_heads"], "t": plan.seq,
                  "d": c["hidden_size"] // c["num_attention_heads"],
                  "causal": True, "itemsize": 2},
        # one chip's grouped matmuls: every (token, expert) pair is a row
        "grouped_matmul": {
            "rows": micro * plan.seq * c["num_experts_per_tok"],
            "d_model": c["hidden_size"], "d_hidden": c["intermediate_size"],
            "groups": c["num_experts"], "itemsize": 2},
    }
    return MoETrainSystem(engine, info, plan.batch)
