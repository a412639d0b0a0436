"""SmallThinker training through ``deepspeed_tpu.initialize``: the
program's ``GPT`` with the published configuration's keys. Layers by kind
(``sliding_window_layout``: a window of ``sliding_window_size`` positions
with rotary where ``rope_layout`` says so, every position without rotary
elsewhere), grouped queries, RMSNorm, no bias, an untied head; a router
that reads the block's input, a softmax over the chosen logits (the
program's ``moe_norm_topk_prob`` over softmax scores: the same numbers),
ReLU-gated experts of which this chip holds ``moe.experts_held``, dropless,
no auxiliary loss."""
from perfbench import swa_flops
from perfbench.builders import _common
from perfbench.builders.olmoe_train import MoETrainSystem

KINDS = {0: "attention", 1: "window"}


def layer_types(config):
    """The kinds of the layers this file runs, the first of the published
    layout (whole periods). The model rotates exactly its window layers."""
    n = config["num_hidden_layers"]
    windowed, rotated = (config[key][:n] for key in (
        "sliding_window_layout", "rope_layout"))
    if windowed != rotated:
        raise ValueError("rotary_kinds turns a kind's q and k: a layout "
                         "that rotates other layers than the window "
                         "layers is another model")
    return tuple(KINDS[w] for w in windowed)


def model_config(config, section, n_positions, **changed):
    """The program's ``GPTConfig`` for a configuration file whose top level
    holds the published ``config.json`` keys; ``changed`` replaces fields
    (the reference check's controls)."""
    from deepspeed_tpu.models.transformer_lm import GPTConfig

    c, m = config, config["moe"]
    if not (c["moe_primary_router_apply_softmax"] and c["norm_topk_prob"]):
        raise ValueError("the weights are a softmax over the chosen logits")
    fields = dict(
        vocab_size=config["model"]["vocab_size"], n_positions=n_positions,
        n_embd=c["hidden_size"], n_layer=c["num_hidden_layers"],
        n_head=c["num_attention_heads"], n_kv_head=c["num_key_value_heads"],
        attn_head_dim=c["head_dim"], norm="rmsnorm",
        layer_norm_epsilon=c["rms_norm_eps"], use_bias=False, rotary=True,
        rope_theta=float(c["rope_theta"]), learned_positions=False,
        tie_word_embeddings=c["tie_word_embeddings"],
        layer_types=layer_types(c), sliding_window=c["sliding_window_size"],
        rotary_kinds=("window",),
        moe_num_experts=m["routed_over"],
        moe_top_k=c["moe_num_active_primary_experts"],
        moe_intermediate_size=c["moe_ffn_hidden_size"],
        moe_experts_held=tuple(m["experts_held"]),
        moe_drop_tokens=False, moe_gated_experts=True,
        moe_expert_activation=m["expert_activation"],
        moe_router_input=m["router_input"], moe_norm_topk_prob=True,
        moe_aux_loss_coef=0.0,
        dtype=_common.dtype(section["compute_dtype"]),
        param_dtype=_common.dtype(section["param_dtype"]),
        scan_layers=True, remat=section.get("remat", False),
        remat_policy=section.get("remat_policy", "full"),
        use_flash_attention=section["use_flash_attention"],
        fused_head_ce=section.get("fused_head_ce", "auto"))
    fields.update(changed)
    return GPTConfig(**fields)


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
    held = c["moe"]["experts_held"][1]
    share = held / c["moe"]["routed_over"]
    info = {
        "flops_per_token": swa_flops.train_flops_per_token(
            cfg.layer_types, c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"],
            c["moe_ffn_hidden_size"], c["moe"]["routed_over"],
            c["moe_num_active_primary_experts"] * share,
            cfg.vocab_size, plan.seq, c["sliding_window_size"]),
        "tokens_per_step": plan.tokens_per_step,
        "step_program": "jit_train_step",
        # the full layers' calls (flash_*); the window layers' calls
        # (window_flash_*) are counted with their window
        "flash": {"bh": micro * c["num_attention_heads"], "t": plan.seq,
                  "d": c["head_dim"], "causal": True, "itemsize": 2},
        "window_flash": {"window": c["sliding_window_size"],
                         "kv_heads": micro * c["num_key_value_heads"],
                         "itemsize": 2},
        # this chip's grouped matmuls: the EXPECTED pairs routed to the
        # held experts are the rows (the routed-here share says what the
        # seeded routers sent)
        "grouped_matmul": {
            "rows": int(micro * plan.seq
                        * c["moe_num_active_primary_experts"] * share),
            "d_model": c["hidden_size"],
            "d_hidden": c["moe_ffn_hidden_size"], "groups": held,
            "itemsize": 2},
    }
    return MoETrainSystem(engine, info, plan.batch)
