"""GPT-2 training through ``deepspeed_tpu.initialize``: the configuration
``benchmarks/gpt_pretrain.build`` makes, read from the configuration file."""
from perfbench import flops
from perfbench.builders import _common


def model_config(env, section, n_positions):
    from deepspeed_tpu.models.transformer_lm import GPTConfig

    m = env.config["model"]
    return GPTConfig(
        vocab_size=m["vocab_size"], n_positions=n_positions,
        n_embd=m["n_embd"], n_layer=m["n_layer"], n_head=m["n_head"],
        mlp_ratio=m["mlp_ratio"], activation=m["activation"],
        tie_word_embeddings=m["tie_word_embeddings"],
        dtype=_common.dtype(section["compute_dtype"]),
        param_dtype=_common.dtype(section["param_dtype"]),
        scan_layers=True, remat=section.get("remat", False),
        remat_policy=section.get("remat_policy", "full"),
        use_flash_attention=section["use_flash_attention"])


def build(env, plan):
    import deepspeed_tpu
    from deepspeed_tpu.models.transformer_lm import GPT
    from deepspeed_tpu.parallel.mesh import MeshTopology

    m, t = env.config["model"], env.config["train"]
    if plan.seq > m["n_positions"]:
        raise ValueError("the traffic's sequences exceed the model's "
                         "positions")
    cfg = model_config(env, t, plan.seq)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT(cfg), config=_common.engine_config(env, plan),
        topology=MeshTopology(devices=list(env.devices)),
        seed=_common.program_seed(env.seed))
    micro = int(env.traffic["micro_batch_per_chip"])
    info = {
        "flops_per_token": flops.gpt_train_flops_per_token(
            m["n_layer"], m["n_embd"], m["vocab_size"], plan.seq,
            m["mlp_ratio"]),
        "tokens_per_step": plan.tokens_per_step,
        "step_program": "jit_train_step",
        # one chip's flash calls work on [micro * heads, seq, head_dim]
        "flash": {"bh": micro * m["n_head"], "t": plan.seq,
                  "d": m["n_embd"] // m["n_head"], "causal": True,
                  "itemsize": 2},
    }
    return _common.TrainSystem(engine, info)
