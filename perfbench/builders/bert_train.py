"""BERT pre-training (MLM + NSP head) through ``deepspeed_tpu.initialize``:
the configuration ``benchmarks/bert_pretrain.run`` makes, read from the
configuration file."""
from perfbench import flops
from perfbench.builders import _common


def build(env, plan):
    import deepspeed_tpu
    from deepspeed_tpu.models.bert import BertConfig, BertForPreTraining
    from deepspeed_tpu.parallel.mesh import MeshTopology

    m, t = env.config["model"], env.config["train"]
    if plan.seq > m["max_position_embeddings"]:
        raise ValueError("the traffic's sequences exceed the model's "
                         "positions")
    cfg = BertConfig(
        vocab_size=m["vocab_size"],
        max_position_embeddings=m["max_position_embeddings"],
        type_vocab_size=m["type_vocab_size"], hidden_size=m["hidden_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        intermediate_size=m["intermediate_size"],
        layer_norm_eps=m["layer_norm_eps"],
        approximate_gelu=m["hidden_act"] != "gelu", dropout=t["dropout"],
        dtype=_common.dtype(t["compute_dtype"]),
        param_dtype=_common.dtype(t["param_dtype"]), scan_layers=True,
        remat=t.get("remat", False),
        remat_policy=t.get("remat_policy", "full"))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=BertForPreTraining(cfg),
        config=_common.engine_config(env, plan),
        topology=MeshTopology(devices=list(env.devices)),
        seed=_common.program_seed(env.seed))
    info = {
        "flops_per_token": flops.bert_train_flops_per_token(
            m["num_hidden_layers"], m["hidden_size"], m["intermediate_size"],
            m["vocab_size"], plan.seq, plan.label_share),
        "tokens_per_step": plan.tokens_per_step,
        "step_program": "jit_train_step",
    }
    return _common.TrainSystem(engine, info)
