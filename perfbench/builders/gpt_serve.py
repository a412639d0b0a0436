"""GPT-2 serving: ``init_inference`` -> ``serving.build_serving`` -> the
continuous-batching scheduler, with the plain reference beside it."""
import numpy as np

from perfbench import flops
from perfbench.builders import _common, gpt_train


class ServeSystem:
    def __init__(self, env, engine, scheduler, info):
        self.env = env
        self.engine = engine
        self.scheduler = scheduler
        self.info = info
        self._reference = None

    def subscribe(self, fn):
        from deepspeed_tpu.telemetry import telemetry_bus

        telemetry_bus.subscribe(fn)

    def unsubscribe(self, fn):
        from deepspeed_tpu.telemetry import telemetry_bus

        telemetry_bus.unsubscribe(fn)

    def first_token_margin(self, prompt, token):
        """How far below the reference's largest last-position logit the
        served first token lies, in units of the logits' standard deviation,
        with the configuration's tolerance. The reference is the plain
        cache-free float32 forward of the same parameters."""
        from perfbench.reference import gpt2

        m = self.env.config["model"]
        if self._reference is None:
            self._reference = gpt2.make_last_logits(
                n_head=m["n_head"], n_positions=m["n_positions"])
        ids = np.zeros((m["n_positions"],), np.int32)
        ids[:len(prompt)] = prompt
        logits = np.asarray(self._reference(self.engine.params, ids,
                                            len(prompt)))
        std = float(logits.std())
        return {"margin": float(logits.max() - logits[token]) / std,
                "tolerance": float(
                    self.env.config["serve"]["first_token_tolerance"]),
                "prompt_len": len(prompt), "logit_std": std,
                "is_argmax": bool(int(logits.argmax()) == int(token))}


def build(env, plan):
    import deepspeed_tpu
    from deepspeed_tpu import serving
    from deepspeed_tpu.models.transformer_lm import GPT

    m, s = env.config["model"], env.config["serve"]
    cfg = gpt_train.model_config(env, s, m["n_positions"])
    engine = deepspeed_tpu.init_inference(
        GPT(cfg), dtype=s["dtype"], seed=_common.program_seed(env.seed))
    scheduler = serving.build_serving(engine, dict(s["serving"]))
    itemsize = 2 if s["dtype"] in ("bf16", "bfloat16") else 4
    info = {
        "slots": scheduler.slots,
        "decode_program": "jit_decode_k",
        "weight_bytes": flops.gpt_weight_bytes(
            m["n_layer"], m["n_embd"], m["vocab_size"], m["n_positions"],
            itemsize, m["mlp_ratio"]),
        "kv_bytes_per_position": flops.kv_bytes_per_position(
            m["n_layer"], m["n_embd"], itemsize),
    }
    return ServeSystem(env, engine, scheduler, info)
