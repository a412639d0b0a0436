"""AFMoE / Trinity's stack (attention layers of two kinds side by side: a
window of positions with rotary and every position without; a gated
attention output, four norms a layer, a leading dense layer, and expert
layers of which this chip holds one device's share beside the shared
expert) served through ``init_inference`` -> ``serving.build_serving`` ->
the continuous-batching scheduler, the entry points the other serve cells
use, with the plain reference beside it. Sizes come from the configuration
file's published keys, its ``moe`` block and its ``serve`` section."""
import time

import numpy as np

from perfbench import afmoe_flops, mla_flops
from perfbench.builders import _common, deepseek_v2_serve

# the published names of the layers' kinds -> the program's
KINDS = {"sliding_attention": "window", "full_attention": "attention"}


def model_config(config, section=None):
    """The program's ``GPTConfig`` for a configuration file's published
    keys, served as its ``serve`` section (or ``section``) says."""
    from deepspeed_tpu.models.transformer_lm import GPTConfig

    from perfbench.reference import afmoe

    c, s = config, section or config["serve"]
    sizes = afmoe.sizes(c)      # raises for another form of the stack
    return GPTConfig(
        vocab_size=c["vocab_size"], n_positions=s["cache_positions"],
        n_embd=c["hidden_size"], n_layer=c["num_hidden_layers"],
        n_head=c["num_attention_heads"],
        n_kv_head=c["num_key_value_heads"], attn_head_dim=c["head_dim"],
        intermediate_size=c["intermediate_size"], norm="rmsnorm",
        layer_norm_epsilon=c["rms_norm_eps"], activation=c["hidden_act"],
        gated_mlp=True, use_bias=False, rotary=True,
        rope_theta=float(c["rope_theta"]), learned_positions=False,
        tie_word_embeddings=c["tie_word_embeddings"], qk_norm="head",
        embedding_multiplier=sizes["embed_scale"],
        dtype=_common.dtype(s["compute_dtype"]),
        param_dtype=_common.dtype(s["param_dtype"]), scan_layers=True,
        use_flash_attention=False, num_logits_to_keep=1,
        layer_types=tuple(KINDS[k] for k in c["layer_types"]),
        sliding_window=c["sliding_window"], window_slack=s["window_slack"],
        rotary_kinds=(KINDS["sliding_attention"],), attn_output_gate=True,
        post_norms=True, first_k_dense=c["num_dense_layers"],
        moe_num_experts=c["moe"]["routed_over"],
        moe_top_k=c["num_experts_per_tok"], moe_drop_tokens=False,
        moe_gated_experts=True, moe_norm_topk_prob=c["route_norm"],
        moe_intermediate_size=c["moe_intermediate_size"],
        moe_n_shared=c["num_shared_experts"],
        moe_routed_scale=float(c["route_scale"]),
        moe_experts_held=tuple(c["moe"]["experts_held"]),
        moe_scoring=c["score_func"], moe_expert_bias=True,
        moe_expert_bias_init=float(c["moe"]["expert_bias_std"]),
        moe_renorm_eps=1e-20)


def attention_sizes(c):
    return dict(n_heads=c["num_attention_heads"],
                n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"])


def _padded(n, to):
    return -(-n // to) * to


class WindowServeSystem(deepseek_v2_serve.LatentServeSystem):
    """``LatentServeSystem`` (the plan event, the live positions, the
    routers' load, the lanes a run left) whose reference is
    ``perfbench/reference/afmoe.py`` and whose lanes' "state" is what each
    kind of layer keeps: keys and values of every row a live lane's request
    wrote in the layers that see everything, and of the rows the ring holds
    in the layers that see a window."""

    # the reference runs at a few lengths, so that requests share compiles
    REFERENCE_PAD = 4096
    _step_load = None

    def __init__(self, *args):
        super().__init__(*args)
        self.live_window_positions = []     # (monotonic time, the sum)
        self._info = {}

    # ``info`` as the readers see it: what ``build`` set, and the two
    # kinds' attention counts, which exist once the window has run
    @property
    def info(self):
        return dict(self._info, **self._attention_counts())

    @info.setter
    def info(self, value):
        self._info = value

    def on_bus(self, ev):
        super().on_bus(ev)
        if ev.get("kind") == "serve.stats" and "live_window_positions" in ev:
            self.live_window_positions.append(
                (time.monotonic(), ev["live_window_positions"]))

    def mean_live_window_positions(self):
        """``mean_live_positions`` of the rows the window layers see: each
        lane's newest ``sliding_window`` at most (the program's
        ``serve.stats`` counts them from the scheduler's clocks)."""
        lo, hi = self.env.t_open, self.env.t_close
        inside = [n for t, n in self.live_window_positions if lo <= t <= hi]
        return sum(inside) / len(inside) if inside else None

    def _attention_counts(self):
        """``{"window_attention_step", "full_attention_step"}``: flops and
        bytes of ONE layer's attention of each kind on a decode step, over
        the window's mean visible positions (perfbench/afmoe_flops.py),
        with how many layers of the kind a step runs."""
        seen = {"window": self.mean_live_window_positions(),
                "full": self.mean_live_positions()}
        if not self._info or None in seen.values():
            return {}
        sizes = self._info["attention"]
        return {kind + "_attention_step": dict(
            afmoe_flops.attention_step(self._info["slots"], positions,
                                       **sizes["heads"],
                                       itemsize=sizes["itemsize"]),
            calls_per_step=sizes[kind + "_layers"])
            for kind, positions in seen.items()}

    def step_expert_load(self):
        """The program's ``moe.load`` event for one token a lane (a decode
        step's rows) at the served parameters: a forward pass of its own,
        made once per run and after the window."""
        if self._step_load is None:
            from deepspeed_tpu.moe.utils import publish_expert_load

            ids = np.random.default_rng([self.env.seed, 6]).integers(
                0, self.env.config["vocab_size"],
                size=(self._info["slots"], 1))
            self._step_load = publish_expert_load(
                self.engine.module, self.engine.params,
                {"input_ids": ids.astype(np.int32)})
        return self._step_load

    def live_lanes(self, count, rng):
        """Of ``count`` lanes (``rng`` chooses) that held a request when
        the run ended: ``{"lane", "request_id", "tokens"`` (all that the
        lane's cache has taken in after the prompt)``, "full", "ring"}``,
        the last two the per-kind stacks of the scheduler's own lane cache
        as the window's last decode step left them, one lane of each, on
        the host: ``cached_key`` / ``cached_value`` ``[layers of the kind,
        rows, Hkv, D]``, ``valid`` and, of the rings, ``slot_pos``
        ``[layers, rows]``. The cache is let go afterwards: the reference
        needs its room."""
        import jax

        kept, self.scheduler.lanes_at_exit = \
            self.scheduler.lanes_at_exit, None
        if kept is None:
            return []
        lanes = sorted(kept.live)
        chosen = rng.choice(len(lanes), size=min(count, len(lanes)),
                            replace=False)
        return [dict(
            lane=lanes[i], request_id=kept.live[lanes[i]].request_id,
            tokens=[int(t) for t in kept.live[lanes[i]].tokens],
            full=jax.device_get(kept.positions(lanes[i], KINDS[
                "full_attention"])),
            ring=jax.device_get(kept.positions(lanes[i], KINDS[
                "sliding_attention"]))) for i in chosen]

    def reference_pass(self, seq, offset=0):
        """``hidden_and_states`` of the plain reference over ``seq``, one
        float32 forward of the same parameters, right-padded with zeros to
        a multiple of ``REFERENCE_PAD`` (of 64 where that is longer than
        the cache), the sliding layers' first token at rotary position
        ``offset``."""
        from perfbench.reference import afmoe

        c = self.env.config
        if self._reference is None:
            self._reference = afmoe.sizes(c)
        pad = self.REFERENCE_PAD \
            if c["serve"]["cache_positions"] >= self.REFERENCE_PAD else 64
        ids = np.zeros((_padded(len(seq), pad),), np.int32)
        ids[:len(seq)] = seq
        return ids, afmoe.hidden_and_states(
            self.reference_params(), ids, self._reference, offset=offset)

    def judge_lane(self, prompt, lane):
        """For one of ``live_lanes``, from ONE reference pass over the
        prompt and every token the lane has taken in (rotary counting
        cache rows, as the program's does: the reference starts at the
        lane's first row):

        * ``margin``: of every served token, the first included, how far
          below the reference's largest logit at its position it lies, in
          units of that position's logit standard deviation;
        * ``errors``: the norm of the difference between the lane's keys
          and values and the reference's over the norm of the reference's:
          ``by_layer`` an entry a layer that sees everything, over every
          row the request wrote (which must be exactly the rows ``valid``
          marks); ``tail_by_layer`` an entry a window layer, over the rows
          its ring holds of the request (``slot_pos`` says which position
          a row holds; the newest ``min(rows written, window)`` positions
          must all be there, each once); ``by_head`` the model's first
          layer's, by KV head."""
        import jax.numpy as jnp

        from perfbench.reference import afmoe

        tokens = lane["tokens"]
        n = len(prompt) + len(tokens)
        bucket = self.scheduler.prompt_bucket
        first = _padded(len(prompt), bucket) - len(prompt)
        ids, (hidden, kept) = self.reference_pass(
            list(prompt) + tokens, offset=first)
        s = self._reference
        full, ring = lane["full"], lane["ring"]
        valid = np.asarray(full["valid"][0])
        if valid[first:first + n].sum() != n or valid.sum() != n:
            raise ValueError(
                f"lane {lane['lane']} marks {int(valid.sum())} rows valid, "
                f"its request wrote {n} from row {first}")
        at = list(range(len(prompt) - 1, n - 1))
        margin = afmoe.position_stats(
            self.reference_params(), ids, s, at, tokens, pad_to=512,
            states=hidden)["margin"].tolist()

        def sums(got, ref):
            """Squared norms by KV head of rows ``[rows, Hkv, D]``."""
            ref = jnp.asarray(ref, jnp.float32)
            diff = jnp.asarray(got, jnp.float32) - ref
            return (np.asarray(jnp.sum(diff * diff, (0, 2)), np.float64),
                    np.asarray(jnp.sum(ref * ref, (0, 2)), np.float64))

        by_layer, tail, by_head = [], [], None
        seen = {"window": 0, "attention": 0}
        for layer, kind in enumerate(s["kinds"]):
            stack = KINDS[kind]
            i, seen[stack] = seen[stack], seen[stack] + 1
            if stack == "attention":
                rows = np.arange(first, first + n)
                wrote = rows - first
                held = full
            else:
                held = ring
                at_row = np.asarray(ring["slot_pos"][i])
                rows = np.nonzero(np.asarray(ring["valid"][i])
                                  & (at_row >= first))[0]
                wrote = at_row[rows] - first
                newest = np.arange(n - min(n, s["window"]), n)
                if len(set(wrote)) != len(wrote) or wrote.max() != n - 1 \
                        or not np.isin(newest, wrote).all():
                    raise ValueError(
                        f"lane {lane['lane']}: layer {layer}'s ring does "
                        f"not hold the newest {len(newest)} of its "
                        f"request's {n} rows, each once")
            num, den = (a + b for a, b in zip(*(
                sums(np.asarray(held[name][i])[rows], ref[wrote])
                for name, ref in zip(("cached_key", "cached_value"),
                                     kept[layer]))))
            (by_layer if stack == "attention" else tail).append(
                float(np.sqrt(num.sum() / den.sum())))
            if layer == 0:
                by_head = np.sqrt(num / den).tolist()
        return {"margin": margin,
                "errors": {"by_layer": by_layer, "by_head": [by_head],
                           "tail_by_layer": tail}}


def build(env, plan):
    import deepspeed_tpu
    from deepspeed_tpu import serving
    from deepspeed_tpu.models.transformer_lm import GPT

    c, s = env.config, env.config["serve"]
    engine = deepspeed_tpu.init_inference(
        GPT(model_config(c)), dtype=s["dtype"],
        seed=_common.program_seed(env.seed))
    system = WindowServeSystem(env, engine, None, None)
    system.subscribe(system.on_bus)      # the plan, and the live positions
    system.scheduler = serving.build_serving(engine, dict(s["serving"]))
    system.scheduler.retain_lanes = True      # ``live_lanes`` reads them
    itemsize = 2 if s["dtype"] in ("bf16", "bfloat16") else 4
    kinds = [KINDS[k] for k in c["layer_types"]]
    slots = system.scheduler.slots
    _, held = c["moe"]["experts_held"]
    moe_layers = len(kinds) - c["num_dense_layers"]
    # the pairs a step routes to the held experts, by the routers' own
    # balance: every lane's token chooses top_k of routed_over
    rows = slots * c["num_experts_per_tok"] * held / c["moe"]["routed_over"]
    system.info = {
        "slots": slots,
        "decode_program": "jit_decode_k",
        "attention": {"heads": attention_sizes(c), "itemsize": itemsize,
                      "window_layers": kinds.count("window"),
                      "full_layers": kinds.count("attention")},
        "weights": dict(
            n_layers=len(kinds), n_dense=c["num_dense_layers"],
            vocab=c["vocab_size"], hidden=c["hidden_size"],
            dense_width=c["intermediate_size"],
            expert_width=c["moe_intermediate_size"],
            n_shared=c["num_shared_experts"],
            n_routed=c["moe"]["routed_over"], itemsize=itemsize,
            **attention_sizes(c)),
        "experts_held": held,
        "held_experts_step": dict(
            mla_flops.held_experts_step(
                rows, c["hidden_size"], c["moe_intermediate_size"], held,
                itemsize), calls_per_step=moe_layers),
    }
    return system
