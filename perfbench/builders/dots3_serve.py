"""dots3-note-prev's language model (latent attention of two kinds side by
side: 128 heads over a dense latent cache read through a lightning
indexer's choice of 2,048 rows, 64 heads of another width over a ring of
513 latents, a gate a head; a leading dense layer, and expert layers of
which this chip holds one device's share beside the shared expert) served
through ``init_inference`` -> ``serving.build_serving`` -> the
continuous-batching scheduler, the entry points the other serve cells use,
with the plain reference beside it. Sizes come from the configuration
file's published keys, its ``moe`` block and its ``serve`` section."""
import time

import numpy as np

from perfbench import dots3_flops, mla_flops
from perfbench.builders import _common, afmoe_serve

# the published names of the layers' kinds -> the program's
KINDS = {"sliding_attention": "window", "full_attention": "attention"}
_PREFIX = {"attention": "", "window": "swa_"}


def kind_sizes(c, stack):
    """``dots3_flops.attention_params``'s keywords for one kind."""
    p = _PREFIX[stack]
    out = dict(n_heads=c[p + "num_attention_heads"],
               q_rank=c[p + "q_lora_rank"], kv_rank=c[p + "kv_lora_rank"],
               nope=c[p + "qk_nope_head_dim"], rope=c[p + "qk_rope_head_dim"],
               v_dim=c[p + "v_head_dim"])
    if stack == "attention":
        out.update(ix_heads=c["index_n_heads"], ix_dim=c["index_head_dim"])
    return out


def head_sizes(c, stack):
    """``dots3_flops.absorbed_attention_step``'s keywords for one kind."""
    sizes = kind_sizes(c, stack)
    return {k: sizes[k] for k in ("n_heads", "kv_rank", "rope")}


def model_config(config, section=None):
    """The program's ``GPTConfig`` for a configuration file's published
    keys, served as its ``serve`` section (or ``section``) says."""
    from deepspeed_tpu.models.transformer_lm import (
        GPTConfig,
        IndexerConfig,
        LatentKind,
        MLAConfig,
    )

    from perfbench.reference import dots3

    c, s = config, section or config["serve"]
    dots3.sizes(c)      # raises for another form of the stack

    def kind(stack, theta, indexer=None):
        k = kind_sizes(c, stack)
        return LatentKind(
            n_head=k["n_heads"], mla=MLAConfig(
                q_rank=k["q_rank"], kv_rank=k["kv_rank"],
                nope_dim=k["nope"], rope_dim=k["rope"], v_dim=k["v_dim"]),
            rope_theta=float(theta), indexer=indexer, head_gate=True,
            rank_rescale=c["apply_mla_qkv_lora_rescale"])

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
        layer_types=tuple(KINDS[k] for k in c["layer_types"]),
        sliding_window=c["sliding_window_size"],
        window_slack=s["window_slack"],
        latent_kinds=(
            ("attention", kind("attention", c["rope_theta"], IndexerConfig(
                n_heads=c["index_n_heads"], head_dim=c["index_head_dim"],
                topk=c["index_topk"], q_chunk=s["index_q_chunk"],
                kv_chunk=s["index_kv_chunk"],
                rope_dim=c["qk_rope_head_dim"]))),
            ("window", kind("window", c["swa_rope_theta"]))),
        first_k_dense=c["first_k_dense_replace"],
        moe_num_experts=c["moe"]["routed_over"],
        moe_top_k=c["num_experts_per_tok"], moe_drop_tokens=False,
        moe_gated_experts=True, moe_norm_topk_prob=c["norm_topk_prob"],
        moe_intermediate_size=c["moe_intermediate_size"],
        moe_n_shared=c["n_shared_experts"],
        moe_routed_scale=float(c["routed_scaling_factor"]),
        moe_experts_held=tuple(c["moe"]["experts_held"]),
        moe_scoring=c["scoring_func"], moe_expert_bias=True,
        moe_expert_bias_init=float(c["moe"]["expert_bias_std"]),
        moe_renorm_eps=1e-20)


_padded = afmoe_serve._padded


def _relative(got, ref):
    """The norm of ``got - ref`` over the norm of ``ref``, float64."""
    ref = np.asarray(ref, np.float64)
    diff = np.asarray(got, np.float64) - ref
    return float(np.sqrt((diff * diff).sum() / (ref * ref).sum()))


class LatentKindsServeSystem(afmoe_serve.WindowServeSystem):
    """``WindowServeSystem`` (the plan event, the live positions of both
    kinds, the routers' load) whose reference is
    ``perfbench/reference/dots3.py`` and whose lanes' "state" is what each
    kind of layer keeps: the latent, rotary key and index key of every row
    a live lane's request wrote in the full layers, with what its last
    decode step left of its selection, and the latents and rotary keys of
    the rows the rings hold in the window layers."""

    def __init__(self, *args):
        super().__init__(*args)
        self.live_chosen_positions = []     # (monotonic time, the sum)
        self.last_steps = []                # ``live_lanes`` fills it

    def on_bus(self, ev):
        super().on_bus(ev)
        if ev.get("kind") == "serve.stats":
            now = time.monotonic()
            if "live_chosen_positions" in ev:
                self.live_chosen_positions.append(
                    (now, ev["live_chosen_positions"]))

    def _window_mean(self, series):
        lo, hi = self.env.t_open, self.env.t_close
        inside = [n for t, n in series if lo <= t <= hi]
        return sum(inside) / len(inside) if inside else None

    def mean_live_chosen_positions(self):
        """The mean over the window of the rows the full layers' decode
        queries attend over: each lane's ``min(context, index_topk)`` (the
        program's ``serve.stats`` ``live_chosen_positions``)."""
        return self._window_mean(self.live_chosen_positions)

    def _attention_counts(self):
        """``{"window_latent_attention_step", "sparse_latent_attention_step",
        "latent_index_step"}``: flops and bytes of ONE layer's work on a
        decode step (perfbench/dots3_flops.py) over the window's mean
        positions, with how many layers a step runs: a window layer's
        ``min(context, window)`` rows; a full layer's indexer over its live
        rows, and its attention over the rows its queries CHOSE, whatever
        the implementation fetched to reach them (what the equations need:
        a yardstick that does not move with the code it measures)."""
        seen = {"window": self.mean_live_window_positions(),
                "live": self.mean_live_positions(),
                "chosen": self.mean_live_chosen_positions()}
        if not self._info or None in seen.values():
            return {}
        a, slots = self._info["attention"], self._info["slots"]
        return {
            "window_latent_attention_step": dict(
                dots3_flops.absorbed_attention_step(
                    slots, seen["window"], itemsize=a["itemsize"],
                    **a["window"]), calls_per_step=a["window_layers"]),
            "sparse_latent_attention_step": dict(
                dots3_flops.absorbed_attention_step(
                    slots, seen["chosen"], itemsize=a["itemsize"],
                    **a["full"]),
                calls_per_step=a["full_layers"]),
            "latent_index_step": dict(
                dots3_flops.index_step(
                    slots, seen["live"], itemsize=a["itemsize"],
                    **a["indexer"]), calls_per_step=a["full_layers"])}

    def live_lanes(self, count, rng):
        """Of ``count`` lanes (``rng`` chooses) that held a request when
        the run ended: ``{"lane", "request_id", "tokens", "full", "ring",
        "step"}``, the per-kind stacks of the scheduler's own lane cache as
        the window's last decode step left them, one lane of each, on the
        host: ``full`` the full layers' ``cached_latent``,
        ``cached_rope_key``, ``cached_index_key`` ``[layers, S, .]`` and
        ``valid``; ``ring`` the window layers' ``cached_latent``,
        ``cached_rope_key`` ``[layers, rows, .]``, ``valid`` and
        ``slot_pos``; ``step`` what the last step left (``chosen_rows``
        ``[layers, topk]``, ``choice_query``, ``choice_weights``). The
        cache is let go afterwards: the reference needs its room."""
        import jax

        kept, self.scheduler.lanes_at_exit = \
            self.scheduler.lanes_at_exit, None
        if kept is None:
            return []
        lanes = sorted(kept.live)
        chosen = set(rng.choice(len(lanes), size=min(count, len(lanes)),
                                replace=False).tolist())
        out, self.last_steps = [], []
        for i, n in enumerate(lanes):
            lane = dict(
                lane=n, request_id=kept.live[n].request_id,
                tokens=[int(t) for t in kept.live[n].tokens],
                full=jax.device_get(kept.positions(n, "attention")),
                ring=jax.device_get(kept.positions(n, "window")),
                step=jax.device_get(kept.last_step(n)))
            # of EVERY live lane, the little its last step read and wrote
            self.last_steps.append(self._last_step(lane))
            if i in chosen:
                out.append(lane)
        return out

    def _last_step(self, lane):
        """What ``judge_steps`` needs of one of ``live_lanes``: ``at`` the
        row its last decode step wrote, and a layer at a time the rows
        that step attended over as the lane stores them (``held``: latents,
        rotary keys, which of them it saw: a full layer's ``chosen_rows``,
        a window layer's ring under the program's own rule) and the row it
        wrote (``wrote``: latent and rotary key side by side)."""
        c = self.env.config
        full, ring, step = lane["full"], lane["ring"], lane["step"]
        at = int(np.nonzero(np.asarray(full["valid"][0]))[0].max())
        held, wrote, seen = [], [], {"window": 0, "attention": 0}

        def f32(leaf, rows):
            return np.asarray(leaf, np.float32)[rows]

        for layer, kind in enumerate(c["layer_types"]):
            stack = KINDS[kind]
            i, seen[stack] = seen[stack], seen[stack] + 1
            if stack == "attention":
                picked = np.asarray(step["chosen_rows"][i])
                rows, saw, mine, of = (np.maximum(picked, 0), picked >= 0,
                                       at, full)
            else:
                pos = np.asarray(ring["slot_pos"][i])
                rows = np.arange(len(pos))
                saw = (np.asarray(ring["valid"][i]) & (pos >= 0)
                       & (pos <= at) & (pos > at - c["sliding_window_size"]))
                mine, of = np.nonzero(saw & (pos == at))[0], ring
                if len(mine) != 1:
                    raise ValueError(
                        f"lane {lane['lane']}: layer {layer}'s ring does "
                        f"not hold the row {at} of its last step once")
            held.append((f32(of["cached_latent"][i], rows),
                         f32(of["cached_rope_key"][i], rows), saw))
            wrote.append(np.concatenate([
                f32(of[name][i], mine).reshape(-1)
                for name in ("cached_latent", "cached_rope_key")]))
        return {"lane": lane["lane"], "request_id": lane["request_id"],
                "taken_in": len(lane["tokens"]),
                "last_token": lane["tokens"][-1] if lane["tokens"] else None,
                "at": at, "held": held, "wrote": wrote}

    def judge_steps(self, by_rid):
        """Every live lane's LAST decode step, replayed by the plain
        reference from what the lane itself keeps (``dots3.step_rows``: the
        lane's stored rows, the step's own chosen rows, the token that step
        consumed: the lane's last, or the last of its prompt in
        ``by_rid[request_id]``): the relative norm of
        the difference between the row each layer after the first wrote
        for that token (latent and rotary key) and the replayed one,
        ``[lanes, layers - 1]``. The reference's own earlier rows and its
        own choice do not enter, so neither the choice's noise nor the
        state's: what is left is ONE step's arithmetic, the decode
        softmax of every kind and the routers of every expert layer but
        the last among it. (The first layer's row is the token's alone.)"""
        from perfbench.reference import dots3

        steps = self.last_steps
        if not steps:
            return np.zeros((0, 0))
        if self._reference is None:
            self._reference = dots3.sizes(self.env.config)
        layers = range(len(steps[0]["held"]))
        bucket = self.scheduler.prompt_bucket
        prompts = [list(by_rid[st["request_id"]].prompt) for st in steps]
        for st, prompt in zip(steps, prompts):
            if st["at"] != _padded(len(prompt), bucket) + st["taken_in"] - 1:
                raise ValueError(
                    f"lane {st['lane']}'s last valid row is {st['at']}: not "
                    f"the last of a prompt of {len(prompt)} in buckets of "
                    f"{bucket} and {st['taken_in']} tokens")
        held = [tuple(np.stack([st["held"][i][j] for st in steps])
                      for j in range(3)) for i in layers]
        rows = dots3.step_rows(
            self.reference_params(),
            [prompt[-1] if st["last_token"] is None else st["last_token"]
             for st, prompt in zip(steps, prompts)],
            [st["at"] for st in steps], self._reference, held)
        return np.asarray([[_relative(
            st["wrote"][i], np.concatenate([
                np.asarray(rows[i][name][b]) for name in (
                    "latent", "rope_key")]))
            for i in layers if i > 0] for b, st in enumerate(steps)])

    def reference_pass(self, seq, offset=0):
        """``hidden_and_states`` of the plain reference over ``seq``, one
        float32 forward of the same parameters, right-padded with zeros to
        a multiple of ``REFERENCE_PAD`` (of 64 where that is longer than
        the cache), the first token at rotary position ``offset``, with
        the indexer's query of the last row."""
        from perfbench.reference import dots3

        c = self.env.config
        if self._reference is None:
            self._reference = dots3.sizes(c)
        pad = self.REFERENCE_PAD \
            if c["serve"]["cache_positions"] >= self.REFERENCE_PAD else 64
        ids = np.zeros((_padded(len(seq), pad),), np.int32)
        ids[:len(seq)] = seq
        return ids, dots3.hidden_and_states(
            self.reference_params(), ids, self._reference, offset=offset,
            queries_at=(len(seq) - 1,))

    def judge_lane(self, prompt, lane):
        """For one of ``live_lanes``, from ONE reference pass over the
        prompt and every token the lane has taken in (rotary counting
        cache rows: the reference starts at the lane's first row):

        * ``margin``: of every served token, the first included, how far
          below the reference's largest logit at its position it lies, in
          units of that position's logit standard deviation;
        * ``errors``: the norm of the difference between what the lane
          keeps and the reference's over the norm of the reference's:
          ``by_layer`` an entry a full layer, of latents and rotary keys
          together over every row the request wrote (exactly the rows
          ``valid`` marks); ``index_by_layer`` the same of the index keys;
          ``tail_by_layer`` an entry a window layer, over the rows its ring
          holds of the request (``slot_pos`` says which position a row
          holds; the newest ``min(rows written, window)`` must all be
          there, each once); ``by_head`` the model's first layer's latent
          alone;
        * ``selection``: as ``keye_vl_serve.SelectedServeSystem``'s, of the
          rows the lane's LAST decode step attended over in each full
          layer (``chosen_rows``): ``miss_by_layer`` against the rows the
          reference's query chooses of the lane's own stored index keys
          (float64), ``choice_miss_by_layer`` against those the step's own
          stored query chooses, ``miss_reference_keys_by_layer`` against
          the reference's choice over its own keys (for the record)."""
        from perfbench.reference import dots3

        tokens = lane["tokens"]
        n = len(prompt) + len(tokens)
        bucket = self.scheduler.prompt_bucket
        first = _padded(len(prompt), bucket) - len(prompt)
        ids, (hidden, kept, _, queries) = self.reference_pass(
            list(prompt) + tokens, offset=first)
        s = self._reference
        full, ring, step = lane["full"], lane["ring"], lane["step"]
        valid = np.asarray(full["valid"][0])
        if valid[first:first + n].sum() != n or valid.sum() != n:
            raise ValueError(
                f"lane {lane['lane']} marks {int(valid.sum())} rows valid, "
                f"its request wrote {n} from row {first}")
        at = list(range(len(prompt) - 1, n - 1))
        margin = dots3.position_stats(
            self.reference_params(), ids, s, at, tokens, pad_to=512,
            states=hidden)["margin"].tolist()

        def both(held, i, rows, wrote, ref):
            """Latents and rotary keys together, rows of layer ``i``."""
            got = np.concatenate(
                [np.asarray(held[name][i], np.float32)[rows] for name in (
                    "cached_latent", "cached_rope_key")], -1)
            want = np.concatenate(
                [np.asarray(ref[name])[wrote]
                 for name in ("latent", "rope_key")], -1)
            return _relative(got, want)

        topk = s["topk"]
        by_layer, index, tail, by_head = [], [], [], None
        miss, miss_ref, miss_own = [], [], []
        seen = {"window": 0, "attention": 0}
        for layer, kind in enumerate(s["kinds"]):
            stack = KINDS[kind]
            i, seen[stack] = seen[stack], seen[stack] + 1
            ref = kept[layer]
            if stack == "attention":
                rows = np.arange(first, first + n)
                wrote = rows - first
                by_layer.append(both(full, i, rows, wrote, ref))
                stored = np.asarray(full["cached_index_key"][i],
                                    np.float32)[rows]
                k_i = np.asarray(ref["index_key"])[:n]
                index.append(_relative(stored, k_i))
                q_i, w = (np.asarray(a)[0] for a in queries[layer])
                picked = np.asarray(step["chosen_rows"][i])
                read = np.unique(picked[picked >= 0]) - first
                sound = (len(read) == min(topk, n)
                         == int((picked >= 0).sum())
                         and read[0] >= 0 and read[-1] < n)
                for out, query, weights, keys in (
                        (miss, q_i, w, stored), (miss_ref, q_i, w, k_i),
                        (miss_own, np.asarray(step["choice_query"][i]),
                         np.asarray(step["choice_weights"][i]), stored)):
                    want = dots3.choose(query, weights, keys, topk)
                    out.append(
                        1.0 - len(np.intersect1d(read, want)) / len(want)
                        if sound else 1.0)
                held = full
            else:
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
                tail.append(both(ring, i, rows, wrote, ref))
                held = ring
            if layer == 0:
                by_head = [_relative(
                    np.asarray(held["cached_latent"][i], np.float32)[rows],
                    np.asarray(ref["latent"])[wrote])]
        return {"margin": margin,
                "errors": {"by_layer": by_layer, "by_head": [by_head],
                           "tail_by_layer": tail, "index_by_layer": index},
                "selection": {"miss_by_layer": miss,
                              "choice_miss_by_layer": miss_own,
                              "miss_reference_keys_by_layer": miss_ref}}


def build(env, plan):
    import deepspeed_tpu
    from deepspeed_tpu import serving
    from deepspeed_tpu.models.transformer_lm import GPT

    c, s = env.config, env.config["serve"]
    engine = deepspeed_tpu.init_inference(
        GPT(model_config(c)), dtype=s["dtype"],
        seed=_common.program_seed(env.seed))
    system = LatentKindsServeSystem(env, engine, None, None)
    system.subscribe(system.on_bus)      # the plan, and the live positions
    system.scheduler = serving.build_serving(engine, dict(s["serving"]))
    system.scheduler.retain_lanes = True      # ``live_lanes`` reads them
    itemsize = 2 if s["dtype"] in ("bf16", "bfloat16") else 4
    kinds = [KINDS[k] for k in c["layer_types"]]
    slots = system.scheduler.slots
    _, held = c["moe"]["experts_held"]
    moe_layers = len(kinds) - c["first_k_dense_replace"]
    # the pairs a step routes to the held experts, by the routers' own
    # balance: every lane's token chooses top_k of routed_over
    rows = slots * c["num_experts_per_tok"] * held / c["moe"]["routed_over"]
    system.info = {
        "slots": slots,
        "decode_program": "jit_decode_k",
        "attention": {
            "itemsize": itemsize, "window": head_sizes(c, "window"),
            "full": head_sizes(c, "attention"),
            "indexer": dict(q_rank=c["q_lora_rank"], hidden=c["hidden_size"],
                            ix_heads=c["index_n_heads"],
                            ix_dim=c["index_head_dim"]),
            "window_layers": kinds.count("window"),
            "full_layers": kinds.count("attention")},
        "weights": dict(
            kinds=[kind_sizes(c, k) for k in kinds],
            n_dense=c["first_k_dense_replace"], vocab=c["vocab_size"],
            hidden=c["hidden_size"], dense_width=c["intermediate_size"],
            expert_width=c["moe_intermediate_size"],
            n_shared=c["n_shared_experts"],
            n_routed=c["moe"]["routed_over"], itemsize=itemsize),
        "experts_held": held,
        "held_experts_step": dict(
            mla_flops.held_experts_step(
                rows, c["hidden_size"], c["moe_intermediate_size"], held,
                itemsize), calls_per_step=moe_layers),
    }
    return system
