"""Keye-VL-2.0's language model (grouped-query attention over the
positions a lightning indexer chooses, three-section rotary, an expert
layer of which this chip holds one device's share) served through
``init_inference`` -> ``serving.build_serving`` -> the continuous-batching
scheduler, the entry points the other serve cells use, with the plain
reference beside it. Sizes come from the configuration file's published
keys, its ``sa_config`` and its ``moe`` block."""
import numpy as np

from perfbench import dsa_flops, mla_flops
from perfbench.builders import _common, deepseek_v2_serve


def model_config(config, section=None):
    """The program's ``GPTConfig`` for a configuration file's published
    keys, served as its ``serve`` section (or ``section``) says."""
    from deepspeed_tpu.models.transformer_lm import GPTConfig, IndexerConfig

    from perfbench.reference import keye_vl

    c, s, sa = config, section or config["serve"], config["sa_config"]
    sizes = keye_vl.sizes(c)        # raises for another form of the block
    return GPTConfig(
        vocab_size=c["vocab_size"], n_positions=s["cache_positions"],
        n_embd=c["hidden_size"], n_layer=c["num_hidden_layers"],
        n_head=c["num_attention_heads"],
        n_kv_head=c["num_key_value_heads"], attn_head_dim=c["head_dim"],
        norm="rmsnorm", layer_norm_epsilon=c["rms_norm_eps"],
        activation=c["hidden_act"], gated_mlp=True, use_bias=False,
        rotary=True, rope_theta=float(c["rope_theta"]),
        learned_positions=False,
        tie_word_embeddings=c["tie_word_embeddings"], qk_norm="head",
        mrope_section=sizes["sections"],
        indexer=IndexerConfig(
            n_heads=sa["indexer_num_heads"], head_dim=sa["indexer_head_dim"],
            topk=sa["topk"], q_chunk=sa["q_chunk_size"],
            kv_chunk=sa["kv_chunk_size"]),
        dtype=_common.dtype(s["compute_dtype"]),
        param_dtype=_common.dtype(s["param_dtype"]), scan_layers=True,
        use_flash_attention=False, num_logits_to_keep=1,
        moe_num_experts=c["moe"]["routed_over"],
        moe_top_k=c["num_experts_per_tok"], moe_drop_tokens=False,
        moe_gated_experts=True, moe_norm_topk_prob=c["norm_topk_prob"],
        moe_intermediate_size=c["moe_intermediate_size"],
        moe_experts_held=tuple(c["moe"]["experts_held"]))


def attention_sizes(c):
    sa = c["sa_config"]
    return dict(n_heads=c["num_attention_heads"],
                n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                ix_heads=sa["indexer_num_heads"],
                ix_dim=sa["indexer_head_dim"])


def _padded(n, to):
    return -(-n // to) * to


class SelectedServeSystem(deepseek_v2_serve.LatentServeSystem):
    """``LatentServeSystem`` (the plan event, the live positions, the
    routers' load, the lanes a run left) whose reference is
    ``perfbench/reference/keye_vl.py`` and whose lanes' "state" is what
    they keep a position: ``k``, ``v`` and the index key ``kI`` of every
    row a live lane's request wrote, and the rows its last decode query
    attended over with the query that chose them, as the decode program
    left them (``chosen_rows``, ``choice_query``, ``choice_weights``)."""

    # the reference runs at a few lengths, so that requests share compiles
    REFERENCE_PAD = 4096

    def __init__(self, *args):
        super().__init__(*args)
        self.chosen_at_close = {}       # lane -> rows its last step read

    def on_bus(self, ev):
        super().on_bus(ev)
        if ev.get("kind") == "serve.cache_plan" \
                and self.scheduler is not None:
            # the index keys' bytes are the layout's to say
            # (``kv_cache_stats``); the scheduler's event leaves them out
            self.cache_plan = dict(
                ev, index_key_bytes_per_lane=self.scheduler.kv_cache_stats()
                .get("index_key_bytes_per_lane", 0))

    def live_lanes(self, count, rng):
        """``LatentServeSystem.live_lanes`` with each chosen lane's rows
        (``cached_key``, ``cached_value``, ``cached_index_key`` ``[layers,
        S, ...]``, ``valid`` and what its last step left: ``chosen_rows``
        ``[layers, topk]``, ``choice_query``, ``choice_weights``) taken
        to the host as they are sliced: a lane's rows are 0.32 GB here,
        and slices of several held on the device beside the 10 GB cache
        they are cut from would be the run's memory peak. Of EVERY lane
        that held a request it keeps how many rows the decode program says
        its last step attended over (``chosen_at_close``: the rows >= 0 of
        ``chosen_rows``, the layers' mean). The cache is let go
        afterwards."""
        import jax

        kept, self.scheduler.lanes_at_exit = \
            self.scheduler.lanes_at_exit, None
        if kept is None:
            return []
        lanes = sorted(kept.live)
        left = {n: jax.device_get(kept.last_step(n)) for n in lanes}
        self.chosen_at_close = {
            n: float((rows["chosen_rows"] >= 0).sum(-1).mean())
            for n, rows in left.items() if "chosen_rows" in rows}
        chosen = rng.choice(len(lanes), size=min(count, len(lanes)),
                            replace=False)
        return [dict(lane=lanes[i], request_id=kept.live[lanes[i]].request_id,
                     tokens=[int(t) for t in kept.live[lanes[i]].tokens],
                     **jax.device_get(kept.positions(lanes[i])),
                     **left[lanes[i]])
                for i in chosen]

    def mean_selected_positions(self):
        """The rows a decode step's attention read, all lanes: what the
        decode program left in ``chosen_rows`` at the window's last step,
        counted by ``live_lanes`` (so None before ``check`` has run, and
        for a program that leaves none). Contexts only grow inside a
        window, so where every lane is over ``topk`` when it opens this is
        every step's count."""
        return sum(self.chosen_at_close.values()) or None

    def reference_pass(self, seq, offset=0):
        """``hidden_and_states`` of the plain reference over ``seq``, one
        float32 forward of the same parameters, right-padded with zeros to
        a multiple of ``REFERENCE_PAD``, the first token at position
        ``offset`` of every stream."""
        from perfbench.reference import keye_vl

        if self._reference is None:
            self._reference = keye_vl.sizes(self.env.config)
        ids = np.zeros((_padded(len(seq), self.REFERENCE_PAD),), np.int32)
        ids[:len(seq)] = seq
        hidden, rows, _, queries = keye_vl.hidden_and_states(
            self.reference_params(), ids, self._reference, offset=offset,
            queries_at=(len(seq) - 1,))
        return ids, hidden, rows, queries

    def judge_lane(self, prompt, lane):
        """For one of ``live_lanes``, from ONE reference pass over the
        prompt and every token the lane has taken in (rotary counting
        cache rows, as the program's does: the reference starts at the
        lane's first row):

        * ``margin``: of every served token, the first included, how far
          below the reference's largest logit at its position it lies, in
          units of that position's logit standard deviation;
        * ``errors``: the norm of the difference between the lane's rows
          and the reference's over the norm of the reference's, over every
          row the request wrote (the bucket's left padding is not among
          them): ``by_layer`` of keys and values together, ``by_head`` the
          same per KV head (``[layers][Hkv]``), ``tail_by_layer`` of the
          index keys. The rows must be exactly those ``valid`` marks;
        * ``selection``: of the rows the lane's LAST decode step attended
          over (``chosen_rows`` as the decode program left them; its query
          is the last token taken in, which sees every row written), per
          layer, the share that is NOT among the ``topk`` rows of largest
          score when a query scores the lane's OWN stored index keys in
          float64 on the host (``keye_vl.choose``): ``miss_by_layer`` for
          the reference's query of that position (``qI``, ``w``, float32:
          the program's query and keys are the model's), and
          ``choice_miss_by_layer`` for the query the step itself left
          beside its rows (``choice_query``, ``choice_weights``: given its
          own inputs the step's scores and ``top_k`` are the float32
          ones). A step that read another number of rows than ``min(topk,
          rows written)``, or a row the request did not write, misses them
          all. ``miss_reference_keys_by_layer`` is the first against the
          set the reference chooses over its own float32 keys (what the
          stored keys' rounding moves), and ``median_row_error_by_layer``
          the median over the rows written of a row's own relative error
          of keys and values together: both for the record, held to
          nothing."""
        import jax.numpy as jnp

        from perfbench.reference import keye_vl

        tokens = lane["tokens"]
        n = len(prompt) + len(tokens)
        bucket = self.scheduler.prompt_bucket
        first = _padded(len(prompt), bucket) - len(prompt)
        ids, hidden, (k, v, k_i), (q_i, w) = self.reference_pass(
            list(prompt) + tokens, offset=first)
        valid = np.asarray(lane["valid"][0])
        if valid[first:first + n].sum() != n or valid.sum() != n:
            raise ValueError(
                f"lane {lane['lane']} marks {int(valid.sum())} rows valid, "
                f"its request wrote {n} from row {first}")
        at = list(range(len(prompt) - 1, n - 1))
        margin = keye_vl.position_stats(
            self.reference_params(), ids, self._reference, at, tokens,
            pad_to=512, states=hidden)["margin"].tolist()

        def sums(got, ref, axes):
            diff = jnp.asarray(got[:, first:first + n], jnp.float32) \
                - ref[:, :n]
            return (np.asarray(jnp.sum(diff * diff, axes), np.float64),
                    np.asarray(jnp.sum(ref[:, :n] * ref[:, :n], axes),
                               np.float64))

        num, den = (a + b for a, b in zip(
            sums(lane["cached_key"], k, (1, 3)),
            sums(lane["cached_value"], v, (1, 3))))         # [layers, Hkv]
        t_num, t_den = sums(lane["cached_index_key"], k_i, (1, 2))
        r_num, r_den = (a + b for a, b in zip(
            sums(lane["cached_key"], k, (2, 3)),
            sums(lane["cached_value"], v, (2, 3))))         # [layers, n]
        topk = self.env.config["sa_config"]["topk"]
        stored = np.asarray(lane["cached_index_key"], np.float32)
        q_i, w, k_i = (np.asarray(a) for a in (q_i, w, k_i))
        own_q = np.asarray(lane["choice_query"], np.float64)
        own_w = np.asarray(lane["choice_weights"], np.float64)
        miss, miss_ref, miss_own = [], [], []
        for layer, rows in enumerate(np.asarray(lane["chosen_rows"])):
            read = np.unique(rows[rows >= 0]) - first
            sound = (len(read) == min(topk, n) == int((rows >= 0).sum())
                     and read[0] >= 0 and read[-1] < n)
            mine = stored[layer, first:first + n]
            for out, query, weights, keys in (
                    (miss, q_i[layer, 0], w[layer, 0], mine),
                    (miss_ref, q_i[layer, 0], w[layer, 0], k_i[layer, :n]),
                    (miss_own, own_q[layer], own_w[layer], mine)):
                want = keye_vl.choose(query, weights, keys, topk)
                out.append(1.0 - len(np.intersect1d(read, want)) / len(want)
                           if sound else 1.0)
        return {"margin": margin, "errors": {
            "by_layer": np.sqrt(num.sum(1) / den.sum(1)).tolist(),
            "by_head": np.sqrt(num / den).tolist(),
            "tail_by_layer": np.sqrt(t_num / t_den).tolist()},
            "selection": {
                "miss_by_layer": miss,
                "choice_miss_by_layer": miss_own,
                "miss_reference_keys_by_layer": miss_ref,
                "median_row_error_by_layer": np.median(
                    np.sqrt(r_num / r_den), axis=1).tolist()}}


def build(env, plan):
    import deepspeed_tpu
    from deepspeed_tpu import serving
    from deepspeed_tpu.models.transformer_lm import GPT

    c, s = env.config, env.config["serve"]
    engine = deepspeed_tpu.init_inference(
        GPT(model_config(c)), dtype=s["dtype"],
        seed=_common.program_seed(env.seed))
    system = SelectedServeSystem(env, engine, None, None)
    system.subscribe(system.on_bus)      # the plan, and the live positions
    system.scheduler = serving.build_serving(engine, dict(s["serving"]))
    system.scheduler.retain_lanes = True      # ``live_lanes`` reads them
    itemsize = 2 if s["dtype"] in ("bf16", "bfloat16") else 4
    layers, slots = c["num_hidden_layers"], system.scheduler.slots
    _, held = c["moe"]["experts_held"]
    # the pairs a step routes to the held experts, by the routers' own
    # balance: every lane's token chooses top_k of routed_over
    rows = slots * c["num_experts_per_tok"] * held / c["moe"]["routed_over"]
    experts = mla_flops.held_experts_step(
        rows, c["hidden_size"], c["moe_intermediate_size"], held, itemsize)
    sizes = attention_sizes(c)
    system.info = {
        "slots": slots,
        "decode_program": "jit_decode_k",
        "weight_bytes": dsa_flops.decode_weight_bytes(
            layers, c["vocab_size"], c["hidden_size"],
            c["moe_intermediate_size"], held, c["moe"]["routed_over"],
            itemsize=itemsize, **sizes),
        "kv_bytes_per_position": dsa_flops.kv_bytes_per_position(
            layers, sizes["n_kv_heads"], sizes["head_dim"], itemsize),
        "selected_attention": dict(sizes, layers=layers, itemsize=itemsize,
                                   topk=c["sa_config"]["topk"]),
        "held_experts_step": dict(experts, calls_per_step=layers),
    }
    return system
