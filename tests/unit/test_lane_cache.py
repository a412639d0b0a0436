"""The one owner of the lane cache's layout (inference/lane_cache.py) over
the six caches the repo serves, at CPU sizes: what it says of each is what
the scheduler and ``serving/disagg.py`` said on the parent of the PR that
brought it (``tests/unit/data/lane_cache_geometry.json``, recorded there
with these models: 7a4dead)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import serving
from deepspeed_tpu.inference.lane_cache import (
    LaneLayout,
    RecurrentStateError,
)
from deepspeed_tpu.models.transformer_lm import (
    GPT,
    GPTConfig,
    LatentCacheError,
)
from deepspeed_tpu.parallel.mesh import reset_default_topology
from deepspeed_tpu.serving.disagg import lane_kv_bytes

HERE = os.path.dirname(os.path.abspath(__file__))
CACHES = ["dense", "ring", "int8", "hybrid", "retention", "latent"]


def lane_model(name):
    from brumby_tiny import TINY_BRUMBY
    from deepseek_v2_tiny import TINY_DEEPSEEK
    from deepspeed_tpu.ops.sparse_attention.sparse_attention_utils import \
        apply_sparse_attention
    from falcon_h1_tiny import TINY_FALCON_H1
    from perfbench.builders import (
        brumby_serve,
        deepseek_v2_serve,
        falcon_h1_serve,
    )

    base = dict(vocab_size=128, n_positions=256, n_embd=32, n_layer=2,
                n_head=4, dtype=jnp.float32, scan_layers=True)
    return {
        "dense": lambda: GPT(GPTConfig(**base)),
        "ring": lambda: apply_sparse_attention(
            GPT(GPTConfig(rotary=True, learned_positions=False,
                          kv_cache_slack_blocks=1, **base)),
            {"mode": "local_sliding_window", "block": 16,
             "num_sliding_window_blocks": 3}),
        "int8": lambda: GPT(GPTConfig(kv_cache_dtype="int8", **base)),
        "hybrid": lambda: GPT(falcon_h1_serve.model_config(TINY_FALCON_H1)),
        "retention": lambda: GPT(brumby_serve.model_config(TINY_BRUMBY)),
        "latent": lambda: GPT(deepseek_v2_serve.model_config(TINY_DEEPSEEK)),
    }[name]()


# what each kind of cache refuses: (feature asked, class, ``feature``)
REFUSALS = {
    "hybrid": [("prefix_cache", RecurrentStateError, "prefix_cache"),
               ("draft_engine", RecurrentStateError, "draft_engine")],
    "retention": [("prefix_cache", RecurrentStateError, "prefix_cache"),
                  ("draft_engine", RecurrentStateError, "draft_engine")],
    "latent": [("prefix_cache", LatentCacheError, "prefix_cache"),
               ("draft_engine", LatentCacheError, "draft_engine"),
               ("tp", LatentCacheError, "tp > 1")],
}


@pytest.mark.parametrize("name", CACHES)
def test_the_layout_says_of_each_cache_what_the_parent_said(name):
    with open(os.path.join(HERE, "data", "lane_cache_geometry.json"),
              encoding="utf-8") as f:
        want = json.load(f)[name]
    reset_default_topology()
    eng = deepspeed_tpu.init_inference(lane_model(name), dtype="fp32",
                                       seed=0)
    sched = serving.ContinuousBatchingScheduler(eng, slots=3,
                                                prompt_bucket=16)
    sched._ensure_compiled()
    lanes = sched.lane_cache
    # (since PR 52 the geometry also says how many layers keep each
    # declared leaf: all of them, in a model of one kind of layer)
    geometry = dict(lanes.geometry())
    assert set(geometry.pop("leaf_layers").items()) == {
        (leaf.name, eng.module.config.n_layer) for leaf in lanes.leaves}
    assert geometry == want["geometry"]
    assert list(geometry) == [
        "kv_cache_dtype", "resident_bytes", "unquantized_bytes",
        "bytes_per_lane", "state_bytes", "conv_bytes", "norm_bytes",
        "kv_bytes", "state_bytes_per_lane", "conv_bytes_per_lane",
        "norm_bytes_per_lane", "kv_bytes_per_lane",
        "latent_bytes_per_lane", "lanes", "compression_ratio"]
    stats = sched.kv_cache_stats(hbm_override_gib=16.0)
    assert {k: stats[k] for k in want["geometry"]} == want["geometry"]
    assert stats["lanes_at_hbm_budget"] \
        == 16 * 2 ** 30 // want["geometry"]["bytes_per_lane"]
    # the capacity table's two numbers, from a bare module
    assert lane_kv_bytes(lane_model(name)) == want["lane_kv_bytes"]
    assert lane_kv_bytes(lane_model(name), slots=3) \
        == want["lane_kv_bytes[slots=3]"]
    assert jax.tree.map(lambda s: (s.shape, s.dtype),
                        LaneLayout(lane_model(name), 3).shapes) \
        == jax.tree.map(lambda s: (s.shape, s.dtype), lanes.shapes)

    # the empty cache: nothing cached in a ring's slots, all else zero
    empty = sched._empty_cache()
    seen = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(empty)[0]:
        leaf_name = str(path[-1].key)
        seen.add(leaf_name)
        assert np.all(np.asarray(leaf) == (
            -1 if leaf_name == "slot_pos" else 0)), leaf_name
    assert ("slot_pos" in seen) == (name == "ring")
    assert jax.tree.map(lambda a: (a.shape, a.dtype), empty) \
        == jax.tree.map(lambda s: (s.shape, s.dtype), lanes.shapes)
    # every declared leaf is in the cache, and the declaration is the
    # model's
    assert {leaf.name for leaf in lanes.leaves} <= seen
    assert lanes.leaves == eng.module.config.cache_leaves

    # the refusals: each by its class and its ``feature``
    for asked, cls, feature in REFUSALS.get(name, ()):
        with pytest.raises(cls) as err:
            if asked == "tp":
                try:
                    reset_default_topology()
                    tp = deepspeed_tpu.init_inference(
                        lane_model(name), dtype="fp32", mp_size=2)
                    serving.ContinuousBatchingScheduler(tp, slots=3)
                finally:
                    reset_default_topology()
            else:
                lanes.refuse(draft_engine=asked == "draft_engine",
                             prefix_cache=asked == "prefix_cache")
        assert err.value.feature.startswith(feature)
        assert feature in str(err.value)
    if name not in REFUSALS:
        lanes.refuse(draft_engine=True, prefix_cache=True)
