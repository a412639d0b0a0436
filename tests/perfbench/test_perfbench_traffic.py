"""The generators: what the seed may change and what it may not."""
import collections
import os
import types

import numpy as np
import pytest

from perfbench import stats
from perfbench.traffic_kinds import serve_closed, train_repeat

ROOT = stats.repo_root()


def _env(traffic, seed, vocab=50257, chips=1):
    return types.SimpleNamespace(
        traffic=stats.load_json(os.path.join(
            ROOT, "perfbench", "traffic", traffic + ".json")),
        config={"model": {"vocab_size": vocab}}, seed=seed, chips=chips)


SEEDS = [0, 7, 2 ** 31 + 11]


@pytest.fixture(scope="module")
def plans():
    return {s: serve_closed.plan(_env("serve-closed-chat", s))
            for s in SEEDS}


@pytest.mark.parametrize("seed", SEEDS)
def test_every_cycle_uses_each_length_once(plans, seed):
    plan = plans[seed]
    n = len(plan.prompt_lengths)
    assert n == 40 and len(plan.requests) >= 1200
    for c in range(3):
        cyc = plan.requests[c * n:(c + 1) * n]
        assert sorted(len(p) for p, _ in cyc) == sorted(plan.prompt_lengths)
        assert sorted(o for _, o in cyc) == sorted(plan.output_lengths)


def test_seeds_share_the_multiset_and_differ_in_order_and_ids(plans):
    a, b = plans[SEEDS[0]], plans[SEEDS[1]]
    la = [(len(p), o) for p, o in a.requests[:40]]
    lb = [(len(p), o) for p, o in b.requests[:40]]
    assert collections.Counter(x for x, _ in la) == \
        collections.Counter(x for x, _ in lb)
    assert collections.Counter(o for _, o in la) == \
        collections.Counter(o for _, o in lb)
    assert la != lb                                   # order and pairing
    assert a.requests[0][0] != b.requests[0][0]       # token ids
    again = serve_closed.plan(_env("serve-closed-chat", SEEDS[0]))
    assert again.requests[:80] == a.requests[:80]     # same seed, same inputs
    assert again.ramp == a.ramp


def test_requests_beyond_the_pregenerated_ones_follow_the_same_rule(plans):
    plan = serve_closed.plan(_env("serve-closed-chat", 3))
    have = len(plan.requests)
    plan.cursor = have
    extra = [plan.next_request() for _ in range(40)]
    assert sorted(len(p) for p, _ in extra) == sorted(plan.prompt_lengths)
    assert len(plan.requests) == have + 40


def test_lengths_fit_the_model_and_the_tail_shares_one_bucket(plans):
    plan = plans[SEEDS[0]]
    b = plan.bucket
    for p, o in plan.requests[:400]:
        assert serve_closed.bucketed(len(p), b) + o <= 1024
        assert all(0 <= t < 50257 for t in p[:8])
    tail = sorted(plan.prompt_lengths)[-4:]
    assert {serve_closed.bucketed(n, b) for n in tail} == {896}
    rest = sorted(plan.prompt_lengths)[:-4]
    assert max(serve_closed.bucketed(n, b) for n in rest) <= 640
    assert 8 <= min(plan.output_lengths) and max(plan.output_lengths) <= 128


def test_ramp_warms_every_bucket_with_staggered_outputs(plans):
    plan = plans[SEEDS[0]]
    used = {serve_closed.bucketed(n, plan.bucket)
            for n in plan.prompt_lengths}
    ramp = {serve_closed.bucketed(len(p), plan.bucket) for p, _ in plan.ramp}
    assert ramp == used and len(plan.ramp) == plan.clients == 16
    outs = [o for _, o in plan.ramp]
    assert len(set(outs)) == len(outs)


def test_a_grid_that_cannot_fit_is_refused():
    env = _env("serve-closed-chat", 0)
    env.traffic = dict(env.traffic, max_positions=1000)
    with pytest.raises(ValueError):
        serve_closed.plan(env)


@pytest.mark.parametrize("traffic,vocab,chips,rows", [
    ("train-seq1024-micro6", 50257, 1, 6),
    ("train-seq1024-micro6", 50257, 4, 24),
    ("train-mlm-seq128-micro64", 30522, 1, 64),
])
def test_training_batch_comes_from_the_seed(traffic, vocab, chips, rows):
    a = train_repeat.plan(_env(traffic, 5, vocab, chips))
    b = train_repeat.plan(_env(traffic, 5, vocab, chips))
    c = train_repeat.plan(_env(traffic, 6, vocab, chips))
    ids = a.batch["input_ids"]
    assert ids.shape == (rows, a.seq) and ids.dtype == np.int32
    assert a.tokens_per_step == rows * a.seq
    assert np.array_equal(ids, b.batch["input_ids"])
    assert not np.array_equal(ids, c.batch["input_ids"])
    assert ids.min() >= 0 and ids.max() < vocab
    labels = a.batch["labels"]
    if a.label_share == 1.0:
        assert labels is ids
    else:
        share = float((labels != -100).mean())
        assert abs(share - 0.15) < 0.02
        assert np.array_equal(labels[labels != -100], ids[labels != -100])
