"""Elastic topology resume (docs/recovery.md "Elastic topology resume").

Covers the whole N -> N' resume path end to end:

  * data re-stride arithmetic — the union of the new topology's per-rank
    streams is EXACTLY the unconsumed remainder of the global order, for
    shrink, grow, and non-divisor pairs, including mid-epoch resume points
    (property tests over (N, N') in {(8,4), (4,8), (6,4), (8,3)});
  * checkpoint re-layout — an N-device ZeRO-partitioned tree placed on an
    N'-device mesh and back is bitwise identical (runtime/reshard.py);
  * manifest topology metadata — v2 manifests carry the block, v1
    manifests (checked-in fixture) stay loadable same-topology and fail
    with a clear error naming the missing fields when a reshard was
    expected;
  * elastic agent — a post-failure device-count change is a topology
    change, not a crash: no backoff, no budget, and the new device count
    is exported together with DS_TPU_ELASTIC_PREV_WORLD and
    DS_TPU_LAST_VALID_TAG;
  * chaos scenarios (slow) — train on N virtual devices, kill mid-epoch,
    resume on N': loss trajectory matches the uninterrupted run and the
    dataloader stream is token-identical, with an ``elastic.reshard``
    telemetry event carrying per-phase timings.
"""

import copy
import json
import os
import sys
import textwrap

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.data.pipeline import PackedDataPipeline
from deepspeed_tpu.data.streaming import ShardedSampleStream
from deepspeed_tpu.parallel.mesh import MeshTopology
from deepspeed_tpu.runtime import checkpoint_manifest as cm
from deepspeed_tpu.runtime import constants as ds_constants
from deepspeed_tpu.runtime import layout, reshard
from deepspeed_tpu.runtime.zero.sharding import ZeroShardingRules
from deepspeed_tpu.telemetry import telemetry_bus

from unit.simple_model import SimpleModel, random_dataset, tiny_gpt_config

FIXTURE_V1 = os.path.join(os.path.dirname(__file__), "fixtures",
                          "manifest_v1")

RESTRIDE_PAIRS = [(8, 4), (4, 8), (6, 4), (8, 3)]


@pytest.fixture(autouse=True)
def _no_prev_world(monkeypatch):
    """The agent's reshard-expected signal must never leak between tests
    (or in from a real elastic relaunch of the test runner itself)."""
    monkeypatch.delenv(ds_constants.ELASTIC_PREV_WORLD_ENV, raising=False)


# ---------------------------------------------------------------------------
# data re-stride: property tests over the global order
# ---------------------------------------------------------------------------
def global_order(seed, epoch, n):
    order = np.arange(n)
    np.random.RandomState(seed + epoch).shuffle(order)
    return order


def make_streams(dataset, num_shards, seed=3):
    return [ShardedSampleStream(dataset, seed=seed, shard_rank=r,
                                num_shards=num_shards)
            for r in range(num_shards)]


class TestRestrideProperty:
    """The invariant: all old ranks advance in lockstep, so a saved cursor
    c under N shards means the global prefix [offset, offset + c*N) is
    consumed; the new N' ranks must jointly stride the remainder of the
    SAME epoch (same boundary) with zero loss or duplication."""

    SEED = 3
    L = 53  # prime-ish: every pair below truncates to a different boundary

    @pytest.mark.parametrize("n_old,n_new", RESTRIDE_PAIRS)
    @pytest.mark.parametrize("cut", [0, 1, 3, "last"])
    def test_union_is_exact_remainder(self, n_old, n_new, cut):
        data = list(range(self.L))
        streams = make_streams(data, n_old, seed=self.SEED)
        spe = streams[0].samples_per_epoch
        cut = spe - 1 if cut == "last" else cut
        consumed = []
        for _ in range(cut):  # lockstep: one sample per rank per step
            for s in streams:
                consumed.append(next(s))
        state = streams[0].state_dict()
        assert state == streams[-1].state_dict()  # rank-independent

        order = global_order(self.SEED, 0, self.L)
        boundary = n_old * (self.L // n_old)
        frontier = cut * n_old
        assert consumed == [data[order[g]] for g in range(frontier)]
        expected_remainder = [data[order[g]]
                              for g in range(frontier, boundary)]

        resumed = make_streams(data, n_new, seed=self.SEED)
        for s in resumed:
            s.load_state_dict(state)
        per_rank = []
        for r, s in enumerate(resumed):
            count = len(range(frontier + r, boundary, n_new))
            got = [next(s) for _ in range(count)]
            assert s.epoch == 0, "drained past the saved epoch's boundary"
            # rank r' owns exactly the strided positions frontier+r'+k*N'
            assert got == [data[order[g]]
                           for g in range(frontier + r, boundary, n_new)]
            per_rank.append(got)
        union = [x for got in per_rank for x in got]
        assert sorted(union) == sorted(expected_remainder)
        assert len(union) == boundary - frontier  # disjoint: no duplicates

    @pytest.mark.parametrize("n_old,n_new", RESTRIDE_PAIRS)
    def test_restride_mid_later_epoch_uses_that_epochs_order(
            self, n_old, n_new):
        data = list(range(self.L))
        streams = make_streams(data, n_old, seed=self.SEED)
        spe = streams[0].samples_per_epoch
        for _ in range(spe + 2):  # all of epoch 0 plus 2 steps of epoch 1
            for s in streams:
                next(s)
        assert streams[0].epoch == 1
        state = streams[0].state_dict()

        resumed = make_streams(data, n_new, seed=self.SEED)
        for s in resumed:
            s.load_state_dict(state)
        order1 = global_order(self.SEED, 1, self.L)
        frontier = 2 * n_old
        # next sample of new rank 0 is the frontier of EPOCH 1's order
        assert next(resumed[0]) == data[order1[frontier]]

    def test_epoch_rollover_after_restride(self):
        """Once the resumed ranks drain the old epoch's remainder, the
        next epoch starts fresh at the NEW topology's boundary."""
        n_old, n_new = 8, 3
        data = list(range(self.L))
        streams = make_streams(data, n_old, seed=self.SEED)
        for _ in range(2):
            for s in streams:
                next(s)
        state = streams[0].state_dict()
        resumed = make_streams(data, n_new, seed=self.SEED)
        for s in resumed:
            s.load_state_dict(state)
        boundary = n_old * (self.L // n_old)
        frontier = 2 * n_old
        rank0_count = len(range(frontier, boundary, n_new))
        for _ in range(rank0_count):
            next(resumed[0])
        nxt = next(resumed[0])  # rolls the epoch
        assert resumed[0].epoch == 1
        assert resumed[0].epoch_boundary == n_new * (self.L // n_new)
        assert nxt == data[global_order(self.SEED, 1, self.L)[0]]

    def test_same_topology_resume_bit_identical(self):
        data = list(range(self.L))
        ref = ShardedSampleStream(data, seed=7, shard_rank=1, num_shards=4)
        live = ShardedSampleStream(data, seed=7, shard_rank=1, num_shards=4)
        for _ in range(5):
            next(live)
        state = live.state_dict()
        expect = [next(live) for _ in range(20)]  # crosses an epoch edge
        fresh = ShardedSampleStream(data, seed=7, shard_rank=1, num_shards=4)
        fresh.load_state_dict(state)
        assert [next(fresh) for _ in range(20)] == expect
        # and identical to a never-interrupted stream at the same position
        for _ in range(5):
            next(ref)
        assert [next(ref) for _ in range(20)] == expect

    def test_legacy_three_int_state_resumes_same_topology(self):
        """Pre-geometry states ({seed, epoch, cursor}) must keep resuming
        exactly as before the manifest/geometry change."""
        data = list(range(self.L))
        live = ShardedSampleStream(data, seed=5, shard_rank=2, num_shards=4)
        for _ in range(7):
            next(live)
        legacy = {k: live.state_dict()[k] for k in ("seed", "epoch",
                                                    "cursor")}
        expect = [next(live) for _ in range(15)]
        fresh = ShardedSampleStream(data, seed=5, shard_rank=2, num_shards=4)
        fresh.load_state_dict(legacy)
        assert [next(fresh) for _ in range(15)] == expect

    def test_pipeline_restride_delivers_pending_work_once(self):
        """The half-packed rows and ready batches in a saved pipeline
        state belong to ONE old pipeline; after a re-stride exactly one
        new rank (rank 0) may carry them forward."""
        rng = np.random.RandomState(0)
        data = [{"input_ids": rng.randint(1, 97, size=rng.randint(3, 15))
                 .astype(np.int32)} for _ in range(64)]
        pipe = PackedDataPipeline(data, batch_size=2, seq_length=32,
                                  seed=9, shard_rank=0, num_shards=2)
        for _ in range(3):
            next(pipe)
        state = pipe.state_dict()
        assert state["stream"]["num_shards"] == 2

        resumed = [PackedDataPipeline(data, batch_size=2, seq_length=32,
                                      seed=9, shard_rank=r, num_shards=4)
                   for r in range(4)]
        for p in resumed:
            p.load_state_dict(copy.deepcopy(state))
        # rank 0 carries the half-packed rows forward; everyone else
        # starts clean (the rows would otherwise be delivered 4 times)
        assert resumed[0]._packer.state_dict() == state["packer"]
        for p in resumed[1:]:
            assert p._packer.state_dict()["rows"] == []
            assert p._ready == []
        for p in resumed:
            batch = next(p)  # every rank still produces batches
            assert batch["input_ids"].shape == (2, 32)


# ---------------------------------------------------------------------------
# checkpoint re-layout: N -> N' -> N bitwise round-trip
# ---------------------------------------------------------------------------
def _param_tree(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "dense1": {"kernel": rng.randn(16, 32).astype(np.float32),
                   "bias": rng.randn(32).astype(np.float32)},
        "head": {"kernel": rng.randn(32, 8).astype(np.float32)},
        # indivisible by any mesh size below: stays replicated everywhere
        "norm": {"scale": rng.randn(5).astype(np.float32)},
    }


def _sharding_tree(n_devices, tree):
    topo = MeshTopology(fsdp=n_devices, devices=jax.devices()[:n_devices])
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype),
        tree)
    return ZeroShardingRules(topo, stage=3).param_sharding_tree(shapes)


class TestReshardRoundtrip:
    @pytest.mark.parametrize("n_old,n_new", RESTRIDE_PAIRS)
    def test_roundtrip_bitwise(self, eight_devices, n_old, n_new):
        host = _param_tree()
        sh_old = _sharding_tree(n_old, host)
        sh_new = _sharding_tree(n_new, host)
        placed_old, _ = reshard.place_tree(host, sh_old)
        if 32 % n_old == 0:  # indivisible counts (6) legally replicate
            assert "fsdp" in str(
                placed_old["dense1"]["kernel"].sharding.spec)
        placed_new, phases = reshard.reshard_tree(placed_old, sh_new)
        assert set(phases) == {"gather_s", "place_s", "total_s"}
        assert all(v >= 0 for v in phases.values())
        back, _ = reshard.reshard_tree(placed_new, sh_old)
        for path in (("dense1", "kernel"), ("dense1", "bias"),
                     ("head", "kernel"), ("norm", "scale")):
            a = host[path[0]][path[1]]
            b = np.asarray(jax.device_get(back[path[0]][path[1]]))
            np.testing.assert_array_equal(a, b)

    def test_describe_and_verify_state_dict(self, eight_devices):
        host = _param_tree()
        sh = _sharding_tree(8, host)
        placed, _ = reshard.place_tree(host, sh)
        record = layout.describe_shardings(sh, placed)
        assert record["dense1/kernel"]["shape"] == [16, 32]
        assert any(e == "fsdp" for e in record["dense1/kernel"]["spec"])
        checked, _ = reshard.verify_state_dict(host, record, "model")
        assert checked == 4
        bad = {"dense1": {"kernel": host["dense1"]["kernel"][:, :16],
                          "bias": host["dense1"]["bias"]},
               "head": {"kernel": host["head"]["kernel"]},
               "norm": {"scale": host["norm"]["scale"]}}
        with pytest.raises(reshard.ReshardError,
                           match=r"dense1\.kernel.*\(16, 32\)"):
            reshard.verify_state_dict(bad, record, "model")


# ---------------------------------------------------------------------------
# manifest topology metadata + v1 back-compat
# ---------------------------------------------------------------------------
class TestManifestTopology:
    def test_v2_manifest_carries_topology(self, tmp_path, eight_devices):
        topo = MeshTopology(fsdp=8)
        meta = layout.topology_metadata(topo, zero_stage=3)
        tag_dir = str(tmp_path / "global_step5")
        payload = b"x" * 64
        cm.atomic_write_bytes(os.path.join(tag_dir, "model.msgpack"),
                              payload)
        cm.write_manifest(tag_dir, "global_step5",
                          {"model.msgpack": cm.payload_digest(payload)},
                          topology=meta)
        doc = cm.read_manifest(tag_dir)
        assert doc["version"] == cm.MANIFEST_VERSION == 2
        saved = cm.manifest_topology(tag_dir)
        assert saved["world_size"] == 8
        assert saved["zero_stage"] == 3
        assert saved["axis_sizes"]["fsdp"] == 8
        assert cm.verify_tag_dir(tag_dir) == []
        assert layout.topology_matches(saved, topo, zero_stage=3) == []
        small = MeshTopology(fsdp=4, devices=jax.devices()[:4])
        mismatches = layout.topology_matches(saved, small, zero_stage=3)
        assert any("world_size 8 -> 4" in m for m in mismatches)

    def test_v1_fixture_verifies_and_has_no_topology(self):
        tag_dir = os.path.join(FIXTURE_V1, "global_step1")
        doc = cm.read_manifest(tag_dir)
        assert doc is not None and doc["version"] == 1
        assert cm.verify_tag_dir(tag_dir) == []
        assert cm.manifest_topology(tag_dir) is None

    def test_v1_fixture_same_topology_decide_is_quiet(self, eight_devices):
        decision = reshard.decide(FIXTURE_V1, "global_step1",
                                  MeshTopology(fsdp=8))
        assert decision.saved is None and not decision.needed
        assert "pre-v2" in decision.describe()

    def test_v1_fixture_expected_reshard_names_missing_fields(
            self, eight_devices, monkeypatch):
        monkeypatch.setenv(ds_constants.ELASTIC_PREV_WORLD_ENV, "8")
        topo = MeshTopology(fsdp=4, devices=jax.devices()[:4])
        with pytest.raises(reshard.ReshardError) as e:
            reshard.decide(FIXTURE_V1, "global_step1", topo)
        for field in cm.TOPOLOGY_FIELDS:
            assert field in str(e.value)

    def test_engine_save_writes_topology_and_v1_strip_roundtrips(
            self, tmp_path, eight_devices, monkeypatch):
        """A fresh save carries the block; stripping it back to a v1
        manifest stays loadable same-topology and errors clearly when the
        agent signalled a topology change."""
        cfg = {"train_micro_batch_size_per_gpu": 4,
               "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
               "zero_optimization": {"stage": 3},
               "steps_per_print": 10 ** 9}

        def make():
            from deepspeed_tpu.runtime.dataloader import RepeatingLoader
            engine, _, loader, _ = deepspeed_tpu.initialize(
                model=SimpleModel(hidden_dim=8), config=cfg,
                training_data=random_dataset(64))
            return engine, iter(RepeatingLoader(loader))

        engine, it = make()
        engine.train_batch(it)
        engine.save_checkpoint(str(tmp_path))
        tag = cm.read_latest(str(tmp_path))
        tag_dir = str(tmp_path / tag)
        saved = cm.manifest_topology(tag_dir)
        assert saved is not None
        assert saved["world_size"] == engine.topology.num_devices
        assert saved["zero_stage"] == 3
        assert "params" in saved["partition_specs"]

        # strip back to v1 (sizes/crcs of listed files are untouched)
        doc = cm.read_manifest(tag_dir)
        del doc["topology"]
        doc["version"] = 1
        with open(cm.manifest_path(tag_dir), "w") as f:
            json.dump(doc, f)
        assert cm.verify_tag_dir(tag_dir) == []

        engine2, it2 = make()
        engine2.train_batch(it2)
        loaded_tag, _ = engine2.load_checkpoint(str(tmp_path))
        assert loaded_tag == tag  # same-topology v1 load still works

        monkeypatch.setenv(ds_constants.ELASTIC_PREV_WORLD_ENV,
                           str(engine2.topology.num_devices * 2))
        engine3, it3 = make()
        engine3.train_batch(it3)
        with pytest.raises(reshard.ReshardError, match="partition_specs"):
            engine3.load_checkpoint(str(tmp_path))


# ---------------------------------------------------------------------------
# elastic agent: topology change is not a crash
# ---------------------------------------------------------------------------
def _write_worker(tmp_path, body) -> str:
    worker = tmp_path / "worker.py"
    worker.write_text(textwrap.dedent(body))
    return str(worker)


def _valid_ckpt(tmp_path, tag="global_step7"):
    ckpt = tmp_path / "ckpt"
    tag_dir = str(ckpt / tag)
    path = os.path.join(tag_dir, "model.msgpack")
    cm.atomic_write_bytes(path, b"weights" * 10)
    cm.write_manifest(tag_dir, tag, {"model.msgpack": cm.file_digest(path)})
    cm.write_latest(str(ckpt), tag)
    return str(ckpt), tag


class TestAgentTopologyChange:
    def test_shrink_relaunches_without_budget_and_exports_together(
            self, tmp_path):
        """Worker dies, the slice comes back smaller: the agent relaunches
        immediately (no backoff, no restart budget, no failure-time entry)
        and the next incarnation sees DS_TPU_NUM_PROCS,
        DS_TPU_ELASTIC_PREV_WORLD and DS_TPU_LAST_VALID_TAG together."""
        from deepspeed_tpu.elasticity.elastic_agent import DSElasticAgent

        ckpt, tag = _valid_ckpt(tmp_path)
        log = tmp_path / "env_log"
        worker = _write_worker(tmp_path, f"""
            import json, os, sys
            p = {str(log)!r}
            runs = json.load(open(p)) if os.path.exists(p) else []
            runs.append({{k: os.environ.get(k) for k in (
                "DS_TPU_NUM_PROCS", "DS_TPU_ELASTIC_PREV_WORLD",
                "DS_TPU_LAST_VALID_TAG")}})
            json.dump(runs, open(p, "w"))
            sys.exit(9 if len(runs) == 1 else 0)
        """)
        worlds = [8, 4, 4]  # pre-launch, post-failure probe, pre-relaunch
        agent = DSElasticAgent([sys.executable, worker], {},
                               discover_world=lambda: worlds.pop(0),
                               max_restarts=0, backoff_s=5.0, jitter=0.0,
                               ckpt_dir=ckpt)
        delays = []
        agent._sleep = delays.append
        assert agent.run() == 0
        # max_restarts=0: any ordinary failure would have ended the run —
        # the shrink consumed no budget and slept no backoff
        assert agent.restart_count == 0
        assert delays == []
        assert agent._failure_times == []
        runs = json.loads(log.read_text())
        assert runs[0]["DS_TPU_NUM_PROCS"] == "8"
        assert runs[0]["DS_TPU_ELASTIC_PREV_WORLD"] is None
        assert runs[1] == {"DS_TPU_NUM_PROCS": "4",
                           "DS_TPU_ELASTIC_PREV_WORLD": "8",
                           "DS_TPU_LAST_VALID_TAG": tag}

    def test_crash_loop_still_fires_at_stable_world(self, tmp_path):
        """After the topology settles, repeated failures are a crash loop
        again — the shrink exemption must not disable the guard; the
        stable-world relaunch also clears the PREV_WORLD export."""
        from deepspeed_tpu.elasticity.elastic_agent import (
            CrashLoopError, DSElasticAgent)

        log = tmp_path / "env_log"
        worker = _write_worker(tmp_path, f"""
            import json, os, sys
            p = {str(log)!r}
            runs = json.load(open(p)) if os.path.exists(p) else []
            runs.append(os.environ.get("DS_TPU_ELASTIC_PREV_WORLD"))
            json.dump(runs, open(p, "w"))
            sys.exit(9)
        """)
        worlds = [8] + [4] * 20
        agent = DSElasticAgent([sys.executable, worker], {},
                               discover_world=lambda: worlds.pop(0),
                               max_restarts=10, backoff_s=0.0, jitter=0.0,
                               crash_loop_window_s=60.0,
                               crash_loop_threshold=3)
        with pytest.raises(CrashLoopError, match="crash loop detected"):
            agent.run()
        # the 8->4 failure did not count; three STABLE-world failures did
        assert agent.restart_count == 2
        runs = json.loads(log.read_text())
        # launch 2 expects the reshard; stable relaunches 3..4 do not
        assert runs == [None, "8", None, None]


# ---------------------------------------------------------------------------
# chaos: kill mid-epoch on N devices, resume on N' (make chaos scenarios)
# ---------------------------------------------------------------------------
class _RecordingIter:
    def __init__(self, it):
        self.it = it
        self.token_batches = []

    def __iter__(self):
        return self

    def __next__(self):
        batch = next(self.it)
        self.token_batches.append(np.asarray(batch["input_ids"]).copy())
        return batch


def _doc_dataset(n_docs=256, vocab=97, seed=4):
    rng = np.random.RandomState(seed)
    return [{"input_ids": rng.randint(1, vocab, size=rng.randint(3, 15))
             .astype(np.int32)} for _ in range(n_docs)]


@pytest.mark.slow
class TestChaosElasticResume:
    """``make chaos`` scenarios: the loss trajectory after an N -> N'
    resume matches the uninterrupted N-device run and the dataloader
    stream is token-identical."""

    @pytest.mark.parametrize("n_old,micro_old,n_new,micro_new",
                             [(8, 1, 4, 2), (4, 2, 8, 1)],
                             ids=["shrink-8to4", "grow-4to8"])
    def test_resume_matches_uninterrupted(self, eight_devices, tmp_path,
                                          n_old, micro_old, n_new,
                                          micro_new):
        from deepspeed_tpu.models.transformer_lm import GPT

        def build(n, micro):
            # micro is per-device: global batch stays micro * n == 8
            cfg = {
                "train_micro_batch_size_per_gpu": micro,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3},
                "data_pipeline": {"enabled": True, "seq_length": 32,
                                  "prefetch": False, "seed": 17},
                "steps_per_print": 10 ** 9,
            }
            topo = MeshTopology(fsdp=n, devices=jax.devices()[:n])
            engine, _, loader, _ = deepspeed_tpu.initialize(
                model=GPT(tiny_gpt_config(n_positions=32)), config=cfg,
                training_data=_doc_dataset(), topology=topo)
            return engine, iter(loader)

        # the "uninterrupted" run IS the first engine: saving does not
        # perturb it, and abandoning it after 6 steps is the kill
        engine, it = build(n_old, micro_old)
        pre_losses = [float(engine.train_batch(it)) for _ in range(3)]
        engine.save_checkpoint(str(tmp_path))
        rec = _RecordingIter(it)
        ref_losses = [float(engine.train_batch(rec)) for _ in range(3)]
        assert all(np.isfinite(pre_losses + ref_losses))

        engine2, it2 = build(n_new, micro_new)
        engine2.train_batch(it2)  # materialize state templates for load
        events = []
        telemetry_bus.subscribe(events.append)
        try:
            tag, _ = engine2.load_checkpoint(str(tmp_path))
        finally:
            telemetry_bus.unsubscribe(events.append)
        assert tag is not None
        assert engine2.ft_stats["ckpt_reshards"] == 1

        reshards = [e for e in events if e["kind"] == "elastic.reshard"]
        assert len(reshards) == 1
        ev = reshards[0]
        assert ev["saved_world"] == n_old
        assert ev["current_world"] == n_new
        assert f"world_size {n_old} -> {n_new}" in ev["mismatches"]
        for phase in ("detect_s", "load_s", "verify_params_s",
                      "place_params_s", "total_s"):
            assert ev[phase] >= 0.0

        rec2 = _RecordingIter(it2)
        res_losses = [float(engine2.train_batch(rec2)) for _ in range(3)]
        # token-identical stream: the resumed run consumes exactly the
        # batches the uninterrupted run would have consumed
        assert len(rec.token_batches) == len(rec2.token_batches)
        for a, b in zip(rec.token_batches, rec2.token_batches):
            np.testing.assert_array_equal(a, b)
        # loss trajectory within sentinel tolerance: same data, bitwise
        # resharded params/optimizer — only reduction order differs
        np.testing.assert_allclose(res_losses, ref_losses,
                                   rtol=2e-3, atol=1e-5)
