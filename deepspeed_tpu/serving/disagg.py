"""Disaggregated serving: prefill/decode split with exact KV hand-off.

Prefill and decode want different machines. Chunked prompt prefill is
compute-bound (big matmuls over whole blocks — MXU work), while the
continuous-batching token loop is memory-bound (one [slots, 1] step per
token, HBM-bandwidth-limited on the KV cache). A replica serving both
interleaves them on one set of cores, so every admission prefill stalls
every decoding lane's next token — the TTFT/ITL coupling that
disaggregated serving architectures (DistServe, Splitwise, the
reference's DeepSpeed-FastGen ancestry) exist to break.

This module splits the two phases over the machinery the scheduler
already has, without weakening any exactness guarantee:

* :class:`PrefillWorker` — a prefill-role replica: runs the SAME exact
  chunked prefill the scheduler's admission path runs (block-aligned
  spans via ``engine._chunked_prefill``, identical left-pad bucketing),
  and emits a :class:`KVHandoff` — the first sampled token plus the
  ``[1, ...]`` decode cache, sized in bytes as it would cross a wire.
* :class:`KVHandoff` — the transfer artifact. Exactness argument: the
  scheduler's ``kv_handoff`` admission splices this cache into a lane
  with the SAME jitted ``_splice`` used for local prefills, and greedy
  decode is a pure function of (weights, cache, last token) — so a
  decode replica continuing from a handed cache is token-identical to
  one that prefilled locally (tested in test_serving_disagg.py).
* :class:`DisaggServer` — in-process composition of N prefill workers
  and one decode scheduler: routes each prompt to a prefill worker
  (hash-affine via ``FleetCoordinator.place_prefill`` when a coordinator
  is wired, round-robin otherwise), accounts every hand-off as a
  ``serve.kv_transfer`` event, and submits the request to the decode
  scheduler with the hand-off attached. The decode scheduler may run
  int8 KV lanes and speculative decoding — both compose with hand-off
  because the handed cache is spliced through the same leaf protocol.

The int8 KV cache (``kv_cache_dtype="int8"`` on the model config /
``{"kv_cache": "int8"}`` in the inference config) earns its keep twice
here: resident lane bytes shrink ~2x vs bf16 (~3.9x vs fp32) so one
decode replica holds proportionally more lanes under the same HBM
budget (:func:`lane_kv_bytes` computes the capacity table), and the
hand-off payload — the bytes ``serve.kv_transfer`` meters — shrinks by
the same factor. NOTE: hand-off requires producer and consumer to agree
on ``prompt_bucket`` AND cache dtype; :class:`DisaggServer` validates
the bucket and leaves dtype agreement to the leaf-shape check in
``_splice`` (mismatched trees fail loudly at splice time).
"""

import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.engine import probe_length
from deepspeed_tpu.inference.lane_cache import LaneLayout
from deepspeed_tpu.ops.sparse_attention.sparse_attention_utils import (
    ring_engaged,
)
from deepspeed_tpu.telemetry.bus import KIND_SERVE_KV_TRANSFER, publish

__all__ = ["KVHandoff", "PrefillWorker", "DisaggServer", "lane_kv_bytes",
           "tree_nbytes"]


def tree_nbytes(tree) -> int:
    """Total payload bytes of a pytree of arrays — what a cross-host
    KV hand-off actually ships (int8 leaves count 1 byte/elt, their f32
    scale sidebands count too: the wire cost is honest, not idealized)."""
    return int(sum(
        leaf.size * jnp.dtype(leaf.dtype).itemsize
        for leaf in jax.tree.leaves(tree) if hasattr(leaf, "dtype")))


def lane_kv_bytes(model, slots: int = 1) -> Dict[str, int]:
    """Per-lane decode KV-cache footprint for ``model``: the scheduler's
    own accounting (inference/lane_cache.py ``LaneLayout.geometry``) over
    pure ``eval_shape``, no parameters materialized, so sizing a 70B-scale
    capacity table costs microseconds.

    Returns ``resident_bytes`` (what this cache stores: int8 payloads +
    f32 scale sidebands under ``kv_cache_dtype="int8"``) and
    ``unquantized_bytes`` (the compute-dtype twin) for ONE lane — the
    lanes-per-HBM capacity tables in docs/performance.md divide the HBM
    budget by these.
    """
    kv = LaneLayout(model, slots).geometry()
    return {"resident_bytes": kv["resident_bytes"] // slots,
            "unquantized_bytes": kv["unquantized_bytes"] // slots}


@dataclass
class KVHandoff:
    """One prefill replica's output for one prompt: everything a decode
    replica needs to continue EXACTLY (greedy decode is a pure function
    of weights + cache + last token)."""
    request_id: Any
    first_token: int
    cache: Any            # [1, ...] decode cache pytree
    nbytes: int           # payload size as shipped (tree_nbytes)
    prompt_bucket: int    # the producer's bucket — consumer must match
    prefill_s: float = 0.0

    def as_submit_arg(self):
        """The ``kv_handoff=`` value for ``scheduler.submit``."""
        return (self.first_token, self.cache)


class PrefillWorker:
    """A prefill-role replica over one engine: exact chunked prompt
    prefill -> :class:`KVHandoff`. Temperature is pinned greedy — the
    hand-off's exactness story is the greedy purity argument, and the
    first token must match what the decode replica would have sampled."""

    def __init__(self, engine, prompt_bucket: Optional[int] = None,
                 replica: int = 0):
        self.engine = engine
        self.replica = int(replica)
        self._mcfg = getattr(engine.module, "config", None)
        ring = ring_engaged(self._mcfg) if self._mcfg is not None else None
        if prompt_bucket is None:
            prompt_bucket = ring[2] if ring is not None else 64
        if ring is not None and prompt_bucket % ring[2] != 0:
            raise ValueError(
                f"prompt_bucket {prompt_bucket} must be a multiple of "
                f"the ring layout block {ring[2]} (same rule as the "
                "decode scheduler — the cache bakes in the pad offset)")
        self.prompt_bucket = int(prompt_bucket)
        self.prefills = 0
        self.kv_bytes = 0

    def _bucketed(self, n: int) -> int:
        b = self.prompt_bucket
        return ((n + b - 1) // b) * b

    def _ensure_compiled(self):
        eng = self.engine
        if eng._params is None or not hasattr(eng, "_param_shardings"):
            eng._materialize(jnp.zeros(
                (1, probe_length(self._mcfg, self.prompt_bucket)),
                jnp.int32))
        if eng._prefill_fn is None:
            eng._build_decode_fns()

    def prefill(self, prompt: Sequence[int], request_id=None) -> KVHandoff:
        """Run one prompt's exact chunked prefill; returns the hand-off."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("an empty prompt cannot seed generation")
        self._ensure_compiled()
        eng = self.engine
        t0 = time.monotonic()
        Lp = self._bucketed(len(prompt))
        ids = np.zeros((1, Lp), np.int32)
        mask = np.zeros((1, Lp), bool)
        ids[0, Lp - len(prompt):] = prompt
        mask[0, Lp - len(prompt):] = True
        logits_last, cache = eng._chunked_prefill(
            jnp.asarray(ids), jnp.asarray(mask))
        first = int(np.asarray(jnp.argmax(logits_last, axis=-1))[0])
        nbytes = tree_nbytes(cache)
        self.prefills += 1
        self.kv_bytes += nbytes
        return KVHandoff(request_id=request_id, first_token=first,
                         cache=cache, nbytes=nbytes,
                         prompt_bucket=self.prompt_bucket,
                         prefill_s=time.monotonic() - t0)

    def stats(self) -> Dict[str, Any]:
        return {"replica": self.replica, "prefills": self.prefills,
                "kv_bytes": self.kv_bytes}


class DisaggServer:
    """In-process prefill/decode disaggregation: N prefill workers feed
    one decode scheduler through :class:`KVHandoff`s.

    ``submit`` runs the prefill SYNCHRONOUSLY on the chosen worker (the
    in-process analogue of a prefill tier answering an RPC), accounts
    the hand-off (``serve.kv_transfer``), and queues the request on the
    decode scheduler with the cache attached — the decode loop never
    runs a prompt prefill, so its inter-token latency stops absorbing
    admission stalls. ``run`` drives the decode scheduler.

    ``coordinator`` (optional, a role-aware ``FleetCoordinator``) takes
    over prefill placement (hash-affine) and transfer accounting;
    without one, placement is round-robin and events publish directly.
    """

    def __init__(self, scheduler, prefill_workers: Sequence[PrefillWorker],
                 coordinator=None):
        if not prefill_workers:
            raise ValueError("DisaggServer needs >= 1 PrefillWorker")
        self.scheduler = scheduler
        self.workers = list(prefill_workers)
        self.coordinator = coordinator
        for w in self.workers:
            if w.prompt_bucket != scheduler.prompt_bucket:
                raise ValueError(
                    f"prefill worker bucket {w.prompt_bucket} != decode "
                    f"scheduler bucket {scheduler.prompt_bucket}: the "
                    "handed cache bakes in the pad offset, so producer "
                    "and consumer must bucket identically")
        self._rr = 0
        self.handoffs = 0
        self.handoff_bytes = 0

    def _pick_worker(self, prompt) -> int:
        if self.coordinator is not None:
            # in-process workers have no transport to heartbeat through,
            # and the coordinator's silence schedule would mark them
            # DOWN during a long prefill compile — a worker we can call
            # directly is alive by definition, so vouch for it here
            # (out-of-process replicas still live or die by their pipes)
            for w in self.workers:
                self.coordinator.health.heartbeat(w.replica)
            replica, _how = self.coordinator.place_prefill(prompt)
            for i, w in enumerate(self.workers):
                if w.replica == replica:
                    return i
            raise ValueError(
                f"coordinator placed prefill on replica {replica}, but "
                f"no PrefillWorker here carries that replica index")
        i = self._rr
        self._rr = (self._rr + 1) % len(self.workers)
        return i

    def submit(self, prompt: Sequence[int], max_new_tokens: int = 32,
               **submit_kw) -> int:
        """Prefill on a worker, hand off, queue on the decode scheduler.
        Returns the decode scheduler's request id."""
        idx = self._pick_worker(prompt)
        worker = self.workers[idx]
        h = worker.prefill(prompt)
        self.handoffs += 1
        self.handoff_bytes += h.nbytes
        rid = self.scheduler.submit(prompt, max_new_tokens=max_new_tokens,
                                    kv_handoff=h.as_submit_arg(),
                                    **submit_kw)
        if self.coordinator is not None:
            self.coordinator.record_kv_transfer(
                rid, from_replica=worker.replica, to_replica=-1,
                nbytes=h.nbytes, transfer_s=h.prefill_s)
        else:
            publish(KIND_SERVE_KV_TRANSFER, request_id=rid,
                    from_replica=worker.replica, to_replica=-1,
                    bytes=h.nbytes, transfers_total=self.handoffs,
                    bytes_total=self.handoff_bytes)
        return rid

    def run(self, poll_fn=None):
        return self.scheduler.run(poll_fn)

    def stats(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "handoffs": self.handoffs,
            "handoff_bytes": self.handoff_bytes,
            "workers": [w.stats() for w in self.workers],
            "frontdoor": self.scheduler.frontdoor_stats(),
        }
        if self.coordinator is not None:
            out["fleet"] = self.coordinator.stats()
        return out
