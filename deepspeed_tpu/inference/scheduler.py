"""Continuous-batching serving loop over the KV-cache decode path.

``InferenceEngine.generate`` serves ONE fixed batch start-to-finish: every
sequence waits for the slowest, and a finished lane burns decode FLOPs as
padding until lockstep termination. Production serving (vLLM-style
continuous batching; the reference's DeepSpeed-FastGen/MII serving layer)
instead keeps a fixed-shape decode batch hot and swaps *sequences* through
its lanes:

* the decode step is ONE jitted ``[slots, 1]`` program, compiled once —
  admissions and evictions never change its shape, so the hot loop never
  recompiles (the CUDA-graph-replay discipline, applied to scheduling);
* a finished sequence's lane is freed immediately and refilled from the
  pending queue: admission runs an EXACT chunked prefill on a ``[1, Lp]``
  batch (engine.prefill_chunk_spans — block-aligned passes keep every
  chunk's window ring-resident) and splices the resulting cache into the
  lane's cache rows with ``dynamic_update_slice`` — possible because the
  model's decode caches carry PER-ROW clocks (``cache_index[B]``,
  ``slot_pos[B, S]``), so one lane's time axis resets without touching its
  neighbors;
* completion is per-sequence (EOS or per-request max tokens), not
  lockstep, and every emitted token fires a streaming callback;
* the decode loop runs ONE STEP AHEAD of the host: step n+1 is dispatched
  from the token vector step n left on the device, and only then are
  step n's tokens read, emitted, journalled and counted, while the device
  computes. A lane's end is therefore seen one step late: the token that
  step n+1 computed for a sequence that ended at step n is dropped (no
  callback, no journal record, no count) and its cache rows are garbage
  that the lane's next admission overwrites. An admission is handed to
  the device whole and waited for as little: prefill, the first token
  into the device's token vector, splice, for every free lane (no more
  than ``ADMISSIONS_IN_FLIGHT`` unread at once), then the decode step
  with the new lanes in it, and only then the host reads the step in
  flight and after it the first tokens, in admission order, so
  the device goes from one admission's splice to the next one's prefill
  and on to the step without waiting for the host. A request that ends
  at its first token is seen as late as any other: one dropped row, the
  lane refilled an iteration later. Speculative decoding keeps the
  synchronous order, because its next input is the host's acceptance
  test.

Free lanes keep decoding garbage tokens — attention is row-independent and
the masked softmax is NaN-safe, so a garbage lane costs FLOPs but never
contaminates a neighbor; its next admission overwrites every cache row it
touched. The recurrent state a model's mixers keep (a hybrid block's
Mamba-2 state and convolution tail, models/mamba2.py; a retention block's
state and normaliser, which are ALL its lanes hold: models/
power_retention.py) are leaves of the same cache. What a lane keeps is
declared once by the model (``GPTConfig.cache_leaves``) and read from
there by the one module that knows a lane cache's layout (inference/
lane_cache.py); nothing here names a leaf. A finished lane's state goes on
absorbing garbage tokens, is read by nobody, and is overwritten whole by
the splice of the lane's next admission. A state cannot be truncated to a
prefix, so such a model refuses speculative decoding and the prefix cache
(``RecurrentStateError``).

Prompts are LEFT-padded to a ``prompt_bucket`` multiple to bound prefill
compile count (bucket is a multiple of the layout block for ring models,
so whole-block shifts preserve window visibility exactly; rotary positions
are relative, ALiBi shifts are row-constant under softmax, and wpe reads
the per-row semantic ``position`` counter — the same left-padding argument
as ``generate``'s ragged path). Caveat, shared with that path: BSLongformer
leading-global slots are PHYSICAL positions, so left-padding shifts real
tokens out of the global region — serve those layouts through
``generate``, or with bucket == prompt length.
"""

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.engine import (
    continuation_chunk_spans,
    prefill_chunk_spans,
    probe_length,
    programs_scope_table,
)
from deepspeed_tpu.inference.lane_cache import (
    PLAN_FIELDS,
    LaneClocks,
    LaneLayout,
    LanesAtExit,
)
from deepspeed_tpu.models.transformer_lm import (
    GPTConfig,
    decode_attention_block,
)
from deepspeed_tpu.moe.experts import expert_matrices
from deepspeed_tpu.ops.sparse_attention.sparse_attention_utils import (
    ring_engaged,
)
from deepspeed_tpu.parallel.mesh import set_default_topology
from deepspeed_tpu.telemetry.builds import build_log
from deepspeed_tpu.telemetry.scopes import SCOPE_SAMPLE, DispatchedProgram
from deepspeed_tpu.telemetry.spans import (
    SERVE_ADMIT,
    SERVE_DECODE_READ,
    SERVE_DECODE_STEP,
    SERVE_DELIVER,
    SERVE_EMIT,
    SERVE_FIRST_TOKEN_READ,
    SERVE_ITERATION,
    SERVE_PREFILL,
    SERVE_SPLICE,
    SERVE_STATS,
    span,
)

# the names of the scheduler's own programs as a profiler trace has them
# (its ``XLA Modules`` events) and as ``program_scopes()`` keys them
PROGRAM_SPLICE = "jit_splice"

# what a token after a request's first is emitted under: no span of its own
_NO_SPAN = contextlib.nullcontext()

# The plain loop's admissions whose first token the host has not read yet,
# at most: a prefill's ``[1, ...]`` lane cache is allocated when the prefill
# is dispatched and freed when its splice has RUN, so every admission in
# flight holds one (0.2 GB at 1.3B and 1,024 positions), and a run's first
# iteration has every lane free. Four covers what steady traffic admits in
# one iteration; beyond it the oldest first token is read first, which waits
# for that prefill while the others keep the device busy.
ADMISSIONS_IN_FLIGHT = 4


class AdmissionRejected(RuntimeError):
    """Base for 429-style rejections; carries the request id (or None when
    rejected before one was issued) and a machine-readable reason."""

    def __init__(self, message: str, reason: str = "rejected"):
        super().__init__(message)
        self.reason = reason


class QueueFullError(AdmissionRejected):
    """submit() hit the scheduler's ``max_pending`` bound."""

    def __init__(self, message: str):
        super().__init__(message, reason="queue_full")


class RequestShedError(AdmissionRejected):
    """The admission controller shed this request to hold its SLO."""

    def __init__(self, message: str, reason: str = "slo_shed"):
        super().__init__(message, reason=reason)


class DeadlineExceededError(AdmissionRejected):
    """The request's deadline expired before it could be served."""

    def __init__(self, message: str):
        super().__init__(message, reason="deadline")


class DrainingError(AdmissionRejected):
    """Admission is closed: the scheduler is draining (SIGTERM)."""

    def __init__(self, message: str):
        super().__init__(message, reason="draining")


@dataclass
class Request:
    """One sequence to serve: prompt token ids plus completion rules."""
    prompt: Sequence[int]
    max_new_tokens: int = 32
    eos_token_id: Optional[int] = None
    # called as callback(request_id, token_id, done) per emitted token
    stream_callback: Optional[Callable[[int, int, bool], None]] = None
    request_id: Optional[int] = None
    # absolute time.monotonic() by which the FIRST token must be on its
    # way; an expired request is shed from the queue, never a lane
    t_deadline: Optional[float] = None
    # failover replay: tokens this request already emitted on a replica
    # that died. Admission re-prefills prompt + replay_tokens (prompt at
    # its original bucket, then continuation_chunk_spans over the
    # emitted region — identical pad offset and chunk geometry to the
    # uninterrupted run) and decoding continues under the ORIGINAL
    # max_new_tokens budget. Greedy decode is a pure function of
    # (weights, tokens-so-far), so the continuation is token-identical.
    replay_tokens: Optional[List[int]] = None
    # disaggregated serving hand-off (serving/disagg.py): a prefill
    # replica already ran this prompt's exact chunked prefill, and
    # admission splices the handed ``(first_token, [1, ...] cache)``
    # into a lane instead of prefilling locally. The producer must have
    # used the SAME prompt_bucket — the cache bakes in the pad offset.
    kv_handoff: Optional[Any] = None


@dataclass
class Completion:
    """Result + latency telemetry for one served request."""
    request_id: int
    tokens: List[int]
    prompt_len: int
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first_token: float = 0.0
    t_done: float = 0.0

    @property
    def ttft_s(self) -> float:
        """Time-to-first-token from submission (includes queue wait)."""
        return self.t_first_token - self.t_submit

    @property
    def per_token_s(self) -> float:
        """Mean inter-token latency after the first token."""
        n = len(self.tokens)
        if n <= 1:
            return 0.0
        return (self.t_done - self.t_first_token) / (n - 1)


@dataclass
class _Lane:
    req: Request
    comp: Completion
    emitted: int = 0


@dataclass
class ServingStats:
    completions: List[Completion] = field(default_factory=list)
    wall_s: float = 0.0
    decode_steps: int = 0
    # decode steps dispatched before the step before them was read
    decode_steps_ahead: int = 0
    # tokens a step computed for a lane whose request had ended by the
    # time the host read them (a lane's end is seen one step late)
    decode_tokens_discarded: int = 0
    # first tokens read after the decode step of their admission's
    # iteration was dispatched (the plain loop's every admission)
    first_tokens_behind_step: int = 0
    # over the plain loop's decode steps, the sum of each step's
    # ``kv_blocks_read_share`` (``LaneClocks.step``)
    kv_blocks_read_share_sum: float = 0.0

    def summary(self) -> Dict[str, Any]:
        ttfts = sorted(c.ttft_s for c in self.completions)
        pts = [c.per_token_s for c in self.completions if len(c.tokens) > 1]
        total_tokens = sum(len(c.tokens) for c in self.completions)

        def pct(xs, q):
            if not xs:
                return 0.0
            return float(xs[min(len(xs) - 1, int(q * len(xs)))])

        return {
            "num_sequences": len(self.completions),
            "total_generated_tokens": total_tokens,
            "wall_s": self.wall_s,
            "aggregate_tokens_per_s": (total_tokens / self.wall_s
                                       if self.wall_s > 0 else 0.0),
            "ttft_s": {"mean": float(np.mean(ttfts)) if ttfts else 0.0,
                       "p50": pct(ttfts, 0.50), "p95": pct(ttfts, 0.95)},
            "per_token_ms": {
                "mean": float(np.mean(pts)) * 1e3 if pts else 0.0,
                "p50": pct(sorted(pts), 0.50) * 1e3,
                "p95": pct(sorted(pts), 0.95) * 1e3},
            "decode_steps": self.decode_steps,
            "decode_steps_ahead": self.decode_steps_ahead,
            "decode_tokens_discarded": self.decode_tokens_discarded,
            "first_tokens_behind_step": self.first_tokens_behind_step,
            "kv_blocks_read_share": (
                self.kv_blocks_read_share_sum / self.decode_steps
                if self.decode_steps else 0.0),
        }


class ContinuousBatchingScheduler:
    """Slot-based continuous batching over an ``InferenceEngine``.

    ``submit()`` requests (before or during ``run()`` — a stream callback
    may submit follow-ups), then ``run()`` drives admissions, the jitted
    fixed-shape decode loop, per-sequence completion, and streaming
    callbacks until the queue drains. Returns completions in finish order.
    """

    def __init__(self, engine, slots: int = 8,
                 prompt_bucket: Optional[int] = None,
                 temperature: float = 0.0,
                 eos_token_id: Optional[int] = None,
                 max_pending: Optional[int] = None,
                 prefix_cache=None,
                 admission_controller=None,
                 reject_callback: Optional[Callable] = None,
                 journal=None,
                 health_provider=None,
                 draft_engine=None,
                 spec_k: int = 0):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if max_pending is not None and max_pending < 1:
            raise ValueError(f"max_pending must be >= 1, got {max_pending}")
        self.engine = engine
        self.slots = int(slots)
        self.temperature = float(temperature)
        self.eos_token_id = eos_token_id
        # front-door hooks (serving/ wires these; all duck-typed so the
        # scheduler keeps zero imports from the serving package):
        #   max_pending — bound on the submit queue, SLO controller or not
        #   prefix_cache — serving.PrefixCache (lookup/promotion_target/
        #       insert/release protocol used in _admit_prefill)
        #   admission_controller — .decide(queue_depth, slots) ->
        #       (admit, reason), consulted per submit()
        #   reject_callback(request_id, reason) — the 429 hook, invoked
        #       before the typed error is raised
        #   journal — serving.RequestJournal (record_submit/record_token/
        #       record_shed), the exact-failover flight record
        #   health_provider — .states() dict folded into frontdoor_stats
        #       and the per-iteration serve.stats event
        #   draft_engine + spec_k — draft-model speculative decoding: the
        #       draft proposes spec_k greedy tokens per lane per step, ONE
        #       batched target forward verifies them, and the per-row
        #       cache clocks rewind past the first mismatch. Exact vs
        #       sequential greedy by construction (every emitted token is
        #       a target-argmax given its prefix), so it composes with
        #       failover replay and the prefix cache unchanged.
        self.max_pending = None if max_pending is None else int(max_pending)
        self.prefix_cache = prefix_cache
        self.admission_controller = admission_controller
        self.reject_callback = reject_callback
        self.journal = journal
        self.health_provider = health_provider
        self.draft_engine = draft_engine
        self.spec_k = int(spec_k)
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.shed_count = 0
        self.deadline_shed_count = 0
        self._draining = False
        self.drain_reason: Optional[str] = None
        self._lanes_active = 0
        self._mcfg = getattr(engine.module, "config", None)

        self._ring = ring_engaged(self._mcfg) if self._mcfg is not None \
            else None
        # what a lane keeps on the device, as the model declares it, and
        # what may be done to it: one owner per engine. What this kind of
        # cache cannot serve is refused here, by name, not by a wrong
        # answer later.
        self.lane_cache = LaneLayout(engine, self.slots)
        self.lane_cache.refuse(draft_engine=draft_engine is not None,
                               prefix_cache=prefix_cache is not None)
        self.draft_lane_cache = None if draft_engine is None \
            else LaneLayout(draft_engine, self.slots)
        # the target's programs serve the draft's cache too: they take
        # any cache tree, and one ``jit_splice`` serves both
        self._splice = self.lane_cache.splice
        self._copy_tree = self.lane_cache.copy
        self._rewind = self.lane_cache.rewind
        if prompt_bucket is None:
            prompt_bucket = self._ring[2] if self._ring is not None else 64
        if self._ring is not None and prompt_bucket % self._ring[2] != 0:
            raise ValueError(
                f"prompt_bucket {prompt_bucket} must be a multiple of the "
                f"ring layout block {self._ring[2]}: admission prefill "
                "left-pads to the bucket, and only whole-block shifts "
                "preserve the training window visibility exactly")
        self.prompt_bucket = int(prompt_bucket)

        # hard capacity for models whose decode cannot stream (dense cache
        # or learned positions): prompt + generation must fit n_positions
        self._max_pos = getattr(self._mcfg, "n_positions", None)
        # (a lane whose every per-position leaf is a ring streams; one
        # layer that keeps every position bounds it: ``LaneLayout.streams``)
        self._streaming = ((self._ring is not None
                            or self.lane_cache.streams) and
                           not getattr(self._mcfg, "learned_positions", True))

        # speculative decoding preconditions — checked HERE, not in the
        # hot loop, because every one of them is a config property
        if self.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if (draft_engine is None) != (self.spec_k == 0):
            raise ValueError(
                "speculative decoding needs BOTH a draft_engine and "
                f"spec_k >= 1 (got draft_engine="
                f"{'set' if draft_engine is not None else 'None'}, "
                f"spec_k={spec_k})")
        self._draft_mcfg = None
        self._draft_ring = None
        if draft_engine is not None:
            if self.temperature != 0.0:
                raise ValueError(
                    "speculative decoding here is EXACT-greedy only "
                    "(accepted tokens are target argmaxes); temperature "
                    f"must be 0.0, got {temperature}")
            if self._ring is not None:
                blk = self._ring[2]
                slack = int(getattr(self._mcfg, "kv_cache_slack_blocks",
                                    0) or 0)
                if slack < 1:
                    raise ValueError(
                        "speculative decoding over a ring KV cache needs "
                        "kv_cache_slack_blocks >= 1 on the TARGET model: "
                        "the k+1-column verify pass writes every column "
                        "before attention reads, and without a slack "
                        "block an unaligned pass can evict entries its "
                        "own earlier columns still need "
                        "(ops/sparse_attention ring_storage_len)")
                if self.spec_k > blk:
                    raise ValueError(
                        f"spec_k ({spec_k}) must be <= the ring layout "
                        f"block ({blk}): one slack block makes passes of "
                        "at most `block` tokens exact")
            self._draft_mcfg = getattr(draft_engine.module, "config", None)
            self._draft_ring = (ring_engaged(self._draft_mcfg)
                                if self._draft_mcfg is not None else None)
            if self._draft_ring is not None and \
                    self.prompt_bucket % self._draft_ring[2] != 0:
                raise ValueError(
                    f"prompt_bucket {self.prompt_bucket} must be a "
                    f"multiple of the DRAFT model's ring block "
                    f"({self._draft_ring[2]}): admission prefills the "
                    "draft cache at the same bucket")

        self._pending: deque = deque()
        self._next_id = 0
        self._set_token_fn = None
        self._cache_plan_published = False
        self._clocks: Optional[LaneClocks] = None      # made by each run
        # set to keep what a run that ends with a step in flight leaves on
        # the device (``LanesAtExit``) in ``lanes_at_exit`` until the next
        # run or until the holder drops it: a whole lane cache stays
        # allocated that long, so it is off unless somebody will look
        self.retain_lanes = False
        self.lanes_at_exit: Optional[LanesAtExit] = None

    # ------------------------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int = 32,
               eos_token_id: Optional[int] = None,
               stream_callback: Optional[Callable] = None,
               deadline_s: Optional[float] = None,
               replay_tokens: Optional[Sequence[int]] = None,
               kv_handoff: Optional[Any] = None) -> int:
        """Queue one request; returns its request id.

        Raises ``QueueFullError`` when the queue is at ``max_pending``,
        ``RequestShedError`` when the admission controller sheds,
        ``DeadlineExceededError`` when ``deadline_s`` is already spent,
        and ``DrainingError`` once ``begin_drain`` closed admission —
        all AdmissionRejected, the 429 surface. The reject callback
        fires first, so a server can answer the client before the raise
        unwinds.

        ``deadline_s`` is a relative first-token budget: a request still
        queued when it expires is shed from the queue (never occupying a
        lane), with a ``serve.deadline_shed`` event. ``replay_tokens``
        marks a failover replay (see ``Request.replay_tokens``): the
        stream callback fires only for NEW tokens — the client already
        holds the replayed prefix.

        ``kv_handoff`` is the disaggregated-prefill hand-off (see
        ``Request.kv_handoff``): admission splices the handed cache
        instead of prefilling locally. Mutually exclusive with
        ``replay_tokens`` — a replayed request must re-run its emitted
        region, which the hand-off by definition has not seen.
        """
        prompt = list(int(t) for t in prompt)
        if not prompt:
            raise ValueError("an empty prompt cannot seed generation")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if kv_handoff is not None and replay_tokens:
            raise ValueError(
                "kv_handoff and replay_tokens are mutually exclusive: a "
                "failover replay must re-run its emitted tokens, which a "
                "prefill hand-off has not seen")
        replay = [int(t) for t in replay_tokens] if replay_tokens else []
        if replay and len(replay) >= max_new_tokens:
            raise ValueError(
                f"replay of {len(replay)} tokens exhausts the "
                f"max_new_tokens budget ({max_new_tokens}) — the request "
                "already finished; do not replay it")
        depth = len(self._pending)
        if self._draining:
            self._reject(DrainingError(
                "admission is closed: the scheduler is draining "
                f"({self.drain_reason})"), depth)
        if deadline_s is not None and deadline_s <= 0:
            from deepspeed_tpu.telemetry.bus import KIND_SERVE_DEADLINE_SHED

            self._reject(DeadlineExceededError(
                f"deadline_s={deadline_s} already expired at submit"),
                depth, kind=KIND_SERVE_DEADLINE_SHED)
        if self.max_pending is not None and depth >= self.max_pending:
            self._reject(QueueFullError(
                f"admission queue is full ({depth}/{self.max_pending} "
                "pending); retry after the scheduler drains"), depth)
        if self.admission_controller is not None:
            admit, reason = self.admission_controller.decide(
                queue_depth=depth, slots=self.slots)
            if not admit:
                self._reject(RequestShedError(
                    f"request shed by admission control: {reason}"), depth)
        bucketed = self._bucketed_len(len(prompt))
        if self._max_pos is not None and not self._streaming and \
                bucketed + max_new_tokens > self._max_pos:
            raise ValueError(
                f"bucketed prompt ({bucketed}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the KV cache capacity "
                f"(n_positions={self._max_pos})")
        rid = self._next_id
        self._next_id += 1
        now = time.monotonic()
        req = Request(prompt=prompt, max_new_tokens=int(max_new_tokens),
                      eos_token_id=(self.eos_token_id if eos_token_id is None
                                    else eos_token_id),
                      stream_callback=stream_callback, request_id=rid,
                      t_deadline=(None if deadline_s is None
                                  else now + float(deadline_s)),
                      replay_tokens=replay or None,
                      kv_handoff=kv_handoff)
        if self.journal is not None:
            self.journal.record_submit(
                rid, prompt, req.max_new_tokens,
                deadline=req.t_deadline, emitted=replay)
        self._pending.append((req, now))
        return rid

    def _reject(self, exc: AdmissionRejected, depth: int, kind=None):
        """Publish serve.shed (or ``kind``), fire the 429 callback,
        raise ``exc``."""
        from deepspeed_tpu.telemetry.bus import KIND_SERVE_SHED, publish

        self.shed_count += 1
        if isinstance(exc, DeadlineExceededError):
            self.deadline_shed_count += 1
        publish(kind or KIND_SERVE_SHED, severity="warning",
                reason=exc.reason, queue_depth=depth,
                shed_total=self.shed_count)
        if self.reject_callback is not None:
            try:
                self.reject_callback(None, exc.reason)
            except Exception:  # the callback must not mask the rejection
                pass
        raise exc

    def begin_drain(self, reason: str = "drain") -> None:
        """Close admission (SIGTERM posture). Safe from a signal
        handler: sets a flag, publishes one ``serve.drain``, touches no
        jax state. ``run()`` finishes the lanes already decoding and
        returns with the queue intact for journal hand-off."""
        if self._draining:
            return
        from deepspeed_tpu.telemetry.bus import KIND_SERVE_DRAIN, publish

        self._draining = True
        self.drain_reason = str(reason)
        publish(KIND_SERVE_DRAIN, severity="warning", phase="begin",
                reason=self.drain_reason, queue_depth=len(self._pending),
                lanes_active=self._lanes_active)

    @property
    def draining(self) -> bool:
        return self._draining

    def _shed_expired(self, req: Request, t_submit: float) -> None:
        """Drop one queue entry whose deadline passed before a lane
        freed up — it never occupies a lane or runs a prefill."""
        from deepspeed_tpu.telemetry.bus import (
            KIND_SERVE_DEADLINE_SHED,
            publish,
        )

        now = time.monotonic()
        self.deadline_shed_count += 1
        publish(KIND_SERVE_DEADLINE_SHED, severity="warning",
                request_id=req.request_id, waited_s=now - t_submit,
                late_s=now - req.t_deadline,
                queue_depth=len(self._pending),
                deadline_shed_total=self.deadline_shed_count)
        if self.journal is not None:
            self.journal.record_shed(req.request_id)
        if self.reject_callback is not None:
            try:
                self.reject_callback(req.request_id, "deadline")
            except Exception:
                pass

    def _bucketed_len(self, n: int) -> int:
        b = self.prompt_bucket
        return ((n + b - 1) // b) * b

    # ------------------------------------------------------------------
    def _ensure_compiled(self):
        eng = self.engine
        set_default_topology(eng.topology)
        # param shapes don't depend on B or T, so one [1, T_probe] probe does
        if eng._params is None or not hasattr(eng, "_param_shardings"):
            eng._materialize(jnp.zeros(
                (1, probe_length(self._mcfg, self.prompt_bucket)),
                jnp.int32))
        if eng._prefill_fn is None:
            eng._build_decode_fns()
        # one tracing for all this scheduler's prompt buckets where the
        # model allows it
        eng.plan_prefill(self.prompt_bucket, self._max_pos or 0)
        if not self._cache_plan_published:
            from deepspeed_tpu.telemetry.bus import (
                KIND_SERVE_CACHE_PLAN,
                publish,
            )

            self._cache_plan_published = True
            kv = self.lane_cache.geometry()
            block = self._decode_attention_block()
            publish(KIND_SERVE_CACHE_PLAN, slots=self.slots,
                    decode_attention="none" if block == 0
                    else "einsum" if block is None else "live_blocks",
                    decode_attention_block=block or 0,
                    expert_matrices=self._expert_matrices(),
                    leaf_layers=kv["leaf_layers"],
                    **{k: kv[k] for k in PLAN_FIELDS if k in kv})
        de = self.draft_engine
        if de is None:
            return
        # the draft engine likewise, probed at ITS layout's length
        if de._params is None or not hasattr(de, "_param_shardings"):
            de._materialize(jnp.zeros(
                (1, probe_length(self._draft_mcfg, self.prompt_bucket)),
                jnp.int32))
        if de._prefill_fn is None:
            de._build_decode_fns()

    def _decode_attention_block(self):
        """Positions a block of the plain loop's decode attention, None
        where it reads every position (the model decides: models/
        transformer_lm.py ``decode_attention_block``; a module without a
        ``GPTConfig`` has no such kernel), 0 where a lane's cache holds no
        keys and values at all (a model whose token mixer is not attention
        declares no leaf of kind "position")."""
        if not isinstance(self._mcfg, GPTConfig):
            return None
        if not self._mcfg.position_leaves:
            return 0
        return decode_attention_block(self._mcfg)

    def _expert_matrices(self) -> str:
        """How the decode step's expert layers read their matrices:
        ``"in_place"`` in the stacked parameters, ``"slice"`` a layer's own
        tensor, ``"none"`` for a model without experts (the model decides,
        by the rule it traces under: moe/experts.py ``expert_matrices``; a
        step sorts ``slots * moe_top_k`` rows a layer)."""
        if not isinstance(self._mcfg, GPTConfig):
            return "none"
        return expert_matrices(self._mcfg,
                               self.slots * self._mcfg.moe_top_k)

    def _empty_cache(self, eng=None):
        """The target engine's empty lane cache (``LaneLayout.empty``), or
        the draft engine's when handed it."""
        return (self.lane_cache if eng is None or eng is self.engine
                else self.draft_lane_cache).empty()

    def _set_token(self, tok_dev, lane, token):
        """Write an admitted lane's first token into the ``[slots]`` token
        vector that the decode steps hand from one to the next on the
        device. ``token`` is the ``[1]`` array the admission's sampling
        left on the device, so this is dispatched behind the prefill and
        waits for no host read (a hand-off's token is a host int). Jitted
        once, lane traced."""
        if self._set_token_fn is None:

            def set_token(vec, lane_idx, tok):
                with jax.named_scope(SCOPE_SAMPLE):
                    return jax.lax.dynamic_update_slice(
                        vec, tok.astype(vec.dtype), (lane_idx,))

            self._set_token_fn = DispatchedProgram(
                jax.jit(set_token), key=lambda a: a[0].shape)
        if not isinstance(token, jax.Array):
            token = np.asarray([token], np.int32)
        return self._set_token_fn(tok_dev, np.int32(lane), token)

    def program_scopes(self) -> Dict[str, Dict[str, Optional[str]]]:
        """``{program_name: {hlo_instruction_name: op_name_path}}`` of every
        program this scheduler has dispatched: the engine's prefill and
        decode programs (and the draft engine's), and its own splice,
        first-token, copy and rewind programs (telemetry/scopes.py; see
        ``InferenceEngine.program_scopes``). After the window, never
        inside it."""
        programs = list(self.engine.step_programs())
        if self.draft_engine is not None:
            programs += self.draft_engine.step_programs()
        programs += self.lane_cache.programs()
        if self._set_token_fn is not None:
            programs.append(self._set_token_fn)
        return programs_scope_table(programs, self.lane_cache.leaves)

    def program_builds(self, before: Optional[float] = None):
        """What this process built so far, by JAX's own account: the
        engine's ``program_builds()`` (the log is the process's, so the
        splice, first-token, copy and rewind programs are in it like the
        engine's)."""
        return build_log.snapshot(before)

    def _draft_prefill(self, ids: np.ndarray, mask: np.ndarray,
                       req: Request):
        """Chunked prefill of the DRAFT model's cache for one admission
        (logits discarded — the draft only proposes from decode steps).
        Replays run the same continuation spans so a failed-over
        request's draft clock lands where its target clock does."""
        de = self.draft_engine
        _, sub = de._chunked_prefill(jnp.asarray(ids), jnp.asarray(mask))
        if req.replay_tokens:
            Lp = ids.shape[1]
            E = len(req.replay_tokens)
            rep_ids = np.asarray([req.replay_tokens], np.int32)
            rep_mask = np.ones((1, E), bool)
            for s, e in continuation_chunk_spans(self._draft_mcfg,
                                                 Lp, Lp + E):
                _, sub = de._prefill_more_fn(
                    de._params, jnp.asarray(rep_ids[:, s - Lp:e - Lp]),
                    jnp.asarray(rep_mask[:, s - Lp:e - Lp]), sub)
        return sub

    def _admit_prefill(self, req: Request, Lp: int):
        """Exact (chunked when needed) prefill of one prompt on a
        ``[1, Lp]`` batch, dispatched and not waited for; returns (first
        sampled token: a device array, or the hand-off's host int; sub
        cache; draft sub cache — None without speculative decoding)."""
        eng = self.engine
        ids = np.zeros((1, Lp), np.int32)
        mask = np.zeros((1, Lp), bool)
        ids[0, Lp - len(req.prompt):] = req.prompt
        mask[0, Lp - len(req.prompt):] = True
        # the draft cache is ALWAYS built locally — a hand-off carries
        # only the target cache (the draft is a decode-side accessory)
        draft_sub = (self._draft_prefill(ids, mask, req)
                     if self.draft_engine is not None else None)
        if req.kv_handoff is not None:
            # disaggregated hand-off: a prefill replica already ran this
            # prompt's exact chunked prefill at the same bucket. Copy
            # before splicing — the producer may fan the same entry out
            # to several decode lanes, and _splice donates.
            first_tok, sub_cache = req.kv_handoff
            return first_tok, self._copy_tree(sub_cache), draft_sub
        if self.prefix_cache is not None:
            logits_last, sub_cache = self._prefix_prefill(
                ids, mask, req.request_id)
        else:
            logits_last, sub_cache = eng._chunked_prefill(
                jnp.asarray(ids), jnp.asarray(mask))
        if req.replay_tokens:
            # failover replay: re-run the emitted tokens as a chunked
            # CONTINUATION prefill starting at the original bucket Lp —
            # identical pad offset and chunk geometry to the
            # uninterrupted run, so the cache state (and every logit
            # after it) is bit-identical to the run that died
            E = len(req.replay_tokens)
            rep_ids = np.asarray([req.replay_tokens], np.int32)
            rep_mask = np.ones((1, E), bool)
            for s, e in continuation_chunk_spans(self._mcfg, Lp, Lp + E):
                logits_last, sub_cache = eng._prefill_more_fn(
                    eng._params, jnp.asarray(rep_ids[:, s - Lp:e - Lp]),
                    jnp.asarray(rep_mask[:, s - Lp:e - Lp]), sub_cache)
        eng._rng, sub = jax.random.split(eng._rng)
        with jax.named_scope(SCOPE_SAMPLE):
            if self.temperature > 0:
                tok = jax.random.categorical(
                    sub, logits_last / self.temperature, axis=-1)
            else:
                tok = jnp.argmax(logits_last, axis=-1)
        return tok, sub_cache, draft_sub

    def _prefill_passes(self, Lp: int, req: Request) -> int:
        """Prefill programs the cold path dispatches for this bucket (a
        prefix-cache hit dispatches fewer; a hand-off none)."""
        if req.kv_handoff is not None:
            return 0
        spans = prefill_chunk_spans(self._mcfg, Lp)
        return 1 if spans is None else len(spans)

    def _prefix_prefill(self, ids: np.ndarray, mask: np.ndarray,
                        request_id):
        """Admission prefill through the shared-prefix cache.

        The cache key is the PADDED column prefix (pads encoded as -1):
        decode positions advance for pad columns too and rotary phases are
        baked into cached keys at write time, so a cached prefix is only
        numerics-compatible with the cold path at the same padded offset.
        Two prompts therefore share an entry iff they agree on both the
        leading tokens AND ``(-len) % prompt_bucket``.

        On a hit: copy the entry's leaves (continuation donates) and resume
        the chunked prefill from the cached length via
        ``continuation_chunk_spans`` — spans that never cross a layout
        block keep every chunk exact, same argument as the cold path. On a
        promotion (``promotion_target``): prefill ``[0, c)`` cold, snapshot
        a copy into the cache, continue to ``Lp``. With no hit and no
        promotion this is byte-for-byte the cold ``_chunked_prefill``.
        """
        eng = self.engine
        pc = self.prefix_cache
        Lp = ids.shape[1]
        cols = tuple(int(t) if m else -1
                     for t, m in zip(ids[0], mask[0]))
        # limit Lp-1 keeps >= 1 column for the continuation pass, so the
        # final span always regenerates the last-token logits
        entry = pc.lookup(cols, limit=Lp - 1, request_id=request_id)
        start = 0
        cache = None
        if entry is not None:
            start = entry.length
            cache = self._copy_tree(entry.cache)
            pc.release(entry)
        target = pc.promotion_target(cols, limit=Lp - 1, have=start)

        logits_last = None
        if cache is None:
            cold_end = target if target is not None else Lp
            logits_last, cache = eng._chunked_prefill(
                jnp.asarray(ids[:, :cold_end]),
                jnp.asarray(mask[:, :cold_end]))
            start = cold_end
        if target is not None and target > start:
            for s, e in continuation_chunk_spans(self._mcfg, start, target):
                logits_last, cache = eng._prefill_more_fn(
                    eng._params, jnp.asarray(ids[:, s:e]),
                    jnp.asarray(mask[:, s:e]), cache)
            start = target
        if target is not None:
            pc.insert(cols[:target], self._copy_tree(cache),
                      request_id=request_id)
        if start < Lp:
            for s, e in continuation_chunk_spans(self._mcfg, start, Lp):
                logits_last, cache = eng._prefill_more_fn(
                    eng._params, jnp.asarray(ids[:, s:e]),
                    jnp.asarray(mask[:, s:e]), cache)
        return logits_last, cache

    def kv_cache_stats(self, hbm_override_gib: Optional[float] = None
                       ) -> Dict[str, Any]:
        """KV-cache byte accounting from the memoized leaf geometry.

        ``resident_bytes`` is what THIS cache actually stores (int8
        payloads plus their f32 scale sidebands when kv_cache_dtype is
        "int8"); ``unquantized_bytes`` is the compute-dtype twin — the
        same geometry with ``cached_key``/``cached_value`` at the model
        dtype and no sidebands. Their ratio is the honest compression
        factor, and with a known HBM size (telemetry/memory.hbm_bytes)
        ``lanes_at_hbm_budget`` says how many decode lanes of THIS
        per-lane footprint fit the part — the capacity number the
        disaggregated-serving sizing tables are built from. The leaves a
        model declares as recurrent state are counted apart from keys,
        values and clocks, each where its declaration says (``state_bytes``,
        ``conv_bytes``, and ``norm_bytes``: the part of ``state_bytes``
        that is a normaliser; ``kv_bytes`` is the rest; each also
        ``_per_lane``). A cache without keys and values has the clocks
        alone in ``kv_bytes``. Which leaves are per position is the
        model's to say (``GPTConfig.position_leaves``); latent attention's
        (a latent and a rotary key a position, no heads) are in
        ``kv_bytes`` and, apart, ``latent_bytes_per_lane``."""
        from deepspeed_tpu.telemetry.memory import hbm_bytes

        out = dict(self.lane_cache.geometry())
        hbm, source = hbm_bytes(override_gib=hbm_override_gib)
        if hbm:
            out["hbm_bytes"] = int(hbm)
            out["hbm_source"] = source
            per_lane = out["bytes_per_lane"]
            out["lanes_at_hbm_budget"] = (int(hbm // per_lane)
                                          if per_lane else 0)
        return out

    def frontdoor_stats(self) -> Dict[str, Any]:
        """Shed + prefix-cache + health counters for benches/servers."""
        out: Dict[str, Any] = {"shed": self.shed_count,
                               "deadline_shed": self.deadline_shed_count,
                               "pending": len(self._pending),
                               "lanes_active": self._lanes_active,
                               "draining": self._draining}
        if self.prefix_cache is not None:
            out["prefix"] = self.prefix_cache.stats()
        if self.admission_controller is not None and \
                hasattr(self.admission_controller, "stats"):
            out["admission"] = self.admission_controller.stats()
        if self.journal is not None and hasattr(self.journal, "stats"):
            out["journal"] = self.journal.stats()
        if self.health_provider is not None and \
                hasattr(self.health_provider, "states"):
            out["health"] = dict(self.health_provider.states())
        if self.draft_engine is not None:
            out["spec"] = {
                "k": self.spec_k,
                "proposed": self.spec_proposed,
                "accepted": self.spec_accepted,
                "accept_rate": (self.spec_accepted / self.spec_proposed
                                if self.spec_proposed else 0.0)}
        # gated on the geometry already being traced (run() does it):
        # frontdoor_stats must stay safe on fake/unmaterialized engines
        if self._cache_plan_published:
            out["kv_cache"] = self.kv_cache_stats()
        return out

    def _publish_stats(self, stats: "ServingStats", lanes,
                       kv: Dict[str, Any]) -> None:
        """One ``serve.stats`` snapshot per scheduler iteration — queue
        depth, lane occupancy, shed counters, prefix hit-rate and fleet
        health, so dashboards see front-door pressure without polling.
        ``kv`` is ``kv_cache_stats()``, which cannot change during a
        ``run()`` and is computed once by it."""
        from deepspeed_tpu.telemetry.bus import KIND_SERVE_STATS, publish

        self._lanes_active = sum(1 for l in lanes if l is not None)
        payload: Dict[str, Any] = {
            "queue_depth": len(self._pending),
            "lanes_active": self._lanes_active,
            "shed": self.shed_count,
            "deadline_shed": self.deadline_shed_count,
            "decode_steps": stats.decode_steps,
            "draining": self._draining,
        }
        if self._clocks is not None:
            payload["live_positions"] = self._clocks.live_positions(lanes)
            if self.lane_cache.window is not None:
                # what the window layers of a mixed stack read of them
                payload["live_window_positions"] = \
                    self._clocks.live_positions(lanes, self.lane_cache.window)
            if self.lane_cache.chosen is not None:
                # and what the layers that choose attend over of them
                payload["live_chosen_positions"] = \
                    self._clocks.live_positions(lanes, self.lane_cache.chosen)
        if self.prefix_cache is not None:
            payload["prefix_hit_rate"] = \
                self.prefix_cache.stats().get("hit_rate", 0.0)
        if self.health_provider is not None and \
                hasattr(self.health_provider, "states"):
            payload["health"] = dict(self.health_provider.states())
        publish(KIND_SERVE_STATS, **payload,
                kv_resident_bytes=kv["resident_bytes"],
                kv_unquantized_bytes=kv["unquantized_bytes"])

    # ------------------------------------------------------------------
    def run(self, poll_fn: Optional[Callable[[], None]] = None
            ) -> ServingStats:
        """Serve the queue to completion; returns stats + completions.

        ``poll_fn`` (optional) is called once per loop iteration, between
        decode steps — the hook a fleet replica uses to pump its control
        pipe, so failover replays submitted mid-run land in free lanes
        without waiting for this run to finish.

        While draining (``begin_drain``): no new admissions, lanes
        already decoding finish normally, and the loop exits with the
        pending queue INTACT — the caller hands those (and nothing else)
        off via the journal.

        The plain decode loop runs one step ahead of the host (module
        docstring): ``stream_callback`` fires for step n's tokens while
        the device computes step n+1. Whether ``run`` returns or raises
        (a ``poll_fn`` or a callback may), it first waits for the step in
        flight, whose result holds the donated cache; that step's tokens
        are nobody's. With ``retain_lanes`` set, that cache and the
        requests its live lanes held stay in ``lanes_at_exit``
        (``LanesAtExit``) for whoever inspects or hands over what the run
        left.

        The loop itself is ``_run``, one Python frame further down, and
        has to stay there: entered directly from the caller, the same
        loop cost the benchmark's ramp (eleven prefill programs traced
        and lowered under this frame) 2.8 s more host time on the v5e's
        host, in this tree and in PR 31's alike (PERF.md, section 6,
        PR 32). Rehearse ``setup_s`` on the chip after moving it.
        """
        unread: list = []   # at most one decode step, dispatched, not read
        self.lanes_at_exit = None
        try:
            return self._run(poll_fn, unread)
        finally:
            for step in unread:
                jax.block_until_ready(step[0])
            if self.retain_lanes and unread:
                self.lanes_at_exit = self.lane_cache.at_exit(
                    *unread[-1][1:])

    def _run(self, poll_fn, unread) -> ServingStats:
        self._ensure_compiled()
        eng = self.engine
        stats = ServingStats()
        lanes: List[Optional[_Lane]] = [None] * self.slots
        use_spec = self.draft_engine is not None
        # The plain loop's token vector lives on the device: a decode step
        # takes the vector the step before it returned, with each
        # admission's first token written into its lane. It and the rng
        # start on the sharding those programs hand back, so that the
        # first call of each is the one specialisation every later call
        # hits. The speculative loop's next input is the host's
        # acceptance test, so its vector stays a host array.
        where = eng.topology.replicated()
        tok = np.zeros((self.slots,), np.int32)
        tok_dev = None if use_spec else jax.device_put(tok, where)
        cache = self._empty_cache()
        self._clocks = LaneClocks(stats, self.slots, self._max_pos,
                                  self._decode_attention_block())
        eng._rng, rng = jax.random.split(eng._rng)
        rng = jax.device_put(rng, where)
        temp = jnp.float32(self.temperature)
        draft_cache = draft_rng = None
        if use_spec:
            de = self.draft_engine
            draft_cache = self._empty_cache(de)
            de._rng, draft_rng = jax.random.split(de._rng)
        t_run0 = time.monotonic()
        # the KV geometry cannot change during a run: read it once for
        # every serve.stats event (kv_cache_stats also asks for the HBM size)
        kv = self.kv_cache_stats()
        # the plain loop's admissions of one iteration: their open spans,
        # and ``(lane_no, lane, first token on the device)`` of those whose
        # first token the host has not read yet, in admission order
        admits = contextlib.ExitStack()
        admitted: list = []

        from deepspeed_tpu.telemetry.bus import (
            KIND_SERVE_ADMIT,
            KIND_SERVE_EVICT,
            KIND_SERVE_FIRST_TOKEN,
            KIND_SERVE_SPEC_ACCEPT,
            publish,
        )

        def finish(lane_no: int, lane: _Lane):
            lane.comp.t_done = time.monotonic()
            stats.completions.append(lane.comp)
            lanes[lane_no] = None
            publish(KIND_SERVE_EVICT, request_id=lane.req.request_id,
                    lane=lane_no, tokens=lane.emitted,
                    queue_depth=len(self._pending))

        def emit(lane_no: int, lane: _Lane, token: int) -> None:
            """Record one token and hand it to the journal and the stream
            callback; a sequence that is done frees its lane. A request's
            first token is a span (it ends the request's time to first
            token on the trace's clock); the others are their step's
            ``ds:serve.deliver``."""
            with span(SERVE_EMIT, request_id=lane.req.request_id) \
                    if lane.comp.t_first_token == 0.0 else _NO_SPAN:
                now = time.monotonic()
                lane.comp.tokens.append(token)
                lane.emitted += 1
                if lane.comp.t_first_token == 0.0:
                    lane.comp.t_first_token = now
                    # replays do not republish serve.first_token: the
                    # client saw its first token on the replica that died,
                    # and a replay-time sample would bias the admission
                    # p95 window
                    if lane.req.replay_tokens is None:
                        publish(KIND_SERVE_FIRST_TOKEN,
                                request_id=lane.req.request_id,
                                lane=lane_no,
                                ttft_s=now - lane.comp.t_submit)
                done = (lane.emitted >= lane.req.max_new_tokens
                        or (lane.req.eos_token_id is not None
                            and token == lane.req.eos_token_id))
                if self.journal is not None:
                    self.journal.record_token(
                        lane.req.request_id, token, done=done)
                if lane.req.stream_callback is not None:
                    lane.req.stream_callback(
                        lane.req.request_id, token, done)
                if done:
                    finish(lane_no, lane)

        def first_token(behind_step: int) -> None:
            """Read the oldest unread first token, which waits for what is
            left of its prefill, and emit it."""
            lane_no, lane, token = admitted.pop(0)
            with span(SERVE_FIRST_TOKEN_READ, behind_step=behind_step,
                      request_id=lane.req.request_id):
                token = int(np.asarray(token).reshape(-1)[0])
            stats.first_tokens_behind_step += behind_step
            emit(lane_no, lane, token)

        def deliver(step) -> None:
            """Read a dispatched decode step's tokens, ``(its [slots]
            token vector, the lanes as they stood at its dispatch)``, and
            hand each to the lane it was computed for. A lane whose
            request has ended since (it may already hold another) drops
            its token: no callback, no journal record, no count."""
            step_tok, owners = np.asarray(step[0]), step[1]
            with span(SERVE_DELIVER):
                for lane_no, lane in enumerate(owners):
                    if lane is None:
                        continue
                    if lanes[lane_no] is lane:
                        emit(lane_no, lane, int(step_tok[lane_no]))
                    else:
                        stats.decode_tokens_discarded += 1

        while True:
            if poll_fn is not None:
                poll_fn()
            active = any(l is not None for l in lanes)
            if self._draining:
                if not active:
                    break  # queue left intact for journal hand-off
            elif not (self._pending or active):
                break
            with span(SERVE_ITERATION, decode_steps=stats.decode_steps), \
                    admits:
                # admissions: fill every free lane from the queue. An
                # expired deadline sheds here — before the prefill, so a
                # doomed request never occupies a lane. Draining admits
                # none. The plain loop hands the device an admission's
                # whole work (prefill, the first token into the token
                # vector, splice) and waits for none of it: the token is
                # read and emitted further down, once the decode step is
                # dispatched behind it, and the admission's span stays
                # open until then, around the time its prefill runs on the
                # device (``admits`` closes the spans in reverse, so those
                # of one iteration nest, also when the loop raises). So a
                # request that ends AT its first token (max_new 1, or the
                # token is EOS) is seen after that step left with its
                # lane: the step computes one row for nobody
                # (``decode_tokens_discarded``) and the lane is refilled
                # in the next iteration: one admission a lane and
                # iteration. No more than ``ADMISSIONS_IN_FLIGHT`` wait
                # for their first token at once. The speculative loop's
                # next input is the host's, so it reads the token here
                # and refills such a lane at once.
                for lane_no in range(
                        self.slots if not self._draining else 0):
                    while lanes[lane_no] is None and self._pending:
                        req, t_submit = self._pending.popleft()
                        if req.t_deadline is not None and \
                                time.monotonic() > req.t_deadline:
                            self._shed_expired(req, t_submit)
                            continue
                        if len(admitted) == ADMISSIONS_IN_FLIGHT:
                            # (a run's first iteration, mostly.) The step
                            # in flight ended before the prefills: its
                            # tokens go first, as the device made them
                            if unread:
                                deliver(unread.pop())
                            first_token(0)
                        replayed = len(req.replay_tokens or ())
                        comp = Completion(request_id=req.request_id,
                                          tokens=list(req.replay_tokens or ()),
                                          prompt_len=len(req.prompt),
                                          t_submit=t_submit)
                        comp.t_admit = time.monotonic()
                        bucket = self._bucketed_len(len(req.prompt))
                        queue_wait_s = comp.t_admit - t_submit
                        # inline, not a helper closure: the same admission
                        # through a nested function cost the ramp 1.2 s of
                        # 12 on the chip (PERF.md, PR 24); the attributes
                        # that take a call come first, which keeps this
                        # frame's stack at 15 slots: the ramp's host time
                        # moves with the frame's size too (ROADMAP D15)
                        admits.enter_context(span(
                            SERVE_ADMIT,
                            queue_wait_us=int(queue_wait_s * 1e6),
                            queue_depth=len(self._pending),
                            prompt_len=len(req.prompt),
                            request_id=req.request_id, lane=lane_no,
                            bucket=bucket))
                        publish(KIND_SERVE_ADMIT,
                                request_id=req.request_id, lane=lane_no,
                                prompt_len=len(req.prompt),
                                bucket=bucket, replayed=replayed,
                                queue_wait_s=queue_wait_s,
                                queue_depth=len(self._pending))
                        with span(SERVE_PREFILL,
                                  chunks=self._prefill_passes(bucket, req)):
                            first_tok, sub_cache, draft_sub = \
                                self._admit_prefill(req, bucket)
                            if not use_spec:
                                tok_dev = self._set_token(
                                    tok_dev, lane_no, first_tok)
                        if use_spec:
                            with span(SERVE_FIRST_TOKEN_READ, behind_step=0,
                                      request_id=req.request_id):
                                first_tok = int(
                                    np.asarray(first_tok).reshape(-1)[0])
                        with span(SERVE_SPLICE):
                            cache = self._splice(cache, sub_cache, lane_no)
                            if use_spec:
                                draft_cache = self._splice(
                                    draft_cache, draft_sub, lane_no)
                                tok[lane_no] = first_tok
                        self._clocks.admit(lane_no, bucket,
                                           len(req.prompt), replayed)
                        lane = _Lane(req=req, comp=comp, emitted=replayed)
                        lanes[lane_no] = lane
                        if use_spec:
                            emit(lane_no, lane, first_tok)
                            admits.close()
                        else:
                            admitted.append((lane_no, lane, first_tok))

                with span(SERVE_STATS):
                    self._publish_stats(stats, lanes, kv)
                if not any(l is not None for l in lanes):
                    # nothing was admitted (shed, or draining), or the
                    # speculative loop's admissions all ended at token 1
                    continue

                if use_spec:
                    # speculative step: the draft proposes k greedy tokens
                    # per lane (k sequential cheap steps), the target
                    # verifies them in ONE [slots, k+1] forward, and both
                    # caches rewind past each lane's first mismatch.
                    # m_eff = min(m, k-1): no bonus token — accepting all
                    # k would need the draft's k-th proposal in ITS cache,
                    # which the proposal loop never wrote. Every emitted
                    # token is a target argmax given the emitted prefix,
                    # so the stream is exactly sequential greedy.
                    k = self.spec_k
                    de = self.draft_engine
                    with span(SERVE_DECODE_STEP,
                              lanes_active=self._lanes_active):
                        snap = self._copy_tree(cache)
                        draft_snap = self._copy_tree(draft_cache)
                        props, _, draft_cache, draft_rng = de._decode_k_fn(
                            de._params, jnp.asarray(tok), draft_cache,
                            draft_rng, jnp.float32(0.0), k)
                        cols = jnp.concatenate(
                            [jnp.asarray(tok)[:, None], props], axis=1)
                        g, cache = eng._verify_greedy_fn(
                            eng._params, cols, cache)
                        stats.decode_steps += 1
                        with span(SERVE_DECODE_READ):
                            g_np = np.asarray(g)
                            props_np = np.asarray(props)
                        matches = props_np == g_np[:, :k]
                        m = np.where(matches.all(axis=1), k,
                                     matches.argmin(axis=1))
                        m_eff = np.minimum(m, k - 1).astype(np.int64)
                        cache = self._rewind(
                            snap, cache,
                            jnp.asarray((k - m_eff).astype(np.int32)))
                        draft_cache = self._rewind(
                            draft_snap, draft_cache,
                            jnp.asarray((k - 1 - m_eff).astype(np.int32)))
                    live = [ln for ln in range(self.slots)
                            if lanes[ln] is not None]
                    self.spec_proposed += k * len(live)
                    accepted_now = int(sum(int(m_eff[ln]) for ln in live))
                    self.spec_accepted += accepted_now
                    publish(KIND_SERVE_SPEC_ACCEPT, k=k, lanes=len(live),
                            proposed=k * len(live), accepted=accepted_now,
                            proposed_total=self.spec_proposed,
                            accepted_total=self.spec_accepted)
                    with span(SERVE_DELIVER):
                        for lane_no in live:
                            lane = lanes[lane_no]
                            for j in range(int(m_eff[lane_no]) + 1):
                                emit(lane_no, lane, int(g_np[lane_no, j]))
                                if lanes[lane_no] is None:
                                    break
                    tok = g_np[np.arange(self.slots), m_eff] \
                        .astype(np.int32).copy()
                else:
                    # ONE fixed-shape decode step for all lanes (garbage
                    # lanes included — row-independent attention keeps
                    # them harmless), dispatched from the token vector the
                    # step before it left on the device and BEFORE that
                    # step is read: its read, emits, callbacks, the stats
                    # and the next poll run while this one computes. A
                    # lane that turns out to have ended at the step before
                    # has one garbage row more, as an empty lane has every
                    # step: the row update is indexed [layer, lane, slot]
                    # with mode="drop" (models/transformer_lm.py), so a
                    # lane writes its own rows only and a slot past its
                    # last position nowhere, and the lane's next splice
                    # overwrites every row it holds.
                    # An admission's lane is in this step: its first token
                    # is in the token vector and its splice ahead of the
                    # step in the device's queue, so the device goes from
                    # prefill to splice to step while the host gets here.
                    ahead = len(unread)
                    with span(SERVE_DECODE_STEP,
                              lanes_active=self._lanes_active, ahead=ahead,
                              kv_blocks_read_share=self._clocks.step()):
                        _, tok_dev, cache, rng = eng._decode_k_fn(
                            eng._params, tok_dev, cache, rng, temp, 1)
                        stats.decode_steps += 1
                        stats.decode_steps_ahead += ahead
                        unread.append((tok_dev, list(lanes), cache))
                        if ahead:
                            with span(SERVE_DECODE_READ):
                                step = unread.pop(0)
                                step = (np.asarray(step[0]), step[1])
                    if ahead:
                        deliver(step)
                    # the unread first tokens of this iteration's
                    # admissions, in order: each read returns when its
                    # prefill ends, with the splices and the step queued
                    # behind it. They follow the step that was in flight, which
                    # the device ended before the prefills and which holds
                    # no token of theirs, and go before the step just
                    # dispatched is ever delivered, so every stream stays
                    # in order. A hand-off's token is a host int and waits
                    # for nothing.
                    while admitted:
                        first_token(1)
                    admits.close()
                    if stats.decode_steps == 1:
                        # the first step of a run is read at once: where
                        # the decode program is compiled or loaded, it is
                        # in that dispatch, and the tokens of a host that
                        # has been away that long are due before the next
                        # poll
                        deliver(unread.pop())

        if unread:
            # dispatched before the host read that the last lanes had
            # ended: nobody's tokens
            deliver(unread.pop())
        stats.wall_s = time.monotonic() - t_run0
        return stats
