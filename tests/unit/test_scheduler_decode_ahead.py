"""The plain decode loop runs one step ahead of the host
(``inference/scheduler.py``): step n+1 is dispatched from the token vector
on the device before step n's tokens are read, so a lane's end is seen one
step late. Under greedy decoding every request must still get exactly the
stream a plain synchronous loop gives it, whatever the lanes do meanwhile;
and every serving program must have ONE specialisation per shape from the
first iteration on, which a CPU can count."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import scheduler as scheduler_mod
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.scheduler import ContinuousBatchingScheduler
from deepspeed_tpu.models.transformer_lm import GPT, GPTConfig
from deepspeed_tpu.parallel.mesh import set_default_topology

BUCKET = 8
SLOTS = 16
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# what the speculative case below read at b1e26aa, before the plain loop
# changed: the branch it must not touch
SPEC_DECODE_STEPS_AT_PARENT = 5
_compiled = []      # names of the programs XLA compiled, in order
jax.monitoring.register_event_duration_secs_listener(
    lambda name, secs, **kw: _compiled.append(kw.get("fun_name", "?"))
    if name == COMPILE_EVENT else None)


def _engine(**kw):
    cfg = GPTConfig(vocab_size=128, n_positions=256, n_embd=32, n_layer=2,
                    n_head=4, dtype=jnp.float32, scan_layers=True, **kw)
    return InferenceEngine(GPT(cfg), {"dtype": "fp32"}, seed=0)


@pytest.fixture(scope="module")
def eng():
    return _engine()


@pytest.fixture(scope="module")
def sched(eng):
    """One scheduler for the cases that leave it reusable: a ``run``
    starts from an empty cache and no lanes every time."""
    return ContinuousBatchingScheduler(eng, slots=SLOTS,
                                       prompt_bucket=BUCKET)


_reference_memo = {}


def reference(eng, prompt, max_new, eos=None):
    """The stream of one request from a plain synchronous loop over a
    batch of one: prefill the left-padded prompt, take the argmax, then
    one decode step per token, each read before the next is dispatched,
    until ``max_new`` tokens or ``eos``."""
    key = (tuple(prompt), max_new)
    if key not in _reference_memo:
        set_default_topology(eng.topology)
        if eng._prefill_fn is None:
            eng._materialize(jnp.zeros((1, BUCKET), jnp.int32))
            eng._build_decode_fns()
        lp = -(-len(prompt) // BUCKET) * BUCKET
        ids = np.zeros((1, lp), np.int32)
        mask = np.zeros((1, lp), bool)
        ids[0, lp - len(prompt):] = prompt
        mask[0, lp - len(prompt):] = True
        logits, cache = eng._chunked_prefill(jnp.asarray(ids),
                                             jnp.asarray(mask))
        tok = np.asarray(jnp.argmax(logits, axis=-1)).astype(np.int32)
        out = [int(tok[0])]
        rng = jax.random.PRNGKey(0)
        while len(out) < max_new:
            toks, _, cache, rng = eng._decode_k_fn(
                eng._params, jnp.asarray(tok), cache, rng,
                jnp.float32(0.0), 1)
            tok = np.asarray(toks[:, 0]).astype(np.int32)
            out.append(int(tok[0]))
        _reference_memo[key] = out
    out = _reference_memo[key]
    return out[:out.index(eos) + 1] if eos in out else out


def _prompts(n, seed, lo=3, hi=30):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 128, size=int(k)).tolist()
            for k in rng.integers(lo, hi, size=n)]


class Recorder:
    """A stream callback that keeps every request's tokens in order and
    refuses a token after ``done`` or for a request it was not told of."""

    def __init__(self):
        self.tokens, self.done = {}, set()

    def __call__(self, rid, token, done):
        assert rid not in self.done, f"token for {rid} after done"
        self.tokens.setdefault(rid, []).append(int(token))
        if done:
            self.done.add(rid)


def _check_streams(eng, stats, rec, wants):
    """``wants``: request id -> (prompt, max_new, eos)."""
    got = {c.request_id: c.tokens for c in stats.completions}
    assert sorted(got) == sorted(wants)
    for rid, (prompt, max_new, eos) in wants.items():
        assert got[rid] == reference(eng, prompt, max_new, eos), rid
        assert rec.tokens[rid] == got[rid], rid     # streamed == returned
    assert rec.done == set(wants)


# ---------------------------------------------------------------------------
# (a) sixteen lanes kept full by a resubmitting callback (the closed loop)
# ---------------------------------------------------------------------------
def test_lanes_kept_full_by_a_resubmitting_callback(eng, sched):
    prompts = _prompts(48, seed=1)
    outs = [2 + (i * 5) % 11 for i in range(len(prompts))]
    rec, wants, todo = Recorder(), {}, list(zip(prompts, outs))

    def submit():
        prompt, want = todo.pop(0)
        wants[sched.submit(prompt, max_new_tokens=want,
                           stream_callback=on_token)] = (prompt, want, None)

    def on_token(rid, token, done):
        rec(rid, token, done)
        if done and todo:
            submit()                    # from inside the callback

    for _ in range(SLOTS):
        submit()
    stats = sched.run()
    _check_streams(eng, stats, rec, wants)
    assert len(wants) == len(prompts)
    # the loop engaged: most steps left before the step before was read,
    # and each request that ended beside a live lane cost one dropped token
    assert stats.decode_steps_ahead > stats.decode_steps // 2
    assert 0 < stats.decode_tokens_discarded <= len(prompts) + SLOTS
    assert stats.summary()["decode_steps_ahead"] == stats.decode_steps_ahead


# ---------------------------------------------------------------------------
# (b) lanes thinning to empty, an idle poll_fn, then new arrivals (the open
# loop's ramp and the opening of its window)
# ---------------------------------------------------------------------------
def test_lanes_thin_to_empty_then_new_arrivals(eng, sched):
    first = _prompts(SLOTS, seed=2)
    later = _prompts(10, seed=3)
    rec, wants = Recorder(), {}
    state = {"polls": 0, "idle_polls": 0, "waves": 0}

    def submit(prompt, want):
        wants[sched.submit(prompt, max_new_tokens=want,
                           stream_callback=rec)] = (prompt, want, None)

    def poll():
        state["polls"] += 1
        if len(rec.done) < len(wants):
            return                      # lanes thin, nothing arrives
        # the system is empty: wait here, as the open loop's generator
        # does, then let more requests arrive; twice, then stop
        state["idle_polls"] += 1
        time.sleep(0.002)
        if state["waves"] < 2:
            state["waves"] += 1
            for prompt in later[:3] if state["waves"] == 1 else later[3:]:
                submit(prompt, 3 + len(prompt) % 5)

    for c, prompt in enumerate(first):
        submit(prompt, 2 * (c + 1))     # staggered: 2, 4, ... 32 tokens
    stats = sched.run(poll_fn=poll)
    _check_streams(eng, stats, rec, wants)
    assert len(wants) == SLOTS + len(later) and state["idle_polls"] >= 2
    # poll_fn ran before every iteration: one per decode step at least
    assert state["polls"] >= stats.decode_steps


# ---------------------------------------------------------------------------
# (c) where a request ends: EOS at the first, a middle and the last token,
# one token asked for
# ---------------------------------------------------------------------------
def _with_first_occurrence_at(eng, index, length=9):
    """A prompt whose reference stream of ``length`` tokens holds its
    ``index``-th token nowhere before ``index``."""
    for seed in range(200):
        prompt = _prompts(1, 1000 + seed)[0]
        ref = reference(eng, prompt, length)
        if ref.index(ref[index]) == index:
            return prompt, ref[index]
    raise AssertionError("no such prompt among 200")


@pytest.mark.parametrize("where", ["first", "middle", "last"])
def test_eos_ends_one_lane_and_no_token_follows_it(eng, sched, where):
    length = 9
    index = {"first": 0, "middle": 4, "last": length - 1}[where]
    prompt, eos = _with_first_occurrence_at(eng, index, length)
    others = _prompts(5, seed=3)
    rec, wants = Recorder(), {}
    ending = sched.submit(prompt, max_new_tokens=length, eos_token_id=eos,
                          stream_callback=rec)
    wants[ending] = (prompt, length, eos)
    for p in others:                    # neighbours that outlive it
        wants[sched.submit(p, max_new_tokens=length + 4,
                           stream_callback=rec)] = (p, length + 4, None)
    stats = sched.run()
    _check_streams(eng, stats, rec, wants)
    assert len(rec.tokens[ending]) == index + 1


def test_one_token_asked_for_ends_at_admission(eng, sched):
    prompts = _prompts(SLOTS + 6, seed=4)
    rec, wants = Recorder(), {}
    for i, p in enumerate(prompts):     # every third request wants one
        want = 1 if i % 3 == 0 else 6
        wants[sched.submit(p, max_new_tokens=want,
                           stream_callback=rec)] = (p, want, None)
    stats = sched.run()
    _check_streams(eng, stats, rec, wants)


def test_every_request_ending_at_admission_costs_one_row_of_one_step(
        eng, sched):
    """A first token is read after the iteration's decode step left with
    its lane (PR 53): requests that all end there cost that one step, a
    row each, and no step more."""
    rec, wants = Recorder(), {}
    for p in _prompts(4, seed=5):
        wants[sched.submit(p, max_new_tokens=1,
                           stream_callback=rec)] = (p, 1, None)
    stats = sched.run()
    _check_streams(eng, stats, rec, wants)
    assert stats.decode_steps == 1 and stats.decode_tokens_discarded == 4
    assert stats.first_tokens_behind_step == 4


# ---------------------------------------------------------------------------
# (d) begin_drain with a step in flight; no step outlives run()
# ---------------------------------------------------------------------------
def test_drain_with_a_step_in_flight_finishes_lanes_keeps_queue(eng):
    sched = ContinuousBatchingScheduler(eng, slots=4, prompt_bucket=BUCKET)
    prompts = _prompts(7, seed=6)
    rec, wants = Recorder(), {}

    def on_token(rid, token, done):
        rec(rid, token, done)
        # the third token of request 1 arrives while the next step runs
        if rid == 1 and len(rec.tokens[1]) == 3:
            sched.begin_drain("test")

    rids = [sched.submit(p, max_new_tokens=8, stream_callback=on_token)
            for p in prompts]
    stats = sched.run()
    for rid in rids[:4]:
        wants[rid] = (prompts[rid], 8, None)
    _check_streams(eng, stats, rec, wants)              # lanes finished
    assert [r.request_id for r, _ in sched._pending] == rids[4:]    # intact
    assert stats.decode_steps_ahead > 0


@pytest.mark.parametrize("how", ["returns", "poll_fn_raises",
                                 "callback_raises"])
def test_no_step_is_left_in_flight_when_run_ends(eng, monkeypatch, how):
    """Every token vector a decode step returned is read by the host, or
    the step is waited for before ``run`` hands control back."""
    sched = ContinuousBatchingScheduler(eng, slots=4, prompt_bucket=BUCKET)
    dispatched, waited = [], []
    sched._ensure_compiled()            # the engine builds its programs
    real = eng._decode_k_fn

    def spy(*args):
        out = real(*args)
        dispatched.append(out[1])
        return out

    monkeypatch.setattr(eng, "_decode_k_fn", spy)
    real_wait = jax.block_until_ready
    monkeypatch.setattr(
        scheduler_mod.jax, "block_until_ready",
        lambda x: waited.append(x) or real_wait(x))

    class Stop(Exception):
        pass

    polls = {"n": 0}

    def poll():
        polls["n"] += 1
        if how == "poll_fn_raises" and polls["n"] == 4:
            raise Stop

    def on_token(rid, token, done):
        if how == "callback_raises" and rid == 2 and done:
            raise Stop

    for i, p in enumerate(_prompts(6, seed=7)):
        sched.submit(p, max_new_tokens=3 + i, stream_callback=on_token)
    if how == "returns":
        stats = sched.run(poll_fn=poll)
        # every step was read: the last one, dispatched for lanes that
        # had all ended, on the way out (its tokens are nobody's)
        assert len(stats.completions) == 6 and not waited
        assert len(dispatched) == stats.decode_steps
        assert stats.decode_tokens_discarded >= 1
    else:
        with pytest.raises(Stop):
            sched.run(poll_fn=poll)
        # poll_fn runs with a step dispatched and unread; a callback runs
        # there too, or inside an admission that has just read it
        assert how == "callback_raises" or len(waited) == 1
    assert dispatched and all(w is dispatched[-1] for w in waited)


# ---------------------------------------------------------------------------
# (e) a deadline shed between two steps
# ---------------------------------------------------------------------------
def test_deadline_shed_between_two_steps(eng):
    sched = ContinuousBatchingScheduler(eng, slots=2, prompt_bucket=BUCKET)
    prompts = _prompts(5, seed=8)
    rec, wants, shed = Recorder(), {}, []
    sched.reject_callback = lambda rid, reason: shed.append((rid, reason))

    def on_token(rid, token, done):
        rec(rid, token, done)
        if rid == 0 and len(rec.tokens[0]) == 2:
            # queued behind two full lanes with a budget that is spent
            # before either frees up: shed at the admission that pops it
            doomed = sched.submit(prompts[4], max_new_tokens=4,
                                  stream_callback=rec, deadline_s=1e-4)
            shed.append(("submitted", doomed))
            time.sleep(0.002)

    for i in range(4):
        want = 6 + i
        wants[sched.submit(prompts[i], max_new_tokens=want,
                           stream_callback=on_token)] = (prompts[i], want,
                                                         None)
    stats = sched.run()
    _check_streams(eng, stats, rec, wants)
    doomed = dict(shed)["submitted"]
    assert (doomed, "deadline") in shed and doomed not in rec.tokens
    assert sched.deadline_shed_count == 1


# ---------------------------------------------------------------------------
# (f) journal replay after a kill between a step's dispatch and its read
# ---------------------------------------------------------------------------
class Journal:
    """What the scheduler tells a journal, held to its contract: tokens
    only for requests it knows, none after ``done``."""

    def __init__(self):
        self.entries = {}

    def record_submit(self, rid, prompt, max_new_tokens, deadline=None,
                      emitted=()):
        assert rid not in self.entries
        self.entries[rid] = {"prompt": list(prompt), "max": max_new_tokens,
                             "tokens": list(emitted), "done": False}

    def record_token(self, rid, token, done=False):
        e = self.entries[rid]
        assert not e["done"], f"token recorded for {rid} after done"
        e["tokens"].append(int(token))
        e["done"] = bool(done)

    def record_shed(self, rid):
        self.entries[rid]["done"] = True


@pytest.mark.parametrize("kill_at_poll", [3, 6, 9])
def test_journal_replay_after_a_kill_between_dispatch_and_read(
        eng, kill_at_poll):
    """``poll_fn`` runs with a step dispatched and unread; a kill there
    loses that step's tokens and nothing else, and a replay of what the
    journal holds gives every request its reference stream: no token
    lost, none doubled, none recorded after ``done``."""
    prompts = _prompts(6, seed=9)
    wants = {i: (p, 5 + 2 * i, None) for i, p in enumerate(prompts)}
    journal = Journal()
    first = ContinuousBatchingScheduler(eng, slots=3, prompt_bucket=BUCKET,
                                        journal=journal)

    class Killed(Exception):
        pass

    polls = {"n": 0}

    def poll():
        polls["n"] += 1
        if polls["n"] == kill_at_poll:
            raise Killed

    for i, (p, want, _) in wants.items():
        assert first.submit(p, max_new_tokens=want) == i
    with pytest.raises(Killed):
        first.run(poll_fn=poll)
    held = {i: dict(e, tokens=list(e["tokens"]))
            for i, e in journal.entries.items()}
    assert any(e["tokens"] and not e["done"] for e in held.values())
    for i, e in held.items():           # a prefix of the truth, no more
        ref = reference(eng, *wants[i])
        assert e["tokens"] == ref[:len(e["tokens"])]
        assert e["done"] == (len(e["tokens"]) == len(ref))

    # the survivor replays every open entry under its original budget
    journal2 = Journal()
    second = ContinuousBatchingScheduler(eng, slots=3, prompt_bucket=BUCKET,
                                         journal=journal2)
    rec, new_of = Recorder(), {}
    for i, e in held.items():
        if not e["done"]:
            new_of[second.submit(e["prompt"], max_new_tokens=e["max"],
                                 stream_callback=rec,
                                 replay_tokens=e["tokens"])] = i
    stats = second.run()
    assert sorted(c.request_id for c in stats.completions) == sorted(new_of)
    for c in stats.completions:
        i = new_of[c.request_id]
        ref = reference(eng, *wants[i])
        assert c.tokens == ref                              # none lost
        assert held[i]["tokens"] + rec.tokens.get(c.request_id, []) == ref
        assert journal2.entries[c.request_id]["tokens"] == ref
        assert journal2.entries[c.request_id]["done"]


# ---------------------------------------------------------------------------
# (g) the speculative branch stays synchronous
# ---------------------------------------------------------------------------
def test_speculative_branch_is_synchronous_as_before(eng):
    draft = _engine()                   # same weights: accepts k-1 of k
    sched = ContinuousBatchingScheduler(eng, slots=3, prompt_bucket=BUCKET,
                                        draft_engine=draft, spec_k=4)
    prompts = _prompts(5, seed=10)
    rec, wants = Recorder(), {}
    for i, p in enumerate(prompts):
        wants[sched.submit(p, max_new_tokens=7 + i,
                           stream_callback=rec)] = (p, 7 + i, None)
    stats = sched.run()
    _check_streams(eng, stats, rec, wants)
    # as at the parent (b1e26aa, same requests): 3 accepted drafts and the
    # target's own token a step, lanes refilled as they end
    assert stats.decode_steps == SPEC_DECODE_STEPS_AT_PARENT
    assert stats.decode_steps_ahead == 0
    assert stats.decode_tokens_discarded == 0
    assert sched._set_token_fn is None  # its token vector is the host's



# ---------------------------------------------------------------------------
# the set-up invariant: one specialisation of every program per shape
# ---------------------------------------------------------------------------
def test_one_specialisation_of_every_program_and_no_compile_after_warm_up():
    """Shape (b), then shape (a), on a fresh engine. The first iteration
    admits one request into every lane and every prefill bucket; after it
    and two decode steps nothing compiles, and every jitted program holds
    one executable per shape. A token vector or an rng that is uncommitted
    on the first call and committed from the second (a jitted call's
    results are, when an argument is) would be a second specialisation of
    the decode program: a second lowering and a second load of the largest
    program there is, about a second of every set-up on the chip."""
    eng = _engine()
    ref_eng = _engine()                 # same seed, same weights
    sched = ContinuousBatchingScheduler(eng, slots=SLOTS,
                                        prompt_bucket=BUCKET)
    buckets = (8, 16, 24, 32)
    rng = np.random.default_rng(11)

    def prompt_in(bucket):
        return rng.integers(1, 128,
                            size=int(rng.integers(bucket - 7,
                                                  bucket + 1))).tolist()

    # (b): sixteen lanes, every bucket, staggered ends, then arrivals
    rec, wants = Recorder(), {}
    state = {"polls": 0, "compiled_at_warm": None, "arrived": False}

    def submit(prompt, want, cb):
        wants[sched.submit(prompt, max_new_tokens=want,
                           stream_callback=cb)] = (prompt, want, None)

    def poll():
        state["polls"] += 1
        if state["polls"] == 3:         # admissions + two steps are behind
            state["compiled_at_warm"] = len(_compiled)
        if len(rec.done) == len(wants) and not state["arrived"]:
            state["arrived"] = True
            for b in buckets:
                submit(prompt_in(b), 5, rec)

    for c in range(SLOTS):
        submit(prompt_in(buckets[c % len(buckets)]), 3 * (c + 1), rec)
    before = len(_compiled)
    stats_b = sched.run(poll_fn=poll)
    assert state["arrived"] and stats_b.decode_steps_ahead > 0

    # (a): the same scheduler, lanes kept full from the callback
    todo = [(prompt_in(buckets[i % len(buckets)]), 2 + i % 9)
            for i in range(40)]

    def on_token(rid, token, done):
        rec(rid, token, done)
        if done and todo:
            submit(*todo.pop(0), on_token)

    for _ in range(SLOTS):
        submit(*todo.pop(0), on_token)
    stats_a = sched.run()

    after_warm = _compiled[state["compiled_at_warm"]:]
    assert after_warm == [], after_warm
    in_warm = _compiled[before:state["compiled_at_warm"]]
    assert in_warm.count("jit(decode_k)") == 1, in_warm
    assert in_warm.count("jit(splice)") == 1, in_warm
    assert in_warm.count("jit(set_token)") == 1, in_warm
    assert in_warm.count("jit(prefill)") == len(buckets), in_warm
    assert list(eng._decode_k_fn.avals) == [((SLOTS,), 1)]     # k stays 1
    assert eng._decode_k_fn.fn._cache_size() == 1
    assert sched.lane_cache._splice_fn.fn._cache_size() == 1
    assert sched._set_token_fn.fn._cache_size() == 1
    assert sorted(eng._prefill_fn.avals) == [(1, b) for b in buckets]
    assert eng._prefill_fn.fn._cache_size() == len(buckets)
    assert eng._prefill_more_fn.fn._cache_size() == 0
    # and the streams are the reference's (from the twin engine, so that
    # its batch of one is no specialisation of this engine's programs)
    got = {c.request_id: c.tokens
           for c in stats_b.completions + stats_a.completions}
    assert sorted(got) == sorted(wants)
    for rid, (prompt, want, _) in wants.items():
        assert got[rid] == reference(ref_eng, prompt, want), rid


# ---------------------------------------------------------------------------
# (k) decode attention reads each lane's live blocks only (PR 34): served
# tokens against a cache-free forward, the span's counter against the rule
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def live_blocks_run(tmp_path_factory):
    """One served run, inside a profiler session, of a fresh engine whose
    decode attention takes the block-skipping kernel at 128 positions a
    block (two blocks of the 256-position cache; the rule alone would
    give this small model one): ``(engine, scheduler, the streamed tokens, {request:
    prompt}, every decode step's host clocks, the serve spans, the bus
    events)``. Eight lanes, twenty requests: lanes are re-used, one prompt
    fills its bucket exactly, some lanes cross the block boundary."""
    return _served_in_a_trace(_engine, tmp_path_factory)


def _served_in_a_trace(make_engine, tmp_path_factory):
    import glob
    import os

    from deepspeed_tpu.ops.pallas import decode_attention as da
    from deepspeed_tpu.telemetry import scopes, spans
    from deepspeed_tpu.telemetry.bus import telemetry_bus

    patch = pytest.MonkeyPatch()
    patch.setattr(da, "_BLOCK_BYTES", 1)
    eng = make_engine()
    sched = ContinuousBatchingScheduler(eng, slots=8, prompt_bucket=BUCKET)
    sched.retain_lanes = True
    steps = []
    real_step = scheduler_mod.LaneClocks.step

    def step(self):
        first, clock = self.first.copy(), self.clock.copy()
        share = real_step(self)
        steps.append((first, clock, share))
        return share

    patch.setattr(scheduler_mod.LaneClocks, "step", step)
    prompts = _prompts(19, seed=11, lo=3, hi=150)
    prompts.insert(0, list(range(1, 2 * BUCKET + 1)))    # fills its bucket
    outs = [3 + (i * 7) % 13 for i in range(len(prompts))]
    events = []
    telemetry_bus.subscribe(events.append)
    trace_dir = str(tmp_path_factory.mktemp("live_blocks") / "trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        rec = Recorder()
        ids = {sched.submit(p, max_new_tokens=n, stream_callback=rec): p
               for p, n in zip(prompts, outs)}
        # ended from poll_fn with lanes still decoding, so that what the
        # run left on the device can be held against the host's clocks
        polls = []

        def poll():
            polls.append(1)
            if len(polls) > 14:
                raise KeyboardInterrupt

        with pytest.raises(KeyboardInterrupt):
            sched.run(poll_fn=poll)
    finally:
        jax.profiler.stop_trace()
        telemetry_bus.unsubscribe(events.append)
        patch.undo()
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[-1]
    found = sorted(
        (e.start_ns, e.name[len(spans.SPAN_PREFIX):], dict(e.stats))
        for plane in scopes.load_trace(path).planes
        for line in plane.lines for e in line.events
        if e.name.startswith(spans.SPAN_PREFIX))
    return eng, sched, rec, ids, steps, found, events


def _check_against_a_cache_free_forward(eng, sched, rec, ids):
    model, n_pos = eng.module, eng.module.config.n_positions

    @jax.jit
    def next_token(params, row, length):
        logits = model.apply({"params": params}, row[None],
                             deterministic=True)
        return jnp.argmax(logits[0, length - 1])

    live = {c.request_id for c in sched.lanes_at_exit.live.values()}
    assert live and rec.done, "the run was to end with lanes decoding"
    assert len(rec.tokens) > 8      # more requests than lanes: re-use
    checked = 0
    for rid, tokens in rec.tokens.items():
        row = np.zeros((n_pos,), np.int32)
        prompt = ids[rid]
        row[:len(prompt)] = prompt
        for i, tok in enumerate(tokens):
            n = len(prompt) + i
            assert int(next_token(eng.params, jnp.asarray(row), n)) == tok, \
                (rid, i)
            row[n] = tok
            checked += 1
    assert any(len(ids[rid]) == 2 * BUCKET for rid in rec.done)
    assert checked > 20


def test_served_tokens_equal_a_cache_free_forward(live_blocks_run):
    """Every token of every request is the argmax, at the last position,
    of the model applied with ``decode=False`` to prompt + tokens so far:
    no cache, no kernel, no scheduler. Requests that ended before the run
    was stopped, and the tokens so far of the ones still in their lanes."""
    _check_against_a_cache_free_forward(*live_blocks_run[:4])


def _check_the_spans_counter(eng, sched, steps, found, per_layer):
    """``per_layer``: whether ``cache_index`` and ``valid`` are stacked a
    layer (keys and values) or one a lane (a latent cache)."""
    from deepspeed_tpu.ops.pallas.decode_attention import live_blocks
    from deepspeed_tpu.telemetry import spans

    on_spans = [attrs["kv_blocks_read_share"] for _, name, attrs in found
                if name == spans.SERVE_DECODE_STEP]
    assert len(on_spans) == len(steps) > 10
    n_pos, n_blocks = eng.module.config.n_positions, 2
    for got, (first, clock, share) in zip(on_spans, steps):
        lo, hi = live_blocks(first, np.minimum(clock, n_pos - 1), 128)
        want = (hi - lo + 1).sum() / (n_blocks * len(clock))
        assert share == pytest.approx(want)
        assert float(got) == pytest.approx(want, abs=1e-6)
    shares = {s[2] for s in steps}
    assert min(shares) == 0.5 and max(shares) > 0.5   # some lanes crossed
    # the host's clocks are the device's: what the last step left there
    cache = sched.lanes_at_exit.cache
    leaves = {p[-1].key: np.asarray(v)[0] if per_layer else np.asarray(v)
              for p, v in jax.tree_util.tree_flatten_with_path(cache)[0]}
    first, clock, _ = steps[-1]
    assert (leaves["cache_index"] == clock + 1).all()
    assert (leaves["valid"].argmax(axis=1) == first).all()


def test_the_spans_counter_is_live_blocks_on_the_hosts_clocks(
        live_blocks_run):
    eng, sched, _, _, steps, found, _ = live_blocks_run
    _check_the_spans_counter(eng, sched, steps, found, per_layer=True)


def test_summary_and_cache_plan_say_how_attention_read(live_blocks_run):
    _, sched, _, _, steps, _, events = live_blocks_run
    plans = [e for e in events if e["kind"] == "serve.cache_plan"]
    assert len(plans) == 1
    assert plans[0]["decode_attention"] == "live_blocks"
    assert plans[0]["decode_attention_block"] == 128
    mean = sum(s[2] for s in steps) / len(steps)
    stats = scheduler_mod.ServingStats(
        decode_steps=len(steps),
        kv_blocks_read_share_sum=sum(s[2] for s in steps))
    assert stats.summary()["kv_blocks_read_share"] == pytest.approx(mean)
    assert scheduler_mod.ServingStats().summary()[
        "kv_blocks_read_share"] == 0.0


@pytest.mark.parametrize("kw,path", [
    ({"kv_cache_dtype": "int8"}, "einsum"),
    ({"alibi": True, "learned_positions": False}, "einsum"),
    ({}, "live_blocks")], ids=["int8", "alibi", "dense"])
def test_cache_plan_names_the_path_of_each_layout(kw, path):
    from deepspeed_tpu.telemetry.bus import telemetry_bus

    sched = ContinuousBatchingScheduler(_engine(**kw), slots=2,
                                        prompt_bucket=BUCKET)
    events = []
    telemetry_bus.subscribe(events.append)
    try:
        sched._ensure_compiled()
    finally:
        telemetry_bus.unsubscribe(events.append)
    plan, = [e for e in events if e["kind"] == "serve.cache_plan"]
    assert plan["decode_attention"] == path
    assert (plan["decode_attention_block"] == 256) == (path == "live_blocks")
    clocks = scheduler_mod.LaneClocks(
        scheduler_mod.ServingStats(), 2, 256,
        sched._decode_attention_block())
    assert clocks.step() == 1.0     # one block a lane, or every position


# ---------------------------------------------------------------------------
# (l) latent attention's decode reads each lane's live blocks once (PR 40):
# the same run with the tiny DeepSeek-V2 block, whose one query token goes
# through ``mla_decode_attn`` (ops/pallas/latent_decode_attention.py)
# ---------------------------------------------------------------------------
def _latent_engine():
    import deepspeed_tpu
    from deepseek_v2_tiny import TINY_DEEPSEEK
    from perfbench.builders import deepseek_v2_serve

    section = dict(TINY_DEEPSEEK["serve"], cache_positions=256)
    return deepspeed_tpu.init_inference(
        GPT(deepseek_v2_serve.model_config(TINY_DEEPSEEK, section)),
        dtype="fp32", seed=3)


@pytest.fixture(scope="module")
def latent_blocks_run(tmp_path_factory):
    """``live_blocks_run`` for a latent lane cache: two blocks of 128 of
    its 256 positions (the latent rule alone would give one)."""
    from deepspeed_tpu.ops.pallas import latent_decode_attention as lda

    calls = []
    patch = pytest.MonkeyPatch()
    real = lda.latent_decode_attention
    patch.setattr(lda, "latent_decode_attention",
                  lambda *a, **k: calls.append(a[4].block) or real(*a, **k))
    try:
        return _served_in_a_trace(_latent_engine, tmp_path_factory) \
            + (calls,)
    finally:
        patch.undo()


def test_latent_served_tokens_equal_a_cache_free_forward(latent_blocks_run):
    """Every token of every request is the argmax, at the last position,
    of the model applied with ``decode=False`` (the per-head form over
    all the tokens, no cache, no kernel) to prompt + tokens so far, with
    lanes re-used and one prompt that fills its bucket exactly."""
    calls = latent_blocks_run[-1]
    assert calls and set(calls) == {128}     # the kernel, at two blocks
    _check_against_a_cache_free_forward(*latent_blocks_run[:4])


def test_latent_spans_counter_is_live_blocks_on_the_hosts_clocks(
        latent_blocks_run):
    """The span's ``kv_blocks_read_share`` is ``live_blocks`` on the
    host's clocks, which are the device's ``cache_index`` and ``valid``
    (one of each a lane for a latent cache, no layer axis)."""
    eng, sched, _, _, steps, found, _, _ = latent_blocks_run
    _check_the_spans_counter(eng, sched, steps, found, per_layer=False)


def test_latent_cache_plan_and_summary_say_live_blocks(latent_blocks_run):
    _, sched, _, _, steps, _, events, _ = latent_blocks_run
    plans = [e for e in events if e["kind"] == "serve.cache_plan"]
    assert len(plans) == 1
    assert plans[0]["decode_attention"] == "live_blocks"
    assert plans[0]["decode_attention_block"] == 128
    assert plans[0]["latent_bytes_per_lane"] > 0
    mean = sum(s[2] for s in steps) / len(steps)
    assert 0.5 < mean < 1.0


def test_a_ragged_last_block_counts_as_a_block_held():
    """A block that does not divide the cache (the latent rule: 2,944
    positions in blocks of 512) leaves a ragged sixth block: the share is
    blocks read over blocks held, 1.0 with every block of every lane
    read."""
    stats = scheduler_mod.ServingStats()
    clocks = scheduler_mod.LaneClocks(stats, 2, 2944, 512)
    clocks.admit(0, 64, 60, 0)
    clocks.admit(1, 512, 500, 2431)
    assert clocks.step() == pytest.approx((1 + 6) / 12)
    clocks.admit(0, 64, 64, 2900)
    assert clocks.step() == 1.0
