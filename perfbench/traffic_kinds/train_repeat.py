"""Training on one seeded batch, repeated.

The batch comes from the seed; the program sees the batch and never the
seed. The window is made of whole steps: it opens after the warm-up steps
are fenced and closes at the first fence at or after ``--seconds``. One
step stays in flight behind the fence (the host dispatches step k+1 before
it waits for step k), as a training loop that logs its loss does.
"""
import math
import time
from dataclasses import dataclass

import numpy as np

ROLE = "train"


@dataclass
class Plan:
    batch: dict
    tokens_per_step: int
    seq: int
    label_share: float


def plan(env):
    p = env.traffic
    rng = np.random.default_rng([env.seed, 0])
    vocab = int(env.config["model"]["vocab_size"])
    rows = int(p["micro_batch_per_chip"]) * env.chips
    seq = int(p["seq"])
    ids = rng.integers(0, vocab, size=(rows, seq), dtype=np.int32)
    if p["labels"] == "next_token":
        labels, share = ids, 1.0
    elif p["labels"] == "masked":
        share = float(p["label_share"])
        labels = np.where(rng.random((rows, seq)) < share, ids,
                          -100).astype(np.int32)
    else:
        raise ValueError(f"unknown labels {p['labels']!r}")
    return Plan(batch={"input_ids": ids, "labels": labels},
                tokens_per_step=rows * seq, seq=seq, label_share=share)


def warm_up(env, system, plan):
    system.load(plan.batch)
    loss = None
    for _ in range(int(env.traffic["warm_up_steps"])):
        loss = system.step()
    system.fence(loss)


def drive(env, system, plan):
    """Steps until the window is over. Untraced: a fence one step behind
    the dispatch. Traced: a fence after every step, so that each step's
    time stands alone."""
    seconds = min(env.seconds, float(env.traffic["trace_seconds"])) \
        if env.trace else env.seconds
    losses, ends = [], []
    t_open = env.open_window(host="train_loop")
    pending = None
    while True:
        with env.span("train_batch"):
            loss = system.step()
        losses.append(loss)
        if env.trace:
            pending, to_fence = None, loss
        else:
            pending, to_fence = loss, pending
        if to_fence is not None:
            with env.span("fence"):
                system.fence(to_fence)
            ends.append(time.monotonic())
            if ends[-1] - t_open >= seconds:
                break
    if pending is not None:     # the step in flight is outside the window
        system.fence(pending)
        losses.pop()
    env.close_window()
    return {"step_ends": ends, "losses": [float(x) for x in losses],
            "fenced_every_step": bool(env.trace)}


def _step_ms(env, record):
    ends = [env.t_open] + record["step_ends"]
    return [(b - a) * 1e3 for a, b in zip(ends, ends[1:])]


def series(env, system, plan, record):
    """Named series and counters for the end-to-end metrics and readers."""
    ends = record["step_ends"]
    return {"step_ms": _step_ms(env, record),
            "tokens_per_s_per_chip": len(ends) * plan.tokens_per_step
            / (ends[-1] - env.t_open) / env.chips}


def end_to_end(series):
    return {"train_tokens_per_s_per_chip": series["tokens_per_s_per_chip"]}


def check(env, system, plan, record):
    """Every loss in the window finite, and the mean of the last five below
    the window's first by ``loss_margin``: the batch repeats, so the model
    memorises it and the loss falls by whole nats within tens of steps; 0.1
    is ten times the step-to-step wobble of a bf16 loss and far below that
    fall, so a step that does not train fails it."""
    losses = record["losses"]
    margin = float(env.traffic["loss_margin"])
    finite = [math.isfinite(x) for x in losses]
    tail = losses[-5:]
    falling = len(losses) >= 2 and \
        sum(tail) / len(tail) < losses[0] - margin
    steps = sorted(_step_ms(env, record))
    return {"correct": all(finite) and falling, "attempted": len(losses),
            "failed": finite.count(False),
            "first_loss": losses[0], "last_loss": losses[-1],
            # for the log: a run that reads far off shows here whether one
            # step stalled or all were slow
            "step_ms_median": steps[len(steps) // 2],
            "step_ms_slowest": steps[-1]}
