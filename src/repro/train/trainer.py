"""Training loop: step fn + data + checkpoints + fault tolerance.

The loop a launcher drives.  Composes:
  * StepBundle (jitted train step with resolved shardings),
  * synthetic (or user) data stream placed under input shardings,
  * CheckpointManager (async atomic saves every ``ckpt_every``),
  * StragglerMonitor + Heartbeat,
  * auto-resume (elastic: restores onto whatever mesh is current).

Each step writes the profiler spans ``fit.data``, ``fit.dispatch``,
``fit.sync`` (waiting for the loss) and ``fit.checkpoint``; without an
active trace they cost a flag check.  There is no span around the whole
step, so a device-idle gap falls inside the span of what the loop was
doing then.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Iterator, Optional

import jax
from jax.profiler import TraceAnnotation

from repro.train.checkpoint import CheckpointManager
from repro.train.fault import Heartbeat, StragglerMonitor


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 100
    ckpt_every: int = 50
    log_every: int = 10
    ckpt_dir: Optional[str] = None
    heartbeat_path: Optional[str] = None
    straggler_threshold: float = 2.0


def train_steps(bundle, state, data_iter: Iterator, n_steps: int):
    """Bare loop: n_steps through the jitted step, no ckpt/heartbeat.

    The parity tests and the SPMD benchmark drive this — same step fn
    the full ``fit`` loop uses, minus host-side machinery, returning the
    final state and the per-step metrics (still device values; callers
    ``float()`` what they need).
    """
    history = []
    for _ in range(n_steps):
        _, batch = next(data_iter)
        state, metrics = bundle.step_fn(state, batch)
        history.append(metrics)
    jax.block_until_ready(state)
    return state, history


def fit(bundle, state, data_iter: Iterator, tcfg: TrainerConfig,
        log_fn: Callable = print):
    """Runs the loop; returns (final_state, history).

    All bookkeeping is keyed off the optimizer step (``state["step"]``),
    NOT the data iterator's counter: after an auto-resume the iterator
    may restart at 0 while the restored state does not, and keying
    checkpoints by the iterator step made filenames collide/regress and
    misfired the save guard.  A stale iterator is fast-forwarded instead
    (skipped batches are cheap — the synthetic stream is seeded per
    step), so resumed runs see the exact continuation of the stream.
    """
    ckpt = CheckpointManager(tcfg.ckpt_dir) if tcfg.ckpt_dir else None
    hb = Heartbeat(tcfg.heartbeat_path) if tcfg.heartbeat_path else None
    mon = StragglerMonitor(tcfg.straggler_threshold)
    history = []
    cur = int(state["step"])  # authoritative; advances with each update
    last_saved = None         # step of the most recent periodic save
    batches = iter(data_iter)
    while cur < tcfg.total_steps:
        with TraceAnnotation("fit.data"):
            # not next(): the profiler's Python tracer records that call
            # as ``$builtins next``, which would outweigh this span where
            # a trace reader names an idle gap by its longest host event
            try:
                it_step, batch = batches.__next__()
            except StopIteration:
                break
        if it_step < cur:  # stale iterator after a resume: fast-forward
            continue
        t0 = time.perf_counter()
        with TraceAnnotation("fit.dispatch"):
            state, metrics = bundle.step_fn(state, batch)
        with TraceAnnotation("fit.sync"):
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            loss = float(metrics["loss"])
        straggler = mon.record(cur, dt)
        rec = {"step": cur, "loss": loss, "sec": dt, "straggler": straggler}
        history.append(rec)
        if hb is not None:
            hb.beat(cur, loss=rec["loss"])
        if straggler:
            log_fn(f"[straggler] step {cur}: {dt:.3f}s "
                   f"(mean {mon.mean:.3f}s)")
        if cur % tcfg.log_every == 0:
            log_fn(f"step {cur:5d} loss {rec['loss']:.4f} {dt*1e3:.1f}ms")
        cur += 1  # == int(state["step"]) without a device sync
        if ckpt is not None and cur % tcfg.ckpt_every == 0:
            with TraceAnnotation("fit.checkpoint"):
                ckpt.save(cur, state)
            last_saved = cur
    if ckpt is not None:
        # final snapshot — but when the loop's last periodic save already
        # covered this step (total_steps % ckpt_every == 0), saving it
        # AGAIN would race the still-async writer on the same
        # step_XXXX.tmp; just wait for that writer to commit instead
        if last_saved == cur:
            ckpt.wait()
        else:
            ckpt.save(cur, state, blocking=True)
    return state, history
