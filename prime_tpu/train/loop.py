"""Training loop driver: timing, throughput, profiling, checkpoint cadence.

The jitted step (trainer.make_train_step) is pure compute; this loop owns the
host-side concerns: per-step wall-clock timing (with a forced device sync,
so the timed region holds the step and not only its dispatch), tokens/sec,
metrics.jsonl logging, periodic orbax checkpoints, and an optional
``jax.profiler`` trace window for a chosen step range (view with
tensorboard/xprof).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable

import jax.numpy as jnp


@dataclass
class LoopReport:
    steps: int = 0
    final_loss: float = float("nan")
    mean_step_time_s: float = float("nan")
    tokens_per_sec: float = float("nan")
    step_times_s: list[float] = field(default_factory=list)

    def as_dict(self) -> dict[str, Any]:
        return {
            "steps": self.steps,
            "final_loss": self.final_loss,
            "mean_step_time_s": self.mean_step_time_s,
            "tokens_per_sec": self.tokens_per_sec,
        }


def train_loop(
    state,
    step_fn: Callable,
    batches: Iterable[tuple],          # yields (tokens, targets, mask)
    *,
    metrics=None,                      # train.metrics.MetricsLogger or None
    checkpoints=None,                  # train.checkpoint.CheckpointManager or None
    checkpoint_every: int = 0,
    profile_dir: str | None = None,
    profile_window: tuple[int, int] = (2, 5),   # [start, stop) steps to trace
    log_every: int = 1,
    on_step: Callable[[int, dict], None] | None = None,
    start_step: int = 0,
):
    """Drive ``step_fn`` over ``batches``. Returns (state, LoopReport).
    ``start_step`` offsets logged step numbers when resuming a run."""
    import jax

    report = LoopReport()
    profiling = False
    try:
        for step, (tokens, targets, mask) in enumerate(batches):
            if profile_dir is not None and step == profile_window[0]:
                jax.profiler.start_trace(profile_dir)
                profiling = True

            t0 = time.perf_counter()
            state, step_metrics = step_fn(state, tokens, targets, mask)
            # scalar fetch = device sync: the step is timed to completion,
            # not to dispatch
            loss = float(step_metrics["loss"])
            dt = time.perf_counter() - t0

            if profiling and step + 1 == profile_window[1]:
                jax.profiler.stop_trace()
                profiling = False

            tokens_this_step = int(jnp.size(tokens))
            report.steps = step + 1
            report.final_loss = loss
            report.step_times_s.append(dt)
            row = {
                "loss": loss,
                "grad_norm": float(step_metrics.get("grad_norm", float("nan"))),
                "step_time_s": dt,
                "tokens_per_sec": tokens_this_step / dt if dt > 0 else 0.0,
            }
            if metrics is not None and step % max(log_every, 1) == 0:
                metrics.log(start_step + step, **row)
            if on_step is not None:
                on_step(start_step + step, row)
            if checkpoints is not None and checkpoint_every and (step + 1) % checkpoint_every == 0:
                checkpoints.save(state, metrics={"loss": loss})
    finally:
        if profiling:
            jax.profiler.stop_trace()

    if report.step_times_s:
        # first step pays compile; report steady-state timing when possible
        steady = report.step_times_s[1:] or report.step_times_s
        report.mean_step_time_s = sum(steady) / len(steady)
        per_step_tokens = tokens_this_step
        report.tokens_per_sec = (
            per_step_tokens / report.mean_step_time_s if report.mean_step_time_s > 0 else 0.0
        )
    return state, report
