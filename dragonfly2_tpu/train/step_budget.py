"""Shared step-loop accounting: compile exclusion + wall-clock budget.

Both trainers measure steady-state throughput the same way — block on the
first step to capture XLA compile time, restart the clock, then count
samples until the optional deadline. This helper holds that logic once so
the accounting can't drift between models.

Progress hooks (the round-2 verdict's "publish throughput incrementally"):
``on_compile`` fires when the first step completes and again on every
mid-run new-program exclusion, always passing the CUMULATIVE compile
seconds so assign-style consumers record the full figure,
``on_progress`` fires every ``progress_every`` steps with the current
steady-state rate — the bench uses these to keep its headline current so a
watchdog fire emits the latest measured rate instead of zero.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

import jax


class StepBudget:
    def __init__(
        self,
        max_seconds: Optional[float] = None,
        on_compile: Optional[Callable[[float], None]] = None,
        on_progress: Optional[Callable[[int, float], None]] = None,
        progress_every: int = 25,
    ):
        self.max_seconds = max_seconds
        self.steps = 0
        self.samples = 0
        self.compile_seconds = 0.0
        self._on_compile = on_compile
        self._on_progress = on_progress
        self._progress_every = max(progress_every, 1)
        self._start = time.perf_counter()
        self._last = self._start
        self._deadline: Optional[float] = None
        self._elapsed: Optional[float] = None
        self._synced = False

    def sync_point(self, prev_output) -> None:
        """Call immediately BEFORE dispatching a program shape that has
        not been compiled yet: drains the async queue so the upcoming
        ``tick(new_program=True)`` excludes only the new dispatch itself
        (compile + its run), not earlier steps' queued device work."""
        if self.steps == 0:
            return  # first-step accounting already covers this case
        jax.block_until_ready(prev_output)
        self._last = time.perf_counter()
        self._synced = True

    def tick(self, n_samples: int, first_step_output,
             new_program: bool = False) -> bool:
        """Account one completed step dispatch; returns True when the
        budget is exhausted and the loop should stop.

        On the first step, blocks on ``first_step_output`` so compile time
        is captured and excluded from the throughput window.

        ``new_program=True`` marks a dispatch that compiled a SECOND
        program shape mid-run (e.g. the tail scan when steps_per_call
        doesn't divide the epoch): the call is blocked on, its whole
        duration is pushed out of the throughput window (start and
        deadline both shift), and its samples are not counted — the
        same exclusion the first step gets. Without this, a tail-scan
        compile of tens of seconds lands inside a 60 s window and
        understates steady-state throughput by double digits (observed
        on-chip: 17.2k vs 23.6k edge-samples/sec at the same config).
        """
        if self.steps == 0:
            jax.block_until_ready(first_step_output)
            now = time.perf_counter()
            self.compile_seconds = now - self._start
            self._start = now
            self._last = now
            if self.max_seconds is not None:
                self._deadline = now + self.max_seconds
            if self._on_compile is not None:
                self._on_compile(self.compile_seconds)
        elif new_program:
            if not self._synced:
                # Without the paired sync_point, _last is stale and the
                # exclusion would swallow the whole steady-state window
                # since the previous program change, inflating the rate.
                raise RuntimeError(
                    "tick(new_program=True) requires sync_point() "
                    "immediately before the new-program dispatch")
            jax.block_until_ready(first_step_output)
            now = time.perf_counter()
            excluded = now - self._last
            self.compile_seconds += excluded
            self._start += excluded
            self._last = now
            if self._deadline is not None:
                self._deadline += excluded
            if self._on_compile is not None:
                # Cumulative, matching the first fire: consumers assign
                # (bench.py gnn_compile_seconds=...), so an increment here
                # would overwrite the real compile figure with the tail's.
                self._on_compile(self.compile_seconds)
        else:
            self.samples += n_samples
        self.steps += 1
        self._synced = False
        if (self._on_progress is not None and self.samples
                and self.steps % self._progress_every == 0):
            # Block on the CURRENT step so the published rate counts
            # completed device work — without this, async dispatch lets
            # the host run tens of steps ahead and the rate would be the
            # dispatch rate, not throughput. The sync bubble costs one
            # device round trip per progress_every steps.
            jax.block_until_ready(first_step_output)
            elapsed = max(time.perf_counter() - self._start, 1e-9)
            self._on_progress(self.steps, self.samples / elapsed)
        return (self._deadline is not None
                and time.perf_counter() >= self._deadline)

    def finish(self) -> None:
        """Freeze the throughput window (call after the final block)."""
        self._elapsed = max(time.perf_counter() - self._start, 1e-9)

    def samples_per_sec(self, batch_size: int) -> float:
        """Steady-state throughput; single-step runs have no post-compile
        window, so the whole run (compile included) is the best estimate."""
        elapsed = self._elapsed or max(time.perf_counter() - self._start, 1e-9)
        if self.samples:
            return self.samples / elapsed
        return batch_size * self.steps / max(self.compile_seconds, 1e-9)
