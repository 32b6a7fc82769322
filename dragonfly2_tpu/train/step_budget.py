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

Every budget also feeds the process-wide ``training`` block of
``/debug/vars`` (:data:`TRAINING`; docs/OBSERVABILITY.md "Training
loops"): what the loops dispatched, and how many executables JAX built
or loaded while a loop ran. What a trainer does before its loop's
budget exists is measured by :func:`setup_phase`.
"""

from __future__ import annotations

import contextlib
import gc
import threading
import time
import weakref
from typing import Callable, Optional

import jax
import numpy as np
from jax._src.dispatch import (
    BACKEND_COMPILE_EVENT,
    JAXPR_TO_MLIR_MODULE_EVENT,
    JAXPR_TRACE_EVENT,
)

from dragonfly2_tpu.utils.debugmon import register_debug_var


class TrainingStats:
    """The ``training`` block: monotonic counters over every
    :class:`StepBudget` of the process, behind one lock. Nothing here
    touches the device.

    - ``loops_started``: budgets created (one per train-loop call).
    - ``dispatches``: ``tick`` calls, one per launched step program.
    - ``steps``: optimizer steps in them (``steps_per_call`` a dispatch).
    - ``samples``: samples counted into throughput windows (the first
      step's and a new program's are excluded with their compile).
    - ``compile_seconds``: what the budgets excluded as compile time.
    - ``loop_compiles``: executables JAX built *or loaded from its
      persistent cache* between a budget's creation and its ``finish``.
      JAX records one backend-compile event per executable either way
      (a cache load fires the cache-hit event besides, which is
      therefore not counted), so a cold and a warm run count the same.
      At least 1 (the step program); a constant of a loop, so one more
      is a recompile.
    - ``steady_compiles``: those of them after the budget's first
      ``tick``. 0 in a healthy run: anything else compiled while the
      loop should only have been dispatching.
    - ``moe_steps``: optimizer steps of loops whose model routes tokens
      over experts (``train_seq``), and over them
      ``moe_assignments_held``: token-to-expert assignments that went to
      an expert held here, summed over the expert layers, and
      ``moe_assignments_hottest``: those of each layer's most-assigned
      held expert, summed over the layers. Hottest over (held / experts
      held) is the load imbalance the grouped products see. Read from
      the device once, at a loop's drain.
    - ``sampler_row_width``: not a counter but the last value set, where
      ``fused_sampling.put_graph_tables`` places GraphSAGE's tables: the
      lanes of a host's neighbour row, or 0 where the graph kept its CSR
      form (and before any table was placed).
    - ``attn_inverse_slots``, ``attn_inverse_filled``: the last values
      set too, where ``train_gat`` builds the GraphTransformer's inverse
      index: its slots (hosts times its width) and those of them that
      name a listing. Their ratio is the share of the attention
      backward's source-major pass that is not padding.
    - ``seq_attn_window``: the last value set too, where ``train_seq``
      builds its step: the window of the model's sliding-attention
      layers in tokens, or 0 for a family (or a cut) without one.
    - ``seq_attn_tiles``, ``seq_attn_tiles_kept``: the last values set
      there too, from the corpus's document ids on the host: the score
      tiles on or under the diagonal of the corpus's rows at the
      full-attention kernel's tile (rows times n(n+1)/2 for n tiles a
      row), and those of them that a document reaches
      (``seq_layers.document_tiles``), which are the ones the TPU kernel
      computes; the rest it skips. Both 0 for a cut without a
      full-attention layer (and for rows that are no whole number of
      tiles, which the kernel does not take).
    - ``seq_sparse_topk``: the last value set there too: how many keys a
      query keeps where the model's attention runs over a learned
      selection (``sa_config.topk``), or 0 for a family without one;
      and over such a loop's steps the counters
      ``seq_sparse_candidates``: the keys its queries could have kept
      (for each query the earlier tokens of its document and itself),
      summed over queries, layers and steps, and
      ``seq_sparse_selected``: those they kept, and
      ``seq_sparse_tiles_held``: the attention kernels' ``[block,
      block]`` tiles that hold a member, summed over layers, sequences
      and steps. Counted from the selections' own masks (the tiles from
      the kernels' own tile table) on the device and read once, at a
      loop's drain.
    - ``seq_sparse_grid_steps``: the last value set where ``train_seq``
      builds its step: the grid steps of one call of those kernels at
      the step's shapes (key-value heads x tiles a row x tiles a column
      where a step takes a whole group of query heads), or 0 for a
      family without a selection. Over ``seq_sparse_tiles_held`` per
      call it says how much of the grid computes.
    - ``seq_loop_steps``: the last value set where ``train_seq`` builds
      its step: how many times a looped family runs its layers with the
      same weights (``total_ut_steps``), or 0 for a family that runs
      them once; and over such a loop's steps the counters
      ``seq_exit_mass_1`` .. ``seq_exit_mass_4``: the exit
      distribution's ``p(t)`` at each pass, summed over the counted
      positions, sequences and steps (in positions; summed on the
      device in fixed point, ``seq_layers.EXIT_MASS_BITS``, and read
      once, at a loop's drain). Where the last one holds nearly all the
      positions, the gates leave nothing to the earlier exits.
    - ``seq_head_fused_blocks``: the last value set where ``train_seq``
      builds its step: the blocks of ``seq_layers.HEAD_BLOCK`` positions
      in which a sequence's loss head forms its gradient in its forward
      pass (three products a block, the logits made once), 1 for a
      sequence no longer than a block; a looped family's one call takes
      every exit's positions (2 for four exits of 4,096). 0 before any
      sequence loop.
    - ``setup_data_seconds``, ``setup_state_seconds``,
      ``setup_tables_seconds``: wall seconds of the trainers' set-up
      phases (:func:`setup_phase`): host structures from the records;
      parameters and optimizer state drawn and placed; the graph's or
      corpus's arrays placed. Each ends when what it placed is on the
      device.
    - ``setup_compiles``: executables built or loaded while a set-up
      phase was open (parameter draws, the optimizer's init, placement
      programs: one-operation programs, mostly).
    - ``loop_compile_seconds``: wall seconds of JAX's tracing, lowering
      and backend compile (or cache load) while a budget was open: the
      step program's, beside ``loop_compiles``. A trace nested in
      another (a ``jit`` called while tracing) counts once.
    """

    KEYS = ("loops_started", "dispatches", "steps", "samples",
            "compile_seconds", "loop_compiles", "steady_compiles",
            "moe_steps", "moe_assignments_held", "moe_assignments_hottest",
            "sampler_row_width", "attn_inverse_slots",
            "attn_inverse_filled", "seq_attn_window", "seq_attn_tiles",
            "seq_attn_tiles_kept", "seq_sparse_topk",
            "seq_sparse_candidates", "seq_sparse_selected",
            "seq_sparse_tiles_held", "seq_sparse_grid_steps",
            "seq_loop_steps", "seq_exit_mass_1", "seq_exit_mass_2",
            "seq_exit_mass_3", "seq_exit_mass_4", "seq_head_fused_blocks",
            "setup_data_seconds",
            "setup_state_seconds", "setup_tables_seconds", "setup_compiles",
            "loop_compile_seconds")
    # What is counted in fractions: seconds, and positions' shares.
    SECONDS = ("compile_seconds", "setup_data_seconds",
               "setup_state_seconds", "setup_tables_seconds",
               "loop_compile_seconds")
    POSITIONS = ("seq_exit_mass_1", "seq_exit_mass_2", "seq_exit_mass_3",
                 "seq_exit_mass_4")

    def __init__(self):
        self._lock = threading.Lock()
        self._counts = dict.fromkeys(self.KEYS, 0)
        self._counts.update(dict.fromkeys(self.SECONDS + self.POSITIONS,
                                          0.0))
        # Budgets between creation and finish. Weak: a loop that raises
        # never reaches finish, and its budget must not keep counting.
        self._open = weakref.WeakSet()
        # The set-up phase open now, if any, and the compile events seen
        # inside the open loops that no later event has covered yet.
        self._phase = None
        self._spans = []

    def add(self, **increments) -> None:
        with self._lock:
            for key, value in increments.items():
                self._counts[key] += value

    def set(self, **values) -> None:
        with self._lock:
            self._counts.update(values)

    def loop_started(self, budget) -> None:
        with self._lock:
            if self._phase is not None:
                raise RuntimeError(f"a train loop started inside the "
                                   f"set-up phase {self._phase!r}")
            self._counts["loops_started"] += 1
            self._open.add(budget)
            self._spans = []

    def loop_finished(self, budget) -> None:
        with self._lock:
            self._open.discard(budget)

    def phase_opened(self, name: str) -> None:
        if self._open:
            # A loop that raised leaves its budget to the collector.
            gc.collect()
        with self._lock:
            if self._phase is not None:
                raise RuntimeError(f"set-up phase {name!r} opened inside "
                                   f"{self._phase!r}: phases do not nest")
            if self._open:
                raise RuntimeError(f"set-up phase {name!r} opened while a "
                                   "train loop runs")
            self._phase = name

    def phase_closed(self, name: str, seconds: float) -> None:
        with self._lock:
            self._phase = None
            self._counts[f"setup_{name}_seconds"] += seconds

    def executable_built(self) -> None:
        with self._lock:
            if self._phase is not None:
                self._counts["setup_compiles"] += 1
            for budget in self._open:
                self._counts["loop_compiles"] += 1
                if budget.steps:
                    self._counts["steady_compiles"] += 1

    def compile_span(self, start: float, end: float) -> None:
        """One tracing, lowering or backend-compile event, reported as it
        ends: inside an open loop, its seconds less those of the events
        reported before it that it holds (a ``jit`` traced inside
        another's trace ends first)."""
        with self._lock:
            if not self._open:
                return
            inner = 0.0
            while self._spans and self._spans[-1][0] >= start:
                a, b = self._spans.pop()
                inner += b - a
            self._spans.append((start, end))
            self._counts["loop_compile_seconds"] += end - start - inner

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._counts)


TRAINING = TrainingStats()
register_debug_var("training", TRAINING.snapshot)


_COMPILE_EVENTS = (JAXPR_TRACE_EVENT, JAXPR_TO_MLIR_MODULE_EVENT,
                   BACKEND_COMPILE_EVENT)


def _on_event_span(event: str, start: float, end: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        TRAINING.compile_span(start, end)
    if event == BACKEND_COMPILE_EVENT:
        TRAINING.executable_built()


# Once, at import; the listener does nothing while no budget or set-up
# phase is open. JAX reports each of these events' span and duration
# together; the span tells a nested trace from the one around it.
jax.monitoring.register_event_time_span_listener(_on_event_span)


SETUP_PHASES = ("data", "state", "tables")


@contextlib.contextmanager
def setup_phase(name: str):
    """One phase of a trainer's set-up, from its entry to its loop's
    ``StepBudget`` (docs/OBSERVABILITY.md "Training loops"): the host
    span ``df2.setup.<name>`` on the profiler's clock, and the phase's
    wall seconds added to the ``training`` block's
    ``setup_<name>_seconds`` (a phase opened twice adds up). Yields
    ``placed(x) -> x``: the phase ends with a wait for everything handed
    to it, so that the device's part of the phase is the phase's and not
    the first step's. Phases do not nest or overlap, and none opens while
    a train loop runs (``RuntimeError``)."""
    if name not in SETUP_PHASES:
        raise ValueError(f"set-up phase {name!r}: one of {SETUP_PHASES}")
    placed = []

    def hand(x):
        placed.append(x)
        return x

    TRAINING.phase_opened(name)
    start = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(f"df2.setup.{name}"):
            yield hand
            jax.block_until_ready(placed)
    finally:
        TRAINING.phase_closed(name, time.perf_counter() - start)


def epoch_mean(losses) -> float:
    """Mean of an epoch's per-step losses (scalars, or one vector per
    multi-step dispatch), taken on the host: one sync on the last of
    them and no device program. A ``jnp`` reduction over the list is a
    new program for every epoch length, compiled mid-run the first time
    an epoch ends."""
    return float(np.mean(np.concatenate(
        [np.ravel(x) for x in jax.device_get(losses)])))


def step_loop(budget, epochs: int, epoch_steps, dispatch, *,
              step_samples: int, drain, serialize_launches: bool = False,
              step_facts: Optional[dict] = None):
    """The skeleton of a train loop with its host spans
    (docs/OBSERVABILITY.md "Training loops"): for each epoch, for each
    ``make_input`` of ``epoch_steps(epoch)``, ``dispatch(make_input())``
    launches one step and returns its loss; ``budget.tick`` counts
    ``step_samples`` and says when to stop. ``drain()`` returns what the
    final wait blocks on. Returns each epoch's mean loss (a host sync at
    an epoch's end). ``step_facts`` are written on every
    ``df2.train.step`` span beside the step's number. The spans cost
    nothing while no profiler runs."""
    span = jax.profiler.TraceAnnotation
    history, stop, step_num = [], False, 0
    for epoch in range(epochs):
        losses = []
        for i, make_input in enumerate(epoch_steps(epoch)):
            with jax.profiler.StepTraceAnnotation(
                    "df2.train.step", step_num=step_num,
                    **(step_facts or {})):
                with span("df2.train.input", epoch=epoch, step=i):
                    inputs = make_input()
                with span("df2.train.dispatch"):
                    loss = dispatch(inputs)
                if serialize_launches:
                    jax.block_until_ready(loss)
                losses.append(loss)
                with span("df2.train.tick"):
                    stop = budget.tick(step_samples, loss)
            step_num += 1
            if stop:
                break
        if losses:
            # A host sync: it waits for every queued step.
            with span("df2.train.epoch_end"):
                history.append(epoch_mean(losses))
        if stop:
            break
    with span("df2.train.drain"):
        jax.block_until_ready(drain())
    budget.finish()
    return history


class StepBudget:
    def __init__(
        self,
        max_seconds: Optional[float] = None,
        on_compile: Optional[Callable[[float], None]] = None,
        on_progress: Optional[Callable[[int, float], None]] = None,
        progress_every: int = 25,
        step_samples: Optional[int] = None,
    ):
        """``step_samples``: samples of one optimizer step, where a
        dispatch may hold several (``steps_per_call``); only the
        ``training`` block's ``steps`` reads it."""
        self.max_seconds = max_seconds
        self.steps = 0
        self.samples = 0
        self.compile_seconds = 0.0
        self._step_samples = step_samples
        self._on_compile = on_compile
        self._on_progress = on_progress
        self._progress_every = max(progress_every, 1)
        self._start = time.perf_counter()
        self._last = self._start
        self._deadline: Optional[float] = None
        self._elapsed: Optional[float] = None
        self._synced = False
        TRAINING.loop_started(self)

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
        counted, excluded = 0, 0.0
        if self.steps == 0:
            jax.block_until_ready(first_step_output)
            now = time.perf_counter()
            self.compile_seconds = excluded = now - self._start
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
            counted = n_samples
        self.steps += 1
        self._synced = False
        TRAINING.add(
            dispatches=1, samples=counted, compile_seconds=excluded,
            steps=(n_samples // self._step_samples
                   if self._step_samples else 1))
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
        TRAINING.loop_finished(self)

    def samples_per_sec(self, batch_size: int) -> float:
        """Steady-state throughput; single-step runs have no post-compile
        window, so the whole run (compile included) is the best estimate."""
        elapsed = self._elapsed or max(time.perf_counter() - self._start, 1e-9)
        if self.samples:
            return self.samples / elapsed
        return batch_size * self.steps / max(self.compile_seconds, 1e-9)
