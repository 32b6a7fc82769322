"""The program's own host spans, out of the window's profiler trace.

The train loops put ``df2.train.*`` spans (``jax.profiler
.TraceAnnotation``) on the profiler's clock: ``df2.train.step`` around
one iteration on the loop's thread, and inside or beside it
``df2.train.wait_input``, ``df2.train.input``, ``df2.train.dispatch``,
``df2.train.tick``, ``df2.train.epoch_end``, ``df2.train.drain``
(docs/OBSERVABILITY.md "Training loops"). The metrics ``host_step_ms``
and ``input_wait_ms`` read them here.

How the trace is found: a reader's ``ctx`` carries neither the cell's
name nor the trace's path, so this takes the newest ``*.xplane.pb``
under ``<checkout>/.bench_trace/``. A process runs one cell, and
``run.py`` clears the cell's directory before it starts the window's
trace, so the newest file is this run's. Read with
``jax.profiler.ProfileData`` alone, as ``trace.py`` does. The trace
covers the window and nothing else, so every span in it belongs to a
step of the window.

The loop's thread is the one that holds the ``df2.train.step`` spans;
thread names are not used (a pool's Python thread name need not reach
the profiler, and several lines are called ``python3``).

A program without the spans (a parent commit that predates them) gives
empty lists, and the readers then return None.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HOST_PLANE = "/host:CPU"
PREFIX = "df2.train."
STEP = PREFIX + "step"


def newest_trace(root: str = ROOT):
    found = glob.glob(os.path.join(root, ".bench_trace", "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


@functools.lru_cache(maxsize=2)
def threads_of(path: str) -> tuple:
    """One tuple of ``(name, start_ns, stop_ns)`` per host thread that
    holds a ``df2.train.*`` span, the loop's thread first."""
    from jax.profiler import ProfileData

    threads = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            spans = tuple(
                (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                for ev in line.events if ev.name.startswith(PREFIX))
            if spans:
                threads.append(spans)
    threads.sort(key=lambda spans: -sum(s[0] == STEP for s in spans))
    return tuple(threads)


def window_threads():
    """``threads_of`` the newest trace; () where there is none."""
    path = newest_trace()
    return threads_of(path) if path else ()


def total_ms(spans, name: str) -> float:
    return sum(stop - start for n, start, stop in spans if n == name) * 1e-6


def host_step_ms(threads) -> float | None:
    """Host time one step needs, from span lists as ``threads_of`` gives
    them: on the loop's thread the ``step`` spans less what lies inside
    them and may block on the device (``tick``: the first step, a
    progress hook, the harness's throttle; ``epoch_end``: a host sync),
    plus the ``input`` spans of every other thread (the prefetch
    workers), over the number of ``step`` spans."""
    if not threads:
        return None
    loop, others = threads[0], threads[1:]
    steps = sorted((start, stop) for n, start, stop in loop if n == STEP)
    if not steps:
        return None
    starts = [s for s, _ in steps]
    busy = sum(stop - start for start, stop in steps)
    for name, start, stop in loop:
        if name in (PREFIX + "tick", PREFIX + "epoch_end"):
            i = bisect.bisect_right(starts, start) - 1
            if i >= 0 and stop <= steps[i][1]:
                busy -= stop - start
    busy += sum(stop - start for spans in others
                for n, start, stop in spans if n == PREFIX + "input")
    return busy * 1e-6 / len(steps)


def input_wait_ms(threads) -> float | None:
    """``wait_input`` on the loop's thread over the number of ``step``
    spans: what the loop spent with nothing to dispatch."""
    if not threads:
        return None
    loop = threads[0]
    steps = sum(n == STEP for n, _, _ in loop)
    if not steps or not any(n == PREFIX + "wait_input" for n, _, _ in loop):
        return None
    return total_ms(loop, PREFIX + "wait_input") / steps
