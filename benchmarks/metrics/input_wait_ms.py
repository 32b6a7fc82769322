"""Time a step's loop spent with nothing to dispatch: the
``df2.train.wait_input`` spans of the loop's thread (the wait on the
prefetch stream, and what the task generator does on that thread: an
epoch's permutation) over the window's steps (``hostspans.py``). Only
``train_gnn`` has the span. Layer: train loops. Moves
``train_samples_per_s``."""

from benchmarks import hostspans


def read(ctx):
    if ctx["trace"] is None:
        return None
    return hostspans.input_wait_ms(hostspans.window_threads())
