"""Host time one step needs: on the loop's thread the ``df2.train.step``
spans less the ``df2.train.tick`` and ``df2.train.epoch_end`` spans
inside them (where the loop may block on the device), plus the
``df2.train.input`` spans of the other threads (the prefetch workers),
over the window's steps (``hostspans.py``). How far the device step can
fall before the loop turns host-bound. Layer: train loops. Moves
``train_samples_per_s``."""

from benchmarks import hostspans


def read(ctx):
    if ctx["trace"] is None:
        return None
    return hostspans.host_step_ms(hostspans.window_threads())
