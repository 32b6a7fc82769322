"""Device time a step spends ranking index scores: the seconds under
``df2.seq.select`` (for every query the exact ``topk``-th largest score
of its candidates, the mask it gives, its packing) over the window's
steps. A time and not a share: a ranking reads no bytes that a roofline
could count once it is fused with the scores. Only a TPU trace carries
scope paths; a program without the scope gives nothing to read. Layer:
kernels. Moves ``train_samples_per_s``."""

chip_only = True


def read(ctx):
    trace, steps = ctx["trace"], ctx["run"]["steps"]
    if trace is None or not steps:
        return None
    seconds = trace.scope_seconds.get("df2.seq.select")
    return 1e3 * seconds / steps if seconds else None
