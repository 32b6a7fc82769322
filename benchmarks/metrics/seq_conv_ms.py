"""Device time a step spends in the gated short convolutions: the
seconds under ``df2.seq.conv`` (input projection, gates, the causal
depthwise taps with their document masks, output projection; forward,
recomputation and backward) over the window's steps. Only a TPU trace
carries scope paths. Layer: kernels. Moves ``train_samples_per_s``."""

chip_only = True


def read(ctx):
    trace, steps = ctx["trace"], ctx["run"]["steps"]
    if trace is None or not steps:
        return None
    seconds = trace.scope_seconds.get("df2.seq.conv")
    return 1e3 * seconds / steps if seconds else None
