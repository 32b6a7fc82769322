"""Device-busy time of the traced window over the optimizer steps in
it. Layer: train loops. Moves ``train_samples_per_s``; a steadier
companion to it, since host stalls do not enter."""


def read(ctx):
    trace, steps = ctx["trace"], ctx["run"]["steps"]
    if trace is None or not steps:
        return None
    return 1e3 * trace.busy_s / steps
