"""Device time a step spends in the shared experts: the seconds under
``df2.moe.shared`` (the gated FFN every token passes beside its routed
experts; forward, recomputation and backward) over the window's steps.
It lies outside ``df2.moe.experts``, so ``moe_expert_roofline`` stays
the routed products'. Only a TPU trace carries scope paths; a program
without the scope gives nothing to read. Layer: kernels. Moves
``train_samples_per_s``."""

chip_only = True


def read(ctx):
    trace, steps = ctx["trace"], ctx["run"]["steps"]
    if trace is None or not steps:
        return None
    seconds = trace.scope_seconds.get("df2.moe.shared")
    return 1e3 * seconds / steps if seconds else None
