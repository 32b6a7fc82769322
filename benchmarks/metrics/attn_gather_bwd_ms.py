"""Device time a step spends in the backward of the GraphTransformer's
neighbour gathers: the seconds under ``df2.attn.gather_bwd`` (the
inverse-index gathers, their reshapes and the sums over the index axis;
``trace.py``: the union of the intervals of the operations whose path
holds the scope, mean over chips) over the window's steps. Only a TPU
trace carries scope paths. Layer: kernels. Moves
``train_samples_per_s``."""

chip_only = True
SCOPE = "df2.attn.gather_bwd"


def read(ctx):
    trace, steps = ctx["trace"], ctx["run"]["steps"]
    if trace is None or not steps or SCOPE not in trace.scope_seconds:
        return None
    return 1e3 * trace.scope_seconds[SCOPE] / steps
