"""Device time a step spends sampling neighbours: the seconds under the
fused GraphSAGE step's ``df2.sample.hop1`` and ``df2.sample.hop2``
scopes (``trace.py``: the union of the intervals of the operations whose
path holds the scope, mean over chips) over the window's steps. Only a
TPU trace carries scope paths. Layer: kernels. Moves
``train_samples_per_s``."""

chip_only = True
SCOPES = ("df2.sample.hop1", "df2.sample.hop2")


def read(ctx):
    trace, steps = ctx["trace"], ctx["run"]["steps"]
    if trace is None or not steps:
        return None
    under = trace.scope_seconds
    found = [under[scope] for scope in SCOPES if scope in under]
    return 1e3 * sum(found) / steps if found else None
