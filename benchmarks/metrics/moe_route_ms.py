"""Device time a step spends moving tokens to the experts and back:
the seconds under the expert layer's ``df2.moe.route`` (router, top-k,
weights), ``df2.moe.dispatch`` (the sort by held expert, rows gathered
into expert order) and ``df2.moe.combine`` (rows gathered back, the
weighted sum) scopes, forward and backward, over the window's steps.
Only a TPU trace carries scope paths. Layer: kernels. Moves
``train_samples_per_s``."""

chip_only = True
SCOPES = ("df2.moe.route", "df2.moe.dispatch", "df2.moe.combine")


def read(ctx):
    trace, steps = ctx["trace"], ctx["run"]["steps"]
    if trace is None or not steps:
        return None
    under = trace.scope_seconds
    found = [under[scope] for scope in SCOPES if scope in under]
    return 1e3 * sum(found) / steps if found else None
