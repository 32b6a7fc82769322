"""Process start to the start of the measured window, on the host's
clock: imports, the graph made from the seed, tables placed, compile or
cache load, the compared and the warm steps. End-to-end."""


def read(ctx):
    return ctx["run"]["setup_seconds"]
