"""How uneven the load of the experts held here is: the assignments of
each expert layer's most-assigned held expert over the held experts'
mean, from the program's own routing counts (the ``training`` block's
``moe_assignments_hottest`` and ``moe_assignments_held``, summed over
the expert layers and read once at the loop's drain). 1 is even; the
grouped products' longest group is this many times the mean. A process
runs one cell, so the block's totals are this run's. Layer: train loops.
Moves ``train_samples_per_s``."""


def read(ctx):
    from dragonfly2_tpu.train import step_budget

    # A program from before the counters has nothing to read.
    block = getattr(step_budget, "TRAINING", None)
    counted = block.snapshot() if block else {}
    held = counted.get("moe_assignments_held")
    if not held:
        return None
    experts = ctx["spec"]["deployment"]["experts_held"][1]
    return counted["moe_assignments_hottest"] * experts / held
