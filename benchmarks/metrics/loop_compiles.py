"""Executables JAX built or loaded from its persistent cache while the
run's train loop was open: the ``training`` block's ``loop_compiles``
(``train/step_budget.py``), from the ``StepBudget``'s creation to its
``finish``, so set-up's and the window's are both in it. A cache load
and a compile count the same. The healthy value is a constant of the
cell (PERF.md section 3); one more is a recompile. A process runs one
cell, so the block's total is this run's. Layer: train loops. Moves
``train_samples_per_s``."""


def read(ctx):
    from dragonfly2_tpu.train import step_budget

    # A program from before the block has nothing to read.
    block = getattr(step_budget, "TRAINING", None)
    return block.snapshot()["loop_compiles"] or None if block else None
