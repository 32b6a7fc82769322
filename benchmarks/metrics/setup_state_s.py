"""Seconds of the trainer's ``state`` set-up phase: parameters and
optimizer state drawn and placed on the device, up to the wait for the
placed state (the ``training`` block's ``setup_state_seconds``,
``train/step_budget.py: setup_phase``): the draws' and the optimizer's
one-operation programs, built or loaded from the cache, and their runs.
A process runs one cell, so the block's total is this run's. Layer:
entry points. Moves ``setup_s``."""


def read(ctx):
    from dragonfly2_tpu.train import step_budget

    # A program from before the set-up phases has nothing to read.
    block = getattr(step_budget, "TRAINING", None)
    counted = block.snapshot() if block else {}
    return counted.get("setup_state_seconds") or None
