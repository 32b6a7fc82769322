"""Seconds JAX spent tracing, lowering and compiling (or loading from
its persistent cache) while the run's train loop was open: the
``training`` block's ``loop_compile_seconds`` (``train/step_budget.py``),
the step program's, beside ``loop_compiles``. A trace nested in another
counts once. Cold, the compile; warm, the trace, the lowering and the
cache load. A process runs one cell, so the block's total is this run's.
Layer: train loops. Moves ``setup_s``."""


def read(ctx):
    from dragonfly2_tpu.train import step_budget

    # A program from before the counter has nothing to read.
    block = getattr(step_budget, "TRAINING", None)
    counted = block.snapshot() if block else {}
    return counted.get("loop_compile_seconds") or None
