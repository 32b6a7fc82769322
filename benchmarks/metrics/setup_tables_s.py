"""Seconds of the trainer's ``tables`` set-up phase: the graph's or the
corpus's arrays placed on the device, up to the wait for them (the
``training`` block's ``setup_tables_seconds``, ``train/step_budget.py:
setup_phase``; GraphSAGE's row tables and edge tables, the
GraphTransformer's features, lists and inverse index, a corpus's
tokens, segments and positions). A process runs one cell, so the
block's total is this run's. Layer: entry points. Moves ``setup_s``."""


def read(ctx):
    from dragonfly2_tpu.train import step_budget

    # A program from before the set-up phases has nothing to read.
    block = getattr(step_budget, "TRAINING", None)
    counted = block.snapshot() if block else {}
    return counted.get("setup_tables_seconds") or None
