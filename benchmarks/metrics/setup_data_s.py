"""Seconds of the trainer's ``data`` set-up phase: the host structures
built from the records before any state is drawn (the ``training``
block's ``setup_data_seconds``, ``train/step_budget.py: setup_phase``;
in ``train_gnn`` the labels, the pair split's ``np.unique``, the CSR's
argsort and the edge samplers, in ``train_gat`` the split and the
neighbour lists with their inverse index, in ``train_seq`` the id
checks and the tiles a document reaches). A process runs one cell, so
the block's total is this run's. Layer: host ingest. Moves
``setup_s``."""


def read(ctx):
    from dragonfly2_tpu.train import step_budget

    # A program from before the set-up phases has nothing to read.
    block = getattr(step_budget, "TRAINING", None)
    counted = block.snapshot() if block else {}
    return counted.get("setup_data_seconds") or None
