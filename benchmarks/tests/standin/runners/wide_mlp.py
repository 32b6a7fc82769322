"""Runner for the stand-in kind ``wide_mlp``: traffic of its own (a
table of rows; no ``fleet``), the window drives
``wide_mlp_program.train`` once."""

from __future__ import annotations

import numpy as np

from benchmarks import instrument


def traffic(spec: dict, seed: int) -> dict:
    """``table.rows`` feature rows of the model's width and a label each
    from a random direction: same seed, same arrays; every seed the
    same sizes."""
    rng = np.random.default_rng(seed)
    rows, width = spec["table"]["rows"], spec["model"]["width"]
    features = rng.standard_normal((rows, width), np.float32)
    teacher = rng.standard_normal(width, np.float32)
    return {"features": features,
            "labels": (features @ teacher > 0).astype(np.float32)}


def drive(spec: dict, arrays: dict, seed: int, plan, mesh, wrap_step) -> None:
    from benchmarks.tests.standin import wide_mlp_program as program

    m, o = spec["model"], spec["optimizer"]
    with instrument.window_budget(plan, program), \
            instrument.observed_jit(program, "train_step", wrap_step):
        program.train(
            arrays["features"], arrays["labels"], width=m["width"],
            layers=m["layers"], batch=spec["batch"],
            learning_rate=o["learning_rate"], weight_decay=o["weight_decay"],
            epochs=spec["epochs"], seed=seed, max_seconds=plan.seconds)
