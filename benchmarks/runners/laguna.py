"""Runner for the ``laguna`` kind: the window drives
``train/seq_trainer.py``'s ``train_seq``, the function the trainer
service calls, once, on the packed corpus made from the seed (the
corpus recipe is ``runners/lfm2_moe.py``'s: the two sequence kinds'
cells differ by the model alone)."""

from __future__ import annotations

from benchmarks import instrument
from benchmarks.runners.lfm2_moe import traffic  # noqa: F401
# At the top, so that a program without this kind fails the cell at
# once, before any traffic is made.
from dragonfly2_tpu.models.laguna import LagunaConfig
from dragonfly2_tpu.train import seq_trainer


def drive(spec: dict, arrays: dict, seed: int, plan, mesh, wrap_step) -> None:
    held, o = spec["deployment"], spec["optimizer"]
    model = LagunaConfig.from_published(
        spec, num_experts=spec["published"]["num_experts"],
        vocab_size=spec["published"]["vocab_size"],
        layers=tuple(held["layers_kept"]),
        experts_held=tuple(held["experts_held"]),
        vocab_held=tuple(held["vocab_rows_held"]))
    if model.compute_dtype != spec["compute_dtype"]:
        raise RuntimeError(f"the configuration states {spec['compute_dtype']}"
                           f"; the program computes in {model.compute_dtype}")
    config = seq_trainer.SeqTrainConfig(
        model=model, batch_size=spec["batch"],
        learning_rate=o["learning_rate"], weight_decay=o["weight_decay"],
        epochs=spec["epochs"], seed=seed, max_seconds=plan.seconds)
    corpus = seq_trainer.SeqCorpus(
        arrays["tokens"], arrays["segments"], arrays["positions"])
    with instrument.window_budget(plan, seq_trainer), \
            instrument.observed_jit(seq_trainer, "train_step", wrap_step):
        seq_trainer.train_seq(corpus, config, mesh)
