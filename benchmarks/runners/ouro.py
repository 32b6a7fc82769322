"""Runner for the ``ouro`` kind: the window drives
``train/seq_trainer.py``'s ``train_seq``, the function the trainer
service calls, once, on the packed corpus made from the seed (the
corpus recipe is ``runners/keye_vl2.py``'s: every row holds the same
documents, dealt into an order by the seed)."""

from __future__ import annotations

from benchmarks import instrument
from benchmarks.runners.keye_vl2 import traffic  # noqa: F401
# At the top, so that a program without this kind fails the cell at
# once, before any traffic is made.
from dragonfly2_tpu.models.ouro import OuroConfig
from dragonfly2_tpu.train import seq_trainer


def drive(spec: dict, arrays: dict, seed: int, plan, mesh, wrap_step) -> None:
    held, o, published = (spec["deployment"], spec["optimizer"],
                          spec["published"])
    model = OuroConfig.from_published(
        spec, vocab_size=published["vocab_size"],
        num_hidden_layers=published["num_hidden_layers"],
        layers=tuple(held["layers_kept"]),
        vocab_held=tuple(held["vocab_rows_held"]))
    if model.compute_dtype != spec["compute_dtype"]:
        raise RuntimeError(f"the configuration states {spec['compute_dtype']}"
                           f"; the program computes in {model.compute_dtype}")
    config = seq_trainer.SeqTrainConfig(
        model=model, batch_size=spec["batch"], seq_len=spec["seq_len"],
        learning_rate=o["learning_rate"], weight_decay=o["weight_decay"],
        epochs=spec["epochs"], seed=seed, max_seconds=plan.seconds)
    corpus = seq_trainer.SeqCorpus(
        arrays["tokens"], arrays["segments"], arrays["positions"])
    with instrument.window_budget(plan, seq_trainer), \
            instrument.observed_jit(seq_trainer, "train_step", wrap_step):
        seq_trainer.train_seq(corpus, config, mesh)
