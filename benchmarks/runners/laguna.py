"""Runner for the ``laguna`` kind: the window drives
``train/seq_trainer.py``'s ``train_seq``, the function the trainer
service calls, once, on the packed corpus made from the seed."""

from __future__ import annotations

import numpy as np

from benchmarks import instrument
from benchmarks.runners.lfm2_moe import token_table
# At the top, so that a program without this kind fails the cell at
# once, before any traffic is made.
from dragonfly2_tpu.models.laguna import LagunaConfig
from dragonfly2_tpu.train import seq_trainer


def traffic(spec: dict, seed: int) -> dict:
    """The cell's inputs from the seed: ``tokens``, ``segments``,
    ``positions`` as ``[R, S]`` int32, what a packer emits. Every row
    holds the same documents, ``corpus.document_lengths`` (they fill a
    row exactly), in that order, and the seed draws each position's id
    from the fixed table; positions restart at each document. Same seed,
    same arrays; every seed, and every row, the same documents at the
    same places: a step attends the same pairs, and the attention keeps
    the same tiles, whatever rows it draws and whatever their tiling."""
    corpus, seq_len = spec["corpus"], spec["seq_len"]
    lengths = np.asarray(corpus["document_lengths"], np.int64)
    rows = corpus["tokens"] // seq_len
    if lengths.sum() != seq_len or rows * seq_len != corpus["tokens"]:
        raise ValueError(f"documents of {lengths.tolist()} tokens do not "
                         f"fill rows of {seq_len}")
    first, held = spec["deployment"]["vocab_rows_held"]
    rng = np.random.default_rng(seed)
    drawn = np.searchsorted(token_table(corpus, held),
                            rng.random(corpus["tokens"]))
    tokens = (first + np.minimum(drawn, held - 1)).astype(np.int32)
    flat = np.tile(lengths, rows)
    segments = np.repeat(np.arange(len(flat), dtype=np.int32), flat)
    starts = np.repeat(np.cumsum(flat) - flat, flat)
    positions = (np.arange(corpus["tokens"]) - starts).astype(np.int32)
    tokens, segments, positions = (
        a.reshape(rows, seq_len) for a in (tokens, segments, positions))
    return {"tokens": tokens, "segments": segments, "positions": positions}


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
