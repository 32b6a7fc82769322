"""Runner for the ``lfm2_moe`` kind: the window drives
``train/seq_trainer.py``'s ``train_seq``, the function the trainer
service calls, once, on the packed corpus made from the seed."""

from __future__ import annotations

from statistics import NormalDist

import numpy as np

from benchmarks import instrument
from benchmarks.references.lfm2_moe import selection_bias
# At the top, so that a program without this kind fails the cell at
# once, before any traffic is made.
from dragonfly2_tpu.train import seq_trainer


def document_lengths(corpus: dict) -> np.ndarray:
    """The corpus's document lengths, a sequence fixed by the file:
    ``documents`` evenly spaced quantiles of a lognormal (``median``,
    ``sigma``) cut to ``min`` .. ``max``; what they sum to over
    ``tokens`` is taken off the last and longest."""
    n = corpus["documents"]
    normal = NormalDist()
    quantiles = np.array([normal.inv_cdf((i + 0.5) / n) for i in range(n)])
    lengths = np.clip(
        np.rint(corpus["median"] * np.exp(corpus["sigma"] * quantiles)),
        corpus["min"], corpus["max"]).astype(np.int64)
    lengths[-1] -= lengths.sum() - corpus["tokens"]
    if lengths[-1] < corpus["min"] or lengths[-1] > corpus["max"]:
        raise ValueError(f"{n} documents do not sum to {corpus['tokens']} "
                         "tokens within one document's length")
    return lengths


def token_table(corpus: dict, ids: int) -> np.ndarray:
    """Cumulative Zipf-Mandelbrot shares of the ``ids`` token ids held:
    id ``r - 1`` has weight ``1 / (r + offset) ** exponent``."""
    ranks = np.arange(1, ids + 1, dtype=np.float64)
    weights = (ranks + corpus["offset"]) ** -corpus["exponent"]
    return np.cumsum(weights / weights.sum())


def traffic(spec: dict, seed: int) -> dict:
    """The cell's inputs from the seed: ``tokens``, ``segments``,
    ``positions`` as ``[R, S]`` int32, what a packer emits. The seed
    deals the fixed length sequence into an order and draws each
    position's id from the fixed table; documents are concatenated and
    cut into rows of ``seq_len`` with no padding (a document cut at a
    row's end becomes two). Same seed, same arrays; every seed the same
    shapes, the same lengths, the same token shares."""
    corpus, seq_len = spec["corpus"], spec["seq_len"]
    first, held = spec["deployment"]["vocab_rows_held"]
    rng = np.random.default_rng(seed)
    lengths = rng.permutation(document_lengths(corpus))
    rows = corpus["tokens"] // seq_len
    drawn = np.searchsorted(token_table(corpus, held),
                            rng.random(corpus["tokens"]))
    tokens = (first + np.minimum(drawn, held - 1)).astype(np.int32)
    segments = np.repeat(np.arange(len(lengths), dtype=np.int32), lengths)
    starts = np.repeat(np.cumsum(lengths) - lengths, lengths)
    positions = (np.arange(corpus["tokens"]) - starts).astype(np.int32)
    tokens, segments, positions = (
        a.reshape(rows, seq_len) for a in (tokens, segments, positions))
    # The document a row begins in the middle of starts anew there.
    cut = segments == segments[:, :1]
    positions = np.where(cut, positions - positions[:, :1], positions)
    return {"tokens": tokens, "segments": segments, "positions": positions}


def drive(spec: dict, arrays: dict, seed: int, plan, mesh, wrap_step) -> None:
    from dragonfly2_tpu.models.lfm2_moe import Lfm2MoeConfig

    held, o = spec["deployment"], spec["optimizer"]
    model = Lfm2MoeConfig.from_published(
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
        epochs=spec["epochs"], seed=seed,
        router_bias=tuple(selection_bias(spec).tolist()),
        max_seconds=plan.seconds)
    corpus = seq_trainer.SeqCorpus(
        arrays["tokens"], arrays["segments"], arrays["positions"])
    with instrument.window_budget(plan, seq_trainer), \
            instrument.observed_jit(seq_trainer, "train_step", wrap_step):
        seq_trainer.train_seq(corpus, config, mesh)
