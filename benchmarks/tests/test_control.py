"""The comparison that decides ``correct`` has to fail what it should:
the control (the reference in the program's place, computed in fp8
where the configurations state bfloat16) and each fault a one-chip
training cell can have, planted under the harness with the look for a
chip skipped. At the configurations' ``rehearse`` sizes; the chip
readings at the cells' own sizes are in PERF.md."""

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


@pytest.fixture(autouse=True)
def _cache_outside_the_checkout(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def _unchanged_state(jitted):
    """A step that returns its state unchanged."""
    import jax
    import jax.numpy as jnp

    def step(state, *rest):
        kept = jax.tree.map(jnp.copy, state)
        _, loss = jitted(state, *rest)
        return kept, loss
    return step


def _half_batch(jitted):
    """Half of the batch left out, the mean taken over the rest."""
    def step(*args):
        if len(args) == 8:      # train_gat: ..., src [k, B], dst, labels
            head, batch = args[:5], [a[:, :a.shape[1] // 2] for a in args[5:]]
            return jitted(*head, *batch)
        state, graph, edges, edge_ids, key = args      # train_gnn, fused
        return jitted(state, graph, edges, edge_ids[:len(edge_ids) // 2], key)
    return step


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch])
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_step_is_not_correct(cell, fault):
    from benchmarks import run

    result = run.run_cell(cell, 41, 0.5, False, rehearse=True,
                          wrap_fault=fault)
    assert result["correct"] is False, result["compared"]
    failed = [k for k, c in result["compared"].items()
              if c["value"] > c["limit"]]
    assert failed, result["compared"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_sound_program_is_correct(cell):
    from benchmarks import run

    result = run.run_cell(cell, 41, 0.5, False, rehearse=True)
    assert result["correct"] is True, result["compared"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_and_the_faults_are_not_correct(cell):
    """``limits.py``'s study, as it is run on the chip at the cell's own
    size: the reference in the program's place, in the nearest precision
    under the one the configuration states, with half of the batch left
    out, and with its state unchanged, each through the harness's
    ``verdict`` under the limits the sound program passes."""
    from benchmarks import limits, run

    for seed in (41, 42, 43):
        study = run.run_cell(cell, seed, 0.3, False, rehearse=True,
                             study=limits.control_study)["study"]
        assert study.pop("program")["correct"] is True
        assert [who for who, found in study.items() if found["correct"]] == []


@pytest.mark.parametrize("cell", CELLS)
def test_the_same_numbers_are_held_at_both_sizes(cell):
    """What carries a fault in the committed cell is what the tests
    above put through ``verdict``."""
    from benchmarks import run

    _, _, workload, _ = run.load_cell(cell, rehearse=True)
    assert list(workload["rehearse_limits"]) == list(workload["limits"])
