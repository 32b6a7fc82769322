"""A model kind the harness has never seen goes in from new files alone
(``standin/``: a runner with traffic of its own and no ``fleet``, a
plain reference, counts, a configuration, a cell) and is rehearsed
through ``run.run_cell`` at about 20M parameters. What the harness
holds of the model's state must not grow with it beyond one leaf: after
the compared steps nothing of the observer's is on the device, and the
comparison works one leaf at a time."""

import gc
import tracemalloc

import pytest


@pytest.fixture(autouse=True)
def _cache_outside_the_checkout(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def test_a_kind_from_new_files_alone(tmp_path, monkeypatch):
    import jax
    import numpy as np

    from benchmarks import compare, run
    from benchmarks.tests.standin import place, wide_mlp_program

    strays, peaks, largest = {}, {}, {}

    def probe(budget, own):
        # The step after the last compared one: the observer has taken
        # all it takes.
        if budget._seen != compare_steps + 1:
            return
        gc.collect()
        mine = {id(a) for a in own}
        strays["arrays"] = [
            (a.shape, a.dtype) for a in jax.live_arrays()
            if id(a) not in mine and a.size > 1]

    plain = compare.numbers

    def traced(program, reference):
        largest["bytes"] = 8 * max(
            np.size(v) for v in compare.leaves(
                reference["params_before"]).values())
        gc.collect()
        tracemalloc.start()
        try:
            return plain(program, reference)
        finally:
            peaks["bytes"] = tracemalloc.get_traced_memory()[1]
            tracemalloc.stop()

    monkeypatch.setattr(wide_mlp_program, "PROBE", probe)
    monkeypatch.setattr(compare, "numbers", traced)
    with place.placed(str(tmp_path)):
        _, _, workload, spec = run.load_cell(place.CELL, rehearse=True)
        compare_steps = workload["compare_steps"]
        assert "fleet" not in spec
        result = run.run_cell(place.CELL, 3000000019, 0.5, False,
                              rehearse=True)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"train_samples_per_s", "setup_s"}
    # 5 layers of 2048 x 2048 and a head: 21M parameters.
    assert largest["bytes"] == 8 * 2048 * 2048
    assert strays["arrays"] == []
    assert peaks["bytes"] < 4 * largest["bytes"], peaks
