"""A model kind the harness has never seen goes in from new files alone
(``standin/``: a runner with traffic of its own and no ``fleet``, a
plain reference, counts, a configuration, a cell) and is rehearsed
through ``run.run_cell`` at about 20M parameters. What the harness
holds of the model's state after the window grows with it by six trees
at the most on the host, the observer's five among them, and by two on
the device between the reference's programs: after the compared steps
nothing of the observer's is on the device, the comparison drops each
tree once its numbers are taken, and the reference keeps its parameters
and the one gradient it is making."""

import gc
import tracemalloc

import pytest


@pytest.fixture(autouse=True)
def _cache_outside_the_checkout(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def test_a_kind_from_new_files_alone(tmp_path, monkeypatch):
    import jax

    from benchmarks import compare, run
    from benchmarks.tests.standin import place, wide_mlp_program

    strays, names = {}, []

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

    class Named(compare.Fold):
        def initial(self, params):
            names.extend((k, v.shape) for k, v in params.items())
            super().initial(params)

    monkeypatch.setattr(wide_mlp_program, "PROBE", probe)
    monkeypatch.setattr(compare, "Fold", Named)
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
    assert max(shape for _, shape in names) == (2048, 2048)
    assert len(names) == 12
    assert strays["arrays"] == []


def _held_after_the_window(tmp_path, monkeypatch, fold, layers: int) -> dict:
    """One rehearsal of the stand-in at ``layers`` layers of 1024 x 1024
    under ``tracemalloc``: the parameters, the host's traced peak from
    the window's close to the last number over what was traced before
    the run (numpy's arrays included, the observer's trees among them)
    and the most device bytes live at a look between the reference's
    programs."""
    import jax

    import importlib

    from benchmarks import compare, run
    from benchmarks.tests.standin import place

    seen = {"device": 0, "params": 0}

    def look():
        if seen.get("after"):
            gc.collect()
            seen["device"] = max(seen["device"], sum(
                a.nbytes for a in jax.live_arrays()))

    class Measured(fold):
        def __init__(self, program):
            gc.collect()
            tracemalloc.reset_peak()
            seen["after"] = True
            super().__init__(program)

        def initial(self, params):
            seen["params"] = sum(v.size for v in params.values())
            look()
            super().initial(params)

        def logit(self, tree):
            look()
            super().logit(tree)

        def gradient(self, step, loss, tree):
            look()
            super().gradient(step, loss, tree)

        def final(self, after, before):
            look()
            super().final(after, before)

        def numbers(self):
            out = super().numbers()
            seen["host"] = tracemalloc.get_traced_memory()[1]
            return out

    plain = run.load_cell

    def sized(name, rehearse):
        bench, cell, workload, spec = plain(name, rehearse)
        model = dict(spec["model"], width=1024, layers=layers)
        return bench, cell, workload, dict(spec, model=model)

    monkeypatch.setattr(compare, "Fold", Measured)
    monkeypatch.setattr(run, "load_cell", sized)
    with place.placed(str(tmp_path / f"at{layers}")):
        monkeypatch.setattr(importlib.import_module(
            "benchmarks.references.wide_mlp"), "PROBE", look)
        gc.collect()
        before = tracemalloc.get_traced_memory()[0]
        result = run.run_cell(place.CELL, 3000000019, 0.3, False,
                              rehearse=True)
    assert result["correct"] is True, result["compared"]
    seen["host"] -= before
    return seen


def test_what_is_held_after_the_window_grows_by_six_trees_at_most(
        tmp_path, monkeypatch):
    """At two sizes, 8M and 33M parameters (the same leaves, more of
    them): what the host holds after the window grows by 24 bytes a
    parameter at the most (6P float32, the observer's 5P among them) and
    what the device holds between the reference's programs by 8 (its
    parameters and the gradient being made)."""
    from benchmarks import compare

    fold = compare.Fold
    tracemalloc.start()
    try:
        small = _held_after_the_window(tmp_path, monkeypatch, fold, 8)
        large = _held_after_the_window(tmp_path, monkeypatch, fold, 32)
    finally:
        tracemalloc.stop()
    more = large["params"] - small["params"]
    assert more > 20_000_000
    host = (large["host"] - small["host"]) / more
    device = (large["device"] - small["device"]) / more
    # The observer alone holds 5P (20 bytes a parameter), less what the
    # first run left behind and the second freed: the measurement sees
    # it.
    assert 16 <= host <= 24, host
    assert 8 - 0.5 <= device <= 8, device


def test_the_comparisons_own_peak_is_some_slices_a_thread(monkeypatch):
    """What the comparison allocates on the host beyond the trees it is
    handed, at the stand-in's 2048 x 2048 leaves walked in slices of
    2**16 elements (64 a leaf): at most four float64 slices a thread, at
    2 layers and at 6 alike, well under one leaf of float64 (so a walk
    of whole leaves fails it) and under one tree."""
    import numpy as np

    from benchmarks import compare

    monkeypatch.setattr(compare, "SLICE", 1 << 16)
    bound = 4 * 8 * compare.SLICE * compare.THREADS
    rng = np.random.default_rng(5)

    def peak(layers: int) -> tuple[int, int]:
        shapes = {f"layer_{i}/{part}": shape for i in range(layers)
                  for part, shape in (("kernel", (2048, 2048)),
                                      ("bias", (2048,)))}
        shapes.update({"head/kernel": (2048, 1), "head/bias": (1,)})
        # Two trees that the test holds in every role: what the fold
        # drops stays traced, so only the fold's own allocations count.
        a, b = ({k: rng.standard_normal(s, dtype=np.float32)
                 for k, s in shapes.items()} for _ in range(2))
        fold = compare.Fold({"params_before": dict(a),
                             "params_after": dict(b),
                             "moments": [dict(b), dict(a), dict(b)],
                             "losses": [1.0, 0.9, 0.8]})
        most = 0
        calls = [(fold.initial, a), (fold.logit, b),
                 (fold.half, 0, b), (fold.gradient, 0, 1.0, a),
                 (fold.half, 1, a), (fold.gradient, 1, 0.9, b),
                 (fold.gradient, 2, 0.8, a), (fold.final, b, a)]
        for call, *args in calls:
            gc.collect()
            tracemalloc.reset_peak()
            start = tracemalloc.get_traced_memory()[0]
            call(*args)
            most = max(most, tracemalloc.get_traced_memory()[1] - start)
        assert set(fold.numbers()) >= {"init_gap", "grad_diff_scaled"}
        return most, sum(v.nbytes for v in a.values())

    tracemalloc.start()
    try:
        small, large = peak(2), peak(6)
    finally:
        tracemalloc.stop()
    for most, tree in (small, large):
        assert most <= bound, (most, bound)
    assert bound < 8 * 2048 * 2048 and bound < large[1], bound
