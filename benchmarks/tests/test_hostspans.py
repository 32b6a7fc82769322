"""The readers of the program's own spans and counters (``hostspans.py``,
``metrics/host_step_ms.py``, ``input_wait_ms.py``, ``loop_compiles.py``):
their arithmetic on span lists made by hand, and on a small trace that
a CPU rehearsal of the GraphSAGE cell records here."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import hostspans

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NEW_CELL = "sage-fleet100k.train.dp4"
MS = 1e6  # spans are in ns


def span(name, start_ms, stop_ms):
    return ("df2.train." + name, start_ms * MS, stop_ms * MS)


def test_host_step_ms_leaves_out_where_the_loop_may_block():
    loop = (
        span("wait_input", 0, 1),
        span("step", 1, 11), span("dispatch", 1, 2), span("tick", 2, 10.5),
        span("wait_input", 11, 11.5),
        # An epoch ends inside this step: a host sync of 3 ms.
        span("step", 11.5, 20), span("epoch_end", 11.5, 14.5),
        span("dispatch", 14.5, 15), span("tick", 15, 19),
        # Outside every step (train_gat's place for it): not subtracted,
        # since it was never added.
        span("epoch_end", 20, 25), span("drain", 25, 26),
    )
    workers = ((span("input", 0, 0.4), span("input", 5, 5.2)),
               (span("input", 2, 2.3),))
    # Steps 10 + 8.5; ticks 8.5 + 4 and the nested epoch_end 3 leave 3;
    # the workers add 0.9; over 2 steps.
    assert hostspans.host_step_ms((loop, *workers)) == pytest.approx(1.95)
    assert hostspans.input_wait_ms((loop, *workers)) == pytest.approx(0.75)


def test_inputs_on_the_loops_own_thread_count_once():
    """``train_gat`` builds its inputs inline, inside the step span."""
    loop = (span("step", 0, 5), span("input", 0, 1), span("dispatch", 1, 2),
            span("tick", 2, 4.5))
    assert hostspans.host_step_ms((loop,)) == pytest.approx(2.5)
    # No wait span in that loop: nothing to read, not zero.
    assert hostspans.input_wait_ms((loop,)) is None


def test_a_program_without_the_spans_gives_nothing():
    assert hostspans.host_step_ms(()) is None
    assert hostspans.input_wait_ms(()) is None
    assert hostspans.host_step_ms(((span("input", 0, 1),),)) is None


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """One traced CPU rehearsal of the one-chip GraphSAGE cell: its
    result line, and its trace under ``.bench_trace/``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(
        tmp_path_factory.mktemp("jax_cache")))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload",
         "sage-fleet100k.train", "--seed", "2147483659", "--seconds", "0.5",
         "--trace", "1", "--rehearse"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_readers_on_a_rehearsal_trace(rehearsed):
    path = hostspans.newest_trace()
    assert os.sep + "sage-fleet100k.train" + os.sep in path
    threads = hostspans.threads_of(path)
    loop, workers = threads[0], threads[1:]
    steps = sum(name == hostspans.STEP for name, _, _ in loop)
    # The trace opens and closes inside a step's tick, so it holds the
    # window's steps but the one it closes in.
    assert rehearsed["run"]["steps"] - 1 <= steps <= rehearsed["run"]["steps"]
    assert workers and all(
        name == "df2.train.input" for spans in workers for name, _, _ in spans)
    names = {name for name, _, _ in loop}
    assert {"df2.train.step", "df2.train.wait_input", "df2.train.dispatch",
            "df2.train.tick"} <= names
    metrics = rehearsed["metrics"]
    assert metrics["host_step_ms"]["value"] == pytest.approx(
        hostspans.host_step_ms(threads))
    assert metrics["input_wait_ms"]["value"] == pytest.approx(
        hostspans.input_wait_ms(threads))
    dispatch = hostspans.total_ms(loop, "df2.train.dispatch") / steps
    assert 0 < dispatch < metrics["host_step_ms"]["value"]
    # The step program and nothing of the harness's (since PR 26 the
    # observer fetches, which compiles nothing, and no reduction is
    # warmed): this loop's count is 1.
    assert metrics["loop_compiles"]["value"] == 1
    # The gaps of the breakdown can now be named by the program's spans.
    assert all(isinstance(name, str)
               for name, _ in rehearsed["breakdown"]["idle_gaps"])


def test_loop_compiles_reads_the_training_block():
    from benchmarks.metrics import loop_compiles
    from dragonfly2_tpu.train import step_budget

    import jax
    import numpy as np

    budget = step_budget.StepBudget()
    jax.jit(lambda x: x + 41)(np.arange(3.0)).block_until_ready()
    budget.finish()
    assert loop_compiles.read({"trace": None}) == (
        step_budget.TRAINING.snapshot()["loop_compiles"]) >= 1


def test_the_new_cell_is_declared_as_the_issue_names_it():
    cell = next(w for w in BENCH["workloads"] if w["name"] == NEW_CELL)
    assert cell["chips"] == 4 and cell["config"] == "sage-fleet100k"
    assert cell["traffic"] == "train.dp4"
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           NEW_CELL + ".json")) as fh:
        workload = json.load(fh)
    assert workload["batch_per_chip"] is True
    assert list(workload["limits"]) == list(workload["rehearse_limits"])
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           "sage-fleet100k.train.json")) as fh:
        one_chip = json.load(fh)
    assert set(workload["limits"]) == set(one_chip["limits"])
    for key in ("compare_steps", "warm_steps", "epochs", "eval_fraction"):
        assert workload[key] == one_chip[key]
    reports = {m["name"] for m in BENCH["per_layer"]
               if NEW_CELL in m.get("workloads", [NEW_CELL])}
    assert {"host_step_ms", "input_wait_ms", "loop_compiles"} <= reports
    assert "gat-fleet50k.train" not in next(
        m for m in BENCH["per_layer"]
        if m["name"] == "input_wait_ms")["workloads"]
