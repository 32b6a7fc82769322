"""StepBudget window accounting — the arithmetic behind every published
samples/sec number (compile exclusion, mid-run new-program exclusion,
deadline shifting). Timing uses real sleeps with coarse bounds so the
assertions hold on a loaded single-core box.
"""
import time

import pytest

from dragonfly2_tpu.train.step_budget import StepBudget


def run_steps(budget, n, batch=10, dt=0.0):
    for _ in range(n):
        if dt:
            time.sleep(dt)
        budget.tick(batch, object())


class TestCompileExclusion:
    def test_first_step_excluded(self):
        b = StepBudget()
        time.sleep(0.15)          # "compile"
        b.tick(10, object())      # first step: no samples counted
        run_steps(b, 5, dt=0.01)
        b.finish()
        assert b.compile_seconds >= 0.15
        assert b.samples == 50
        # window covers only the 5 steady steps, not the 150ms compile
        assert b._elapsed < 0.15

    def test_new_program_excluded_and_deadline_shifted(self):
        b = StepBudget(max_seconds=10.0)
        b.tick(10, object())
        run_steps(b, 3, dt=0.01)
        deadline_before = b._deadline
        compile_before = b.compile_seconds
        b.sync_point(object())
        time.sleep(0.2)           # "tail-scan compile"
        b.tick(10, object(), new_program=True)
        run_steps(b, 3, dt=0.01)
        b.finish()
        excluded = b.compile_seconds - compile_before
        assert excluded >= 0.2
        # the excluded window shifts the deadline by the same amount
        assert b._deadline == pytest.approx(deadline_before + excluded)
        # new-program samples are not counted; 6 steady steps are
        assert b.samples == 60
        # the throughput window excludes the 200ms compile
        assert b._elapsed < 0.2

    def test_rate_unaffected_by_mid_run_compile(self):
        b = StepBudget()
        b.tick(100, object())
        run_steps(b, 4, batch=100, dt=0.02)
        b.sync_point(object())
        time.sleep(0.3)
        b.tick(100, object(), new_program=True)
        run_steps(b, 4, batch=100, dt=0.02)
        b.finish()
        rate = b.samples_per_sec(100)
        # 8 steady steps of ~20ms each -> ~5000 samples/s; a leaked
        # 300ms exclusion would drag it under 1800
        assert rate > 1800


class TestPairingEnforced:
    def test_new_program_without_sync_raises(self):
        b = StepBudget()
        b.tick(10, object())
        b.tick(10, object())
        with pytest.raises(RuntimeError, match="sync_point"):
            b.tick(10, object(), new_program=True)

    def test_sync_consumed_by_tick(self):
        b = StepBudget()
        b.tick(10, object())
        b.sync_point(object())
        b.tick(10, object(), new_program=True)
        with pytest.raises(RuntimeError, match="sync_point"):
            b.tick(10, object(), new_program=True)

    def test_first_step_needs_no_sync(self):
        b = StepBudget()
        b.tick(10, object(), new_program=True)  # steps==0 path wins
        assert b.steps == 1


class TestDeadline:
    def test_budget_exhaustion(self):
        b = StepBudget(max_seconds=0.05)
        b.tick(10, object())
        time.sleep(0.08)
        assert b.tick(10, object()) is True


class TestSetupPhases:
    """``setup_phase``: a trainer's set-up measured from inside (the
    ``training`` block's ``setup_<phase>_seconds`` and ``setup_compiles``,
    the ``df2.setup.<phase>`` span), and where JAX's compile events are
    attributed."""

    def test_phase_adds_its_seconds_and_opens_its_annotation(
            self, monkeypatch):
        import jax

        from dragonfly2_tpu.train.step_budget import TRAINING, setup_phase

        opened = []

        class Annotation:
            def __init__(self, name, **_):
                opened.append(name)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
        before = TRAINING.snapshot()
        with setup_phase("tables") as placed:
            x = jax.numpy.arange(3)
            assert placed(x) is x
            time.sleep(0.05)
        # Opened twice, the seconds add up.
        with setup_phase("tables"):
            time.sleep(0.05)
        after = TRAINING.snapshot()
        assert opened == ["df2.setup.tables"] * 2
        assert after["setup_tables_seconds"] - before[
            "setup_tables_seconds"] >= 0.1
        assert after["setup_data_seconds"] == before["setup_data_seconds"]

    def test_a_phase_that_raises_is_closed(self):
        from dragonfly2_tpu.train.step_budget import TRAINING, setup_phase

        before = TRAINING.snapshot()["setup_data_seconds"]
        with pytest.raises(ValueError, match="records"):
            with setup_phase("data"):
                raise ValueError("bad records")
        with setup_phase("data"):
            pass
        assert TRAINING.snapshot()["setup_data_seconds"] > before

    @pytest.mark.parametrize("where", ["nested", "another_thread"])
    def test_phases_do_not_nest_or_overlap(self, where):
        import threading

        from dragonfly2_tpu.train.step_budget import setup_phase

        if where == "nested":
            with setup_phase("data"):
                with pytest.raises(RuntimeError, match="do not nest"):
                    with setup_phase("state"):
                        pass
            return
        inside, leave = threading.Event(), threading.Event()

        def hold():
            with setup_phase("data"):
                inside.set()
                leave.wait(30)

        thread = threading.Thread(target=hold)
        thread.start()
        try:
            assert inside.wait(30)
            with pytest.raises(RuntimeError, match="do not nest"):
                with setup_phase("tables"):
                    pass
        finally:
            leave.set()
            thread.join(30)

    def test_no_phase_while_a_loop_runs(self):
        from dragonfly2_tpu.train.step_budget import setup_phase

        budget = StepBudget()
        with pytest.raises(RuntimeError, match="while a train loop runs"):
            with setup_phase("state"):
                pass
        budget.finish()
        with setup_phase("state"):
            pass

    def test_a_loop_that_raised_does_not_hold_the_next_set_up(self):
        """A loop that raised never reaches ``finish``; where a reference
        cycle (a traceback's frames) keeps its budget, the next set-up
        still opens."""
        from dragonfly2_tpu.train.step_budget import setup_phase

        class Frame:
            pass

        frame = Frame()
        frame.cycle, frame.budget = frame, StepBudget()
        del frame
        with setup_phase("data"):
            pass

    def test_no_loop_starts_inside_a_phase(self):
        from dragonfly2_tpu.train.step_budget import setup_phase

        with setup_phase("state"):
            with pytest.raises(RuntimeError, match="inside the set-up"):
                StepBudget()

    def test_an_unknown_phase_is_refused(self):
        from dragonfly2_tpu.train.step_budget import setup_phase

        with pytest.raises(ValueError, match="one of"):
            with setup_phase("warmup"):
                pass

    @pytest.mark.parametrize("where", ["phase", "loop", "neither"])
    def test_compile_events_are_attributed_where_they_happen(self, where):
        import contextlib

        import jax
        import numpy as np

        from dragonfly2_tpu.train.step_budget import TRAINING, setup_phase

        before = TRAINING.snapshot()
        budget = StepBudget() if where == "loop" else None
        with (setup_phase("state") if where == "phase"
              else contextlib.nullcontext()):
            # A function of its own: traced, lowered and compiled here.
            jax.jit(lambda x: x * 11 + 3)(np.arange(5.0)).block_until_ready()
        if budget is not None:
            budget.finish()
        after = TRAINING.snapshot()
        built = after["setup_compiles"] - before["setup_compiles"]
        seconds = (after["loop_compile_seconds"]
                   - before["loop_compile_seconds"])
        assert (built >= 1) if where == "phase" else (built == 0)
        assert (seconds > 0) if where == "loop" else (seconds == 0)
        if where == "loop":
            assert after["loop_compiles"] - before["loop_compiles"] == 1

    def test_a_trace_nested_in_another_counts_once(self):
        from dragonfly2_tpu.train.step_budget import TRAINING

        budget = StepBudget()
        before = TRAINING.snapshot()["loop_compile_seconds"]
        # As JAX reports them: an inner jit's trace ends (and is
        # reported) before the trace that holds it.
        for start, end in [(10.0, 11.0), (12.0, 12.5), (11.5, 13.0),
                           (9.0, 14.0), (20.0, 21.0)]:
            TRAINING.compile_span(start, end)
        budget.finish()
        TRAINING.compile_span(30.0, 40.0)  # after the loop: nowhere
        assert TRAINING.snapshot()["loop_compile_seconds"] - before == (
            pytest.approx(6.0))
