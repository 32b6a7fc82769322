"""What the harness puts around the program's train loops.

The window drives the program's own entry (``train_gat``,
``train_gnn``; a kind's runner names it): one call builds the state,
compiles the step, runs the set-up steps and then the measured window,
all on that one object, with ``max_seconds`` set from ``--seconds``. The program exposes no hook for that yet (PERF.md lists
what the ``tracing`` issue should add), so two of its names are swapped
for the length of the call:

- ``StepBudget`` → a subclass of it made by :func:`observed_budget`.
  The program's own ``tick`` keeps the count of samples and decides,
  by its own deadline, when the loop stops. The subclass hides the
  set-up steps from it (the compile, the steps the reference follows,
  the warm steps that are dropped), so that the program's budget meets
  its "first step" where the window opens: it drains the device there,
  as it does after a compile, and starts its clock and its deadline.
  Besides that the subclass keeps the harness's clock beside the
  program's and holds the host to :data:`MAX_IN_FLIGHT` steps ahead of
  the device, which the program does not do.
- the jitted train step → :class:`StepObserver`, which fetches what the
  comparison needs out of the first steps' states to the host before
  the next call donates them, and otherwise only forwards the call.
  Where the program renames ``train_step`` no observer is built, and
  the run fails saying so (``run.py``) rather than carry on unobserved.

Neither changes an argument, a result or the order of calls.
"""

from __future__ import annotations

import collections
import contextlib
import time

import jax
import numpy as np

# Steps the host may be ahead of the device. The program's loops have
# no such limit: the runtime lets dozens of steps queue, the program's
# deadline is checked at dispatch, and a 10 s window would then drain
# for as long again. With 4 the device always has 1.2-2 s of work
# waiting (2 was too few: PERF.md section 6) and the window closes
# within four steps of its deadline.
MAX_IN_FLIGHT = 4


class WindowPlan:
    """The shape of one run: how long a window, how many set-up steps
    before it, and the readings the budget leaves behind."""

    def __init__(self, seconds: float, compare_steps: int, warm_steps: int,
                 on_open=None, on_close=None):
        self.seconds = float(seconds)
        self.setup_steps = int(compare_steps) + int(warm_steps)
        self.on_open = on_open
        self.on_close = on_close
        self.t_open = None
        self.t_close = None
        self.samples = 0
        self.steps = 0
        self.compile_seconds = None

    @property
    def window_seconds(self) -> float:
        return self.t_close - self.t_open


def observed_budget(program_budget, plan: WindowPlan):
    """The program's ``StepBudget``, observed: see the module's text."""

    class ObservedBudget(program_budget):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            if self.max_seconds != plan.seconds:
                raise RuntimeError(
                    f"the runner passed max_seconds={self.max_seconds}; the "
                    f"window is {plan.seconds} s")
            self._seen = 0
            self._created = time.perf_counter()
            self._in_flight = collections.deque()

        def sync_point(self, prev_output) -> None:
            if self._seen:
                raise RuntimeError(
                    "the train loop is about to dispatch a second program "
                    "shape mid-run; a cell's traffic must not compile after "
                    "set-up")

        def _throttle(self, output) -> None:
            self._in_flight.append(output)
            if len(self._in_flight) > MAX_IN_FLIGHT:
                jax.block_until_ready(self._in_flight.popleft())

        def tick(self, n_samples: int, output, new_program: bool = False):
            if new_program and self._seen:
                raise RuntimeError(
                    "a second program shape was dispatched mid-run")
            self._seen += 1
            if self._seen == 1:
                jax.block_until_ready(output)
                plan.compile_seconds = time.perf_counter() - self._created
            if self._seen < plan.setup_steps:
                self._throttle(output)
                return False
            if self._seen == plan.setup_steps:
                jax.block_until_ready(output)
                self._in_flight.clear()
                if plan.on_open is not None:
                    plan.on_open()
                # The program's budget has seen no step yet: this is its
                # first, on which it starts its clock and its deadline.
                stop = super().tick(n_samples, output)
                plan.t_open = time.perf_counter()
                return stop
            self._throttle(output)
            stop = super().tick(n_samples, output)
            plan.samples, plan.steps = self.samples, self.steps - 1
            if stop:
                jax.block_until_ready(output)
                plan.t_close = time.perf_counter()
                if plan.on_close is not None:
                    plan.on_close()
            return stop

        def finish(self) -> None:
            if plan.t_close is None:
                raise RuntimeError(
                    "the train loop ended before the window closed: too few "
                    "epochs for the window, or fewer steps than set-up "
                    "needs")
            super().finish()

    return ObservedBudget


def _fetch(tree):
    """The tree on the host, sharing nothing with the device: on the CPU
    backend ``device_get`` hands out views of the buffers, and a view
    keeps a buffer alive that the next call would donate."""
    return jax.tree.map(np.array, jax.device_get(tree))


class StepObserver:
    """The program's own jitted step. Forwards every call; around the
    first ``compare_steps`` of them it fetches to the host what the
    comparison reads: the parameters before step 1 (from the arguments,
    before the call donates them), each step's loss, Adam's first moment
    after each step (the gradients as the optimizer got them) and the
    parameters after the last compared step (from the returned state,
    before it is handed back). It keeps nothing on the device, so the
    peak the run reports is the program's alone; the waits are set-up's,
    the compared steps lying before the window."""

    def __init__(self, jitted, compare_steps: int):
        self.jitted = jitted
        self.compare_steps = int(compare_steps)
        self.calls = 0
        self.params_before = None
        self.moments = []
        self.params_after = None
        self.losses = []

    def __call__(self, *args):
        self.calls += 1
        n = self.calls
        if n > self.compare_steps:
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                return self.jitted(*args)
        if n == 1:
            self.params_before = _fetch(args[0].params)
        state, loss = self.jitted(*args)
        self.losses.append(float(np.mean(jax.device_get(loss))))
        self.moments.append(_fetch(_adam_mu(state.opt_state)))
        if n == self.compare_steps:
            self.params_after = _fetch(state.params)
        return state, loss

    def readings(self) -> dict:
        if self.calls < self.compare_steps:
            raise RuntimeError(f"the loop made {self.calls} steps; the "
                               f"comparison needs {self.compare_steps}")
        return {"params_before": self.params_before, "moments": self.moments,
                "params_after": self.params_after, "losses": self.losses}


def _adam_mu(opt_state):
    for part in jax.tree.leaves(
            opt_state, is_leaf=lambda x: hasattr(x, "mu")):
        if hasattr(part, "mu"):
            return part.mu
    raise RuntimeError("no Adam state in the optimizer state")


class _JaxWithObservedJit:
    """``jax``, except that ``jit`` of the function named ``target``
    comes back wrapped by ``wrap``. Stands in for the name ``jax`` in one
    module of the program for the length of one call."""

    def __init__(self, target: str, wrap):
        self._target = target
        self._wrap = wrap

    def __getattr__(self, name):
        return getattr(jax, name)

    def jit(self, fun, **kwargs):
        jitted = jax.jit(fun, **kwargs)
        if getattr(fun, "__name__", "") == self._target:
            return self._wrap(jitted)
        return jitted


@contextlib.contextmanager
def swapped(owner, name: str, value):
    old = getattr(owner, name)
    setattr(owner, name, value)
    try:
        yield
    finally:
        setattr(owner, name, old)


@contextlib.contextmanager
def observed_jit(module, target: str, wrap):
    """Inside: ``module.jax.jit`` of the function called ``target`` gives
    ``wrap(jitted)``."""
    with swapped(module, "jax", _JaxWithObservedJit(target, wrap)):
        yield


@contextlib.contextmanager
def window_budget(plan: WindowPlan, *owners):
    """Inside: each ``owner.StepBudget`` is that class, observed on
    ``plan``."""
    with contextlib.ExitStack() as stack:
        for owner in owners:
            stack.enter_context(swapped(
                owner, "StepBudget",
                observed_budget(owner.StepBudget, plan)))
        yield
