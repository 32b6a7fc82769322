"""The comparison that decides ``correct`` for a training cell.

Two sets of readings over the same first steps from the same seed: the
program's (copied out of the timed object's own states by
``instrument.StepObserver``) and the plain reference's. Numbers, each a
gap that is 0 when the two agree; a cell's file holds a limit for each
number it is held to (PERF.md section 2 has the readings behind them):

- ``init_gap``: largest absolute difference between the program's
  parameters before step 1 and the reference's own from the seed.
- ``loss_gap``: largest relative difference of a step's loss.
- ``loss_step_gap``: (L2 - L1) of the program against the reference's,
  over L1. Steps 1 and 2 run on the same parameters (the schedule's
  first learning rate is 0), so L2 - L1 is the rows' doing alone and
  rounding that the two steps share cancels: it sees rows left out,
  though as one scalar it can come out near 0 by chance. (L3 - L2 does
  not cancel: after the first real update, whose direction under Adam is
  the sign of each gradient element, the two sides' parameters differ
  wherever a tiny element's sign does.)
- ``grad_gap``: worst leaf's gap between the norm of the first gradient
  as the optimizer got it (from Adam's first moment) and the
  reference's, against the reference's norm of that leaf or of the
  median leaf, whichever is larger.
- ``grad_diff_scaled``: norm of the difference of the whole first
  gradient, over sqrt(|g| * |grad of the batch's mean logit|). The
  gradient is a sum of residual x d(logit): on seeds where the residuals
  cancel, |g| alone is too small a yardstick and the uncancelled scale
  alone too large; their geometric mean reads steady from seed to seed.
  It sees precision.
- ``row_weight_step_gap``: where the change of the program's gradient
  from step 1 to step 2 lies between the whole batch's (0) and that of
  the mean over one half of the rows alone (1). The two steps run on the
  initial parameters (the schedule's first learning rate is 0), and at
  each the reference gives both gradients, g and g_half. With a =
  g_prog - g and d = g_half - g, a_2 - a_1 is fitted by least squares
  as t·(d_2 - d_1), and the number is |t|. Rows that do not weigh the
  same in the mean move each gradient along its d and nothing else
  (either half left out: t = ±1). The program's rounding has a
  component along d too, and at one step alone as large a one as the
  fault's (PERF.md section 2); but it is a function of the parameters
  far more than of the rows, so between two steps on the same
  parameters nearly all of it cancels, as the loss's does in
  ``loss_step_gap``, while the rows' part, drawn anew at each step,
  does not.
- ``change_gap``: as ``grad_gap`` for the norm of each leaf's change over
  the compared steps. Leaves whose reference gradient is under a
  thousandth of the median leaf's (a key bias under softmax) move under
  Adam by round-off alone and are left out of this one, by that rule.

The reference's trees reach the comparison one at a time, as the
reference makes them (:class:`Fold`), and each is dropped once its
numbers are taken; so are the program's. What the host holds after the
window at P parameters is at most 6P float32, the observer's 5P among
them: the program's first two moments, the reference's two first-half
gradients and its first gradient when the second step's gradient is
walked, one leaf of it at a time (PERF.md section 2).
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

ADAM_B1 = 0.9
# A leaf is walked in slices of this many elements, a thread a slice, so
# that the float64 vectors in flight are a few slices' whatever the leaf.
SLICE = 1 << 21
THREADS = max(1, min(8, os.cpu_count() or 1))


def leaves(tree, prefix="") -> dict:
    """Nested parameter dicts → ``{"a/b/kernel": array}``, each array
    as it is held (nothing copied, nothing converted); a top-level
    ``params`` collection is dropped."""
    out = {}
    for key, value in tree.items():
        name = f"{prefix}/{key}" if prefix else str(key)
        if isinstance(value, dict) or hasattr(value, "items"):
            out.update(leaves(value, name))
        else:
            out[name] = value
    if not prefix and out and all(k.startswith("params/") for k in out):
        out = {k[len("params/"):]: v for k, v in out.items()}
    return out


def host(leaf) -> np.ndarray:
    """One leaf as a host array of its own. A device array's host value
    is cached in the array for as long as it lives (and on the CPU a
    host array may be the device buffer itself), so the value is copied
    out of a copy on the device that the runtime makes (no program is
    compiled) and that goes once read: nothing stays behind in an array
    that the caller keeps."""
    if isinstance(leaf, np.ndarray):
        return leaf
    return np.array(jax.device_put(leaf, may_alias=False))


def _f64(leaf):
    """A float64 copy of a slice, the caller's to work on in place."""
    return np.array(leaf, np.float64)


def _slices(*vectors):
    size = vectors[0].size
    return [tuple(v[i:i + SLICE] for v in vectors)
            for i in range(0, max(size, 1), SLICE)]


def _initial(ours, theirs, after):
    """One slice: the largest gap between the two sides' initial
    parameters, and the program's change over the compared steps,
    squared."""
    ours, theirs = _f64(ours), _f64(theirs)
    gap = ours - theirs
    largest = float(np.max(np.abs(gap, out=gap)))
    del gap
    after = _f64(after)
    after -= ours
    return largest, float(after @ after)


def _squares(*vectors):
    """One slice: the squared norm of the first vector less the second
    (of the first alone where it is alone)."""
    v = _f64(vectors[0])
    if len(vectors) > 1:
        v -= _f64(vectors[1])
    return float(v @ v)


def _gradients(m0, m1, g0, h0, h1, g1):
    """One slice of the two steps on the initial parameters: the
    program's first gradient from Adam's first moment, g_t = (m_t −
    β₁·m_{t−1}) / (1 − β₁), against the reference's; and with a =
    g_prog − g and d = g_half − g at each step, a_2 − a_1 and d_2 − d_1.
    Returns the squared norms of the two first gradients and of their
    difference, (a_2 − a_1)·(d_2 − d_1) and |d_2 − d_1|²."""
    a = _f64(m1)
    before = _f64(m0)
    before *= ADAM_B1
    a -= before
    a /= 1.0 - ADAM_B1
    del before
    first = _f64(m0)
    first /= 1.0 - ADAM_B1
    g = _f64(g0)
    ours, theirs = float(first @ first), float(g @ g)
    a -= first
    first -= g
    diff = float(first @ first)
    del first
    a += g
    d = _f64(h1)
    d -= h0
    d += g
    del g
    g = _f64(g1)
    a -= g
    d -= g
    del g
    return ours, theirs, diff, float(a @ d), float(d @ d)


def _walk(pool, slice_fn, *vectors) -> list:
    """``slice_fn`` over the leaves' slices, in order, on ``pool``."""
    parts = _slices(*(np.asarray(v).reshape(-1) for v in vectors))
    if len(parts) == 1:
        return [slice_fn(*parts[0])]
    return list(pool.map(lambda p: slice_fn(*p), parts))


def _worst_norm_gap(ours: dict, theirs: dict, leaves) -> tuple[float, str]:
    floor = float(np.median(list(theirs.values())))
    worst, at = 0.0, ""
    for k in leaves:
        gap = abs(ours[k] - theirs[k]) / max(theirs[k], floor, 1e-30)
        if gap > worst:
            worst, at = gap, k
    return worst, at


class Fold:
    """The comparison, fed the reference's trees in the order the
    reference makes them (``references/common.py: follow``):
    :meth:`initial`, :meth:`logit`, then for each step :meth:`half`
    (the steps on the initial parameters) and :meth:`gradient`, and last
    :meth:`final`. Each tree, the program's too, is dropped a leaf at a
    time once that leaf's numbers are taken: the program's parameters
    and third moment with the initial parameters; its two other moments,
    the two first-half gradients and the first gradient with the second
    step's gradient, which is read one leaf at a time from where it is.
    A tree may come as device arrays or as host arrays.

    Every number is a function of per-leaf norms and inner products; a
    leaf is walked in slices of float64 on several threads, and the sums
    over leaves are added in the leaves' order (the reference's)."""

    def __init__(self, program: dict):
        """``program``: params_before, moments, params_after, losses, as
        the observer reads them; the dict is emptied, its trees are the
        fold's to drop."""
        self.losses = [float(x) for x in program.pop("losses")]
        self.p0 = leaves(program.pop("params_before"))
        self.p1 = leaves(program.pop("params_after"))
        # No number reads the third moment.
        self.moments = [leaves(m) for m in program.pop("moments")[:2]]
        program.clear()
        self.names = None
        self.init, self.moved_prog, self.moved_ref = {}, {}, {}
        self.logit_sq, self.walked = {}, {}
        self.halves, self.first = {}, None
        self.ref_losses = []

    def initial(self, params) -> None:
        """The reference's parameters before step 1: ``init_gap``, and
        the program's change over the compared steps."""
        r0 = leaves(params)
        if sorted(self.p0) != sorted(r0):
            raise ValueError(
                "the program's and the reference's parameters differ in "
                f"name: {sorted(set(self.p0) ^ set(r0))}")
        self.names = list(r0)
        with ThreadPoolExecutor(THREADS) as pool:
            for k in self.names:
                parts = _walk(pool, _initial, self.p0.pop(k), host(r0[k]),
                              self.p1.pop(k))
                self.init[k] = max(p[0] for p in parts)
                self.moved_prog[k] = float(np.sqrt(sum(p[1] for p in parts)))

    def logit(self, tree) -> None:
        """The gradient of the batch's mean logit, read through its
        leaves' squared norms alone (a kind may hand each leaf over as
        its norm)."""
        with ThreadPoolExecutor(THREADS) as pool:
            for k, v in leaves(tree).items():
                self.logit_sq[k] = sum(_walk(pool, _squares, host(v)))

    def half(self, step: int, tree) -> None:
        """The gradient over the first half of the batch's rows, at a
        step on the initial parameters; the first two are read."""
        if step < 2:
            self.halves[step] = {k: host(v) for k, v in leaves(tree).items()}

    def gradient(self, step: int, loss, tree) -> None:
        """Step ``step``'s loss and gradient. The first gradient is kept
        (as host arrays); the second is walked with everything the two
        steps on the initial parameters give; later ones are not read."""
        self.ref_losses.append(float(loss))
        if step == 0:
            self.first = {k: host(v) for k, v in leaves(tree).items()}
        elif step == 1:
            if sorted(self.halves) != [0, 1] or self.first is None:
                raise ValueError(
                    "the comparison needs two steps on the initial "
                    "parameters, each with its first-half gradient")
            g1 = leaves(tree)
            (m0, m1), h0, h1 = self.moments, self.halves[0], self.halves[1]
            with ThreadPoolExecutor(THREADS) as pool:
                for k in self.names:
                    self.walked[k] = [sum(x) for x in zip(*_walk(
                        pool, _gradients, m0.pop(k), m1.pop(k),
                        self.first.pop(k), h0.pop(k), h1.pop(k),
                        host(g1[k])))]
            self.moments, self.halves, self.first = [], {}, None

    def final(self, after, before) -> None:
        """The reference's parameters after the compared steps and
        before step 1 (drawn again from the seed): its change."""
        r1, r0 = leaves(after), leaves(before)
        with ThreadPoolExecutor(THREADS) as pool:
            for k in self.names:
                self.moved_ref[k] = float(np.sqrt(sum(_walk(
                    pool, _squares, host(r1[k]), host(r0[k])))))

    def numbers(self) -> dict:
        """Each number with the leaf or step that set it."""
        if not self.walked:
            raise ValueError(
                "the comparison needs two steps on the initial parameters, "
                "each with its first-half gradient")
        lp, lr = self.losses, self.ref_losses
        if len(lp) != len(lr):
            raise ValueError(f"{len(lp)} losses against {len(lr)}")
        gaps = [abs(a - b) / max(abs(b), 1e-30) for a, b in zip(lp, lr)]
        gaps = [g if np.isfinite(g) else 1e30 for g in gaps]
        step = int(np.argmax(gaps))
        loss_gap = (float(gaps[step]), f"step {step + 1}")

        # Steps 1 and 2 run on the same parameters (the schedule's first
        # learning rate is 0), so what differs between them is the rows
        # alone, and rounding that the two steps share cancels.
        step = (lp[1] - lp[0]) - (lr[1] - lr[0])
        loss_step_gap = (abs(step) / max(abs(lr[0]), 1e-30),
                         "step 2 - step 1")

        g_prog, g_ref = {}, {}
        diff_sq = logit_sq = ref_sq = along = length = 0.0
        for k in self.names:
            ours, theirs, diff, a_d, d_d = self.walked[k]
            g_prog[k], g_ref[k] = float(np.sqrt(ours)), float(np.sqrt(theirs))
            logit_sq += self.logit_sq[k]
            ref_sq += theirs
            diff_sq += diff
            along += a_d
            length += d_d

        at = max(self.init, key=self.init.get)
        out = {"init_gap": (self.init[at], at), "loss_gap": loss_gap,
               "loss_step_gap": loss_step_gap}
        out["grad_gap"] = _worst_norm_gap(g_prog, g_ref, g_ref)
        scale = np.sqrt(np.sqrt(ref_sq) * np.sqrt(logit_sq))
        out["grad_diff_scaled"] = (
            float(np.sqrt(diff_sq)) / max(scale, 1e-30), "all leaves")
        out["row_weight_step_gap"] = (
            abs(along) / max(length, 1e-300),
            "first half of the rows, step 2 - step 1")

        median_grad = float(np.median(list(g_ref.values())))
        live = [k for k in self.names if g_ref[k] >= 1e-3 * median_grad]
        out["change_gap"] = _worst_norm_gap(self.moved_prog, self.moved_ref,
                                            live)
        # A number that is not finite fails any limit, and stays valid JSON.
        return {name: (value if np.isfinite(value) else 1e30, at)
                for name, (value, at) in out.items()}


def verdict(found: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and, for the result line, each number the cell's file
    holds a limit for, beside that limit."""
    report, ok = {}, True
    for name, limit in limits.items():
        value, at = found[name]
        limit = float(limit)
        report[name] = {"value": value, "limit": limit, "at": at}
        ok = ok and value <= limit
    return bool(ok), report
