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
- ``row_weight_gap``: where the program's gradients lie between the
  whole batch's mean (0) and the mean over one half of its rows alone
  (1). At each step on the initial parameters (the first two: the
  schedule's first learning rate is 0) the reference gives both, g and
  g_half;
  the program's g_prog - g is fitted by least squares as a·g + t·(g_half
  - g), a taking up a common scale (one rounding that every row shares)
  step by step and t being one for all the steps, and the number is |t|.
  Rows that do not weigh the same in the mean move the gradient along
  g_half - g and nothing else (either half left out: t = ±1). Rounding
  has a component there too, since it acts much as a small random
  re-weighting of the rows does (PERF.md section 2), which is why the
  two steps are pooled.
- ``change_gap``: as ``grad_gap`` for the norm of each leaf's change over
  the compared steps. Leaves whose reference gradient is under a
  thousandth of the median leaf's (a key bias under softmax) move under
  Adam by round-off alone and are left out of this one, by that rule.
"""

from __future__ import annotations

import numpy as np

ADAM_B1 = 0.9


def flatten(tree, prefix="") -> dict:
    """Nested parameter dicts → ``{"a/b/kernel": array}``; a top-level
    ``params`` collection is dropped."""
    out = {}
    for key, value in tree.items():
        name = f"{prefix}/{key}" if prefix else str(key)
        if isinstance(value, dict) or hasattr(value, "items"):
            out.update(flatten(value, name))
        else:
            out[name] = np.asarray(value, np.float64)
    if not prefix and out and all(k.startswith("params/") for k in out):
        out = {k[len("params/"):]: v for k, v in out.items()}
    return out


def _norms(tree: dict) -> dict:
    return {k: float(np.linalg.norm(v)) for k, v in tree.items()}


def _worst_norm_gap(ours: dict, theirs: dict, leaves) -> tuple[float, str]:
    floor = float(np.median(list(theirs.values())))
    worst, at = 0.0, ""
    for k in leaves:
        gap = abs(ours[k] - theirs[k]) / max(theirs[k], floor, 1e-30)
        if gap > worst:
            worst, at = gap, k
    return worst, at


def gradients(moments: list) -> list:
    """Adam's first moment after each step → the gradient of each step
    as the optimizer got it: g_t = (m_t − β₁·m_{t−1}) / (1 − β₁)."""
    out, before = [], None
    for moment in map(flatten, moments):
        out.append({k: (v - (ADAM_B1 * before[k] if before else 0.0))
                    / (1.0 - ADAM_B1) for k, v in moment.items()})
        before = moment
    return out


def numbers(program: dict, reference: dict) -> dict:
    """``program``: params_before, moments, params_after, losses.
    ``reference``: params_before, grads, params_after, losses.
    Returns each number with the leaf or step that set it."""
    p0, r0 = flatten(program["params_before"]), flatten(
        reference["params_before"])
    if sorted(p0) != sorted(r0):
        raise ValueError(
            "the program's and the reference's parameters differ in name: "
            f"{sorted(set(p0) ^ set(r0))}")
    out = {}
    init = {k: float(np.max(np.abs(p0[k] - r0[k]))) for k in r0}
    at = max(init, key=init.get)
    out["init_gap"] = (init[at], at)

    lp, lr = program["losses"], reference["losses"]
    if len(lp) != len(lr):
        raise ValueError(f"{len(lp)} losses against {len(lr)}")
    gaps = [abs(a - b) / max(abs(b), 1e-30) for a, b in zip(lp, lr)]
    gaps = [g if np.isfinite(g) else 1e30 for g in gaps]
    step = int(np.argmax(gaps))
    out["loss_gap"] = (float(gaps[step]), f"step {step + 1}")

    # Steps 1 and 2 run on the same parameters (the schedule's first
    # learning rate is 0), so what differs between them is the rows
    # alone, and rounding that the two steps share cancels.
    step = (lp[1] - lp[0]) - (lr[1] - lr[0])
    out["loss_step_gap"] = (abs(step) / max(abs(lr[0]), 1e-30),
                            "step 2 - step 1")

    grads_ref = [flatten(g) for g in reference["grads"]]
    grads_prog = gradients(program["moments"])
    grad_ref, grad_prog = grads_ref[0], grads_prog[0]
    g_ref, g_prog = _norms(grad_ref), _norms(grad_prog)
    out["grad_gap"] = _worst_norm_gap(g_prog, g_ref, g_ref)

    every = lambda t: np.concatenate([t[k].ravel() for k in sorted(t)])  # noqa: E731
    norm = lambda t: float(np.linalg.norm(every(t)))  # noqa: E731
    scale = np.sqrt(norm(grad_ref) * norm(flatten(reference["logit_grad"])))
    out["grad_diff_scaled"] = (
        norm({k: grad_prog[k] - grad_ref[k] for k in grad_ref})
        / max(scale, 1e-30), "all leaves")

    # One t over the steps that have a g_half, a scale of its own for
    # each: with g taken out of both sides step by step, t is a ratio of
    # two sums.
    along, length = 0.0, 0.0
    for ours, whole, half in zip(grads_prog, grads_ref,
                                 reference["grads_first_half"]):
        whole = every(whole)
        off = lambda v: v - whole * (v @ whole) / (whole @ whole)  # noqa: E731
        towards_half = off(every(flatten(half)) - whole)
        along += off(every(ours) - whole) @ towards_half
        length += towards_half @ towards_half
    out["row_weight_gap"] = (abs(float(along)) / max(float(length), 1e-300),
                             "first half of the rows, steps 1-2")

    p1, r1 = flatten(program["params_after"]), flatten(
        reference["params_after"])
    moved_prog = _norms({k: p1[k] - p0[k] for k in r0})
    moved_ref = _norms({k: r1[k] - r0[k] for k in r0})
    median_grad = float(np.median(list(g_ref.values())))
    live = [k for k in r0 if g_ref[k] >= 1e-3 * median_grad]
    out["change_gap"] = _worst_norm_gap(moved_prog, moved_ref, live)
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
