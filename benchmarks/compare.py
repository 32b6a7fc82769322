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
"""

from __future__ import annotations

import numpy as np

ADAM_B1 = 0.9


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


def _f64(leaf):
    """One leaf as a float64 vector of this function's own, so that the
    caller may work in place."""
    return np.array(leaf, np.float64).ravel()


def _flat(leaf):
    """One leaf as a vector in its own type, nothing copied."""
    return np.asarray(leaf).reshape(-1)


def _norm(v) -> float:
    return float(np.sqrt(v @ v))


def _gradient(moments: list, t: int, k: str):
    """Leaf ``k`` of the gradient of step ``t`` as the optimizer got it,
    from Adam's first moment after each step:
    g_t = (m_t − β₁·m_{t−1}) / (1 − β₁)."""
    g = _f64(moments[t][k])
    if t:
        before = _f64(moments[t - 1][k])
        before *= ADAM_B1
        g -= before
    g /= 1.0 - ADAM_B1
    return g


def _worst_norm_gap(ours: dict, theirs: dict, leaves) -> tuple[float, str]:
    floor = float(np.median(list(theirs.values())))
    worst, at = 0.0, ""
    for k in leaves:
        gap = abs(ours[k] - theirs[k]) / max(theirs[k], floor, 1e-30)
        if gap > worst:
            worst, at = gap, k
    return worst, at


def numbers(program: dict, reference: dict) -> dict:
    """``program``: params_before, moments, params_after, losses.
    ``reference``: params_before, grads, params_after, losses,
    logit_grad, grads_first_half.
    Returns each number with the leaf or step that set it.

    Every number is a function of per-leaf norms and inner products, so
    the trees are walked once, one leaf of each in float64 at a time
    (three such vectors at the most): what this holds does not grow
    with the model beyond its largest leaf."""
    p0, r0 = leaves(program["params_before"]), leaves(
        reference["params_before"])
    if sorted(p0) != sorted(r0):
        raise ValueError(
            "the program's and the reference's parameters differ in name: "
            f"{sorted(set(p0) ^ set(r0))}")
    p1, r1 = leaves(program["params_after"]), leaves(
        reference["params_after"])
    moments = [leaves(m) for m in program["moments"]]
    grads_ref = [leaves(g) for g in reference["grads"]]
    halves = [leaves(g) for g in reference["grads_first_half"]]
    logit = leaves(reference["logit_grad"])

    lp, lr = program["losses"], reference["losses"]
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
    loss_step_gap = (abs(step) / max(abs(lr[0]), 1e-30), "step 2 - step 1")

    if min(len(moments), len(grads_ref), len(halves)) < 2:
        raise ValueError("the comparison needs two steps on the initial "
                         "parameters, each with its first-half gradient")
    init, moved_prog, moved_ref, g_prog, g_ref = {}, {}, {}, {}, {}
    diff_sq = logit_sq = ref_sq = 0.0
    # With a = g_prog - g and d = g_half - g at each of the two steps on
    # the initial parameters: (a_2 - a_1)·(d_2 - d_1) and |d_2 - d_1|²,
    # over all leaves.
    along = length = 0.0
    for k in r0:
        ours, theirs = _f64(p0[k]), _f64(r0[k])
        gap = ours - theirs
        init[k] = float(np.max(np.abs(gap, out=gap)))
        del gap
        after = _f64(p1[k])
        after -= ours
        moved_prog[k] = _norm(after)
        del ours
        after = _f64(r1[k])
        after -= theirs
        moved_ref[k] = _norm(after)
        del theirs, after

        scale = _f64(logit[k])
        logit_sq += float(scale @ scale)
        del scale
        # ``a`` and ``d`` become the two differences in place; the other
        # trees' leaves come in as they are held.
        a = _gradient(moments, 1, k)
        first, g = _gradient(moments, 0, k), _f64(grads_ref[0][k])
        g_prog[k], g_ref[k] = _norm(first), _norm(g)
        ref_sq += float(g @ g)
        a -= first
        first -= g
        diff_sq += float(first @ first)
        del first
        a += g
        d = _f64(halves[1][k])
        d -= _flat(halves[0][k])
        d += g
        del g
        g = _f64(grads_ref[1][k])
        a -= g
        d -= g
        del g
        along += float(a @ d)
        length += float(d @ d)
        del a, d

    at = max(init, key=init.get)
    out = {"init_gap": (init[at], at), "loss_gap": loss_gap,
           "loss_step_gap": loss_step_gap}
    out["grad_gap"] = _worst_norm_gap(g_prog, g_ref, g_ref)
    scale = np.sqrt(np.sqrt(ref_sq) * np.sqrt(logit_sq))
    out["grad_diff_scaled"] = (float(np.sqrt(diff_sq)) / max(scale, 1e-30),
                               "all leaves")
    out["row_weight_step_gap"] = (
        abs(along) / max(length, 1e-300),
        "first half of the rows, step 2 - step 1")

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
