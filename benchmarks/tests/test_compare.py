"""``compare.numbers`` walks the trees one leaf at a time; the numbers
are the ones the all-at-once arithmetic gave (every tree in float64 at
once, the leaves of a tree concatenated), which is kept here and
nowhere in the harness. Held equal on both kinds' rehearsal readings:
the sound program's and the fp8 control's."""

import numpy as np
import pytest

from benchmarks import compare

ADAM_B1 = 0.9


def flatten(tree, prefix="") -> dict:
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


def _worst_norm_gap(ours, theirs, leaves):
    floor = float(np.median(list(theirs.values())))
    worst, at = 0.0, ""
    for k in leaves:
        gap = abs(ours[k] - theirs[k]) / max(theirs[k], floor, 1e-30)
        if gap > worst:
            worst, at = gap, k
    return worst, at


def gradients(moments: list) -> list:
    out, before = [], None
    for moment in map(flatten, moments):
        out.append({k: (v - (ADAM_B1 * before[k] if before else 0.0))
                    / (1.0 - ADAM_B1) for k, v in moment.items()})
        before = moment
    return out


def numbers_all_at_once(program: dict, reference: dict) -> dict:
    """``compare.numbers`` as it was until PR 26: every tree in float64
    at once."""
    p0, r0 = flatten(program["params_before"]), flatten(
        reference["params_before"])
    out = {}
    init = {k: float(np.max(np.abs(p0[k] - r0[k]))) for k in r0}
    at = max(init, key=init.get)
    out["init_gap"] = (init[at], at)

    lp, lr = program["losses"], reference["losses"]
    gaps = [abs(a - b) / max(abs(b), 1e-30) for a, b in zip(lp, lr)]
    gaps = [g if np.isfinite(g) else 1e30 for g in gaps]
    step = int(np.argmax(gaps))
    out["loss_gap"] = (float(gaps[step]), f"step {step + 1}")
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

    whole = [every(g) for g in grads_ref[:2]]
    ours = [every(g) for g in grads_prog[:2]]
    half = [every(flatten(g)) for g in reference["grads_first_half"][:2]]
    off = (ours[1] - whole[1]) - (ours[0] - whole[0])
    towards_half = (half[1] - whole[1]) - (half[0] - whole[0])
    out["row_weight_step_gap"] = (
        abs(float(off @ towards_half))
        / max(float(towards_half @ towards_half), 1e-300),
        "first half of the rows, step 2 - step 1")

    p1, r1 = flatten(program["params_after"]), flatten(
        reference["params_after"])
    moved_prog = _norms({k: p1[k] - p0[k] for k in r0})
    moved_ref = _norms({k: r1[k] - r0[k] for k in r0})
    median_grad = float(np.median(list(g_ref.values())))
    live = [k for k in r0 if g_ref[k] >= 1e-3 * median_grad]
    out["change_gap"] = _worst_norm_gap(moved_prog, moved_ref, live)
    return {name: (value if np.isfinite(value) else 1e30, at)
            for name, (value, at) in out.items()}


@pytest.fixture(autouse=True)
def _cache_outside_the_checkout(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


@pytest.mark.parametrize("cell", ["gat-fleet50k.train",
                                  "sage-fleet100k.train"])
def test_the_numbers_did_not_move(cell):
    from benchmarks import limits, run

    ctx = run.run_cell(cell, 2**31 + 11, 0.3, False, rehearse=True,
                       study=lambda ctx: ctx)["study"]
    control = limits.in_program_place(ctx["reference"].readings(
        ctx["spec"], ctx["arrays"], ctx["seed"], ctx["steps"],
        precision="fp8"))
    for program in (ctx["program"], control):
        new = compare.numbers(program, ctx["followed"])
        old = numbers_all_at_once(program, ctx["followed"])
        assert list(new) == list(old)
        for name, (value, at) in old.items():
            assert new[name][1] == at, name
            assert new[name][0] == pytest.approx(value, rel=1e-12, abs=0), name
    assert new["grad_diff_scaled"][0] > 3 * ctx["found"][
        "grad_diff_scaled"][0]
