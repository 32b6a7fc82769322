"""``compare.Fold`` takes the reference's trees as they are made and
drops each once its numbers are taken; the numbers are the ones the
all-at-once arithmetic gives (every tree in float64 at once, the leaves
of a tree concatenated), which is kept here and nowhere in the harness.
Held equal on every kind's rehearsal readings: the sound program's and
the fp8 control's, each against the sound reference."""

import copy
import json
import os

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


ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
KINDS = {}
for _config in BENCH["configs"]:
    KINDS.setdefault(json.load(open(os.path.join(ROOT, _config["file"])))[
        "kind"], _config["name"])
# One cell of each kind.
CELLS = [next(w["name"] for w in BENCH["workloads"] if w["config"] == c)
         for c in KINDS.values()]


def _copied(tree) -> dict:
    return {k: np.array(v) for k, v in tree.items()}


def recording(fold):
    """``fold`` with a whole host copy kept of every tree it is handed,
    in the shapes ``numbers_all_at_once`` reads; each instance is listed
    in ``made``."""
    class Recording(fold):
        made = []

        def __init__(self, program):
            self.program = copy.deepcopy(program)
            self.followed = {"grads": [], "losses": [],
                             "grads_first_half": []}
            self.made.append(self)
            super().__init__(program)

        def initial(self, params):
            self.followed["params_before"] = _copied(params)
            super().initial(params)

        def logit(self, tree):
            self.followed["logit_grad"] = _copied(tree)
            super().logit(tree)

        def half(self, step, tree):
            self.followed["grads_first_half"].append(_copied(tree))
            super().half(step, tree)

        def gradient(self, step, loss, tree):
            self.followed["grads"].append(_copied(tree))
            self.followed["losses"].append(float(loss))
            super().gradient(step, loss, tree)

        def final(self, after, before):
            self.followed["params_after"] = _copied(after)
            super().final(after, before)
    return Recording


@pytest.fixture(autouse=True)
def _cache_outside_the_checkout(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


@pytest.mark.parametrize("cell", CELLS)
def test_the_numbers_did_not_move(cell, monkeypatch):
    from benchmarks import compare, limits, run

    Recording = recording(compare.Fold)
    monkeypatch.setattr(compare, "Fold", Recording)

    def control(ctx):
        args = (ctx["spec"], ctx["arrays"], ctx["seed"], ctx["steps"])
        placed = ctx["reference"].readings(
            *args, limits.InProgramPlace(), precision="fp8")
        ctx["reference"].readings(*args, Recording(placed.readings()))
        return {}

    run.run_cell(cell, 2**31 + 11, 0.3, False, rehearse=True, study=control)
    sound, fp8 = Recording.made
    for fold in (sound, fp8):
        new = fold.numbers()
        old = numbers_all_at_once(fold.program, fold.followed)
        assert list(new) == list(old)
        for name, (value, at) in old.items():
            assert new[name][1] == at, name
            assert new[name][0] == pytest.approx(value, rel=1e-12, abs=0), name
    assert fp8.numbers()["grad_diff_scaled"][0] > 3 * sound.numbers()[
        "grad_diff_scaled"][0]


def folded(program: dict, reference: dict) -> dict:
    """Whole trees handed to ``compare.Fold`` in the order the reference
    makes them; ``program`` is not changed."""
    fold = compare.Fold(dict(program))
    fold.initial(reference["params_before"])
    fold.logit(reference["logit_grad"])
    halves = reference["grads_first_half"]
    for step, (loss, grads) in enumerate(zip(reference["losses"],
                                             reference["grads"])):
        if step < len(halves):
            fold.half(step, halves[step])
        fold.gradient(step, loss, grads)
    fold.final(reference["params_after"], reference["params_before"])
    return fold.numbers()


def test_the_whole_trees_fold_as_they_come():
    """Trees made up here, handed to the fold whole: a leaf of several
    slices and a leaf of one element give the numbers of
    ``numbers_all_at_once``."""
    rng = np.random.default_rng(7)
    shapes = {"w": (3, compare.SLICE + 5), "b": (), "v": (17,)}
    tree = lambda scale=1.0: {  # noqa: E731
        k: (scale * rng.standard_normal(s)).astype(np.float32)
        for k, s in shapes.items()}
    before = tree()
    program = {"params_before": before, "params_after": {
        k: v + 1e-3 * rng.standard_normal(v.shape).astype(np.float32)
        for k, v in before.items()},
        "moments": [tree(0.1), tree(0.1), tree(0.1)],
        "losses": [1.0, 0.9, 0.8]}
    reference = {"params_before": before, "grads": [tree(), tree(), tree()],
                 "params_after": {k: v + 1e-3 for k, v in before.items()},
                 "losses": [1.01, 0.91, 0.81], "logit_grad": tree(),
                 "grads_first_half": [tree(), tree()]}
    new = folded(program, reference)
    old = numbers_all_at_once(program, reference)
    assert list(new) == list(old)
    for name, (value, at) in old.items():
        assert new[name][1] == at, name
        assert new[name][0] == pytest.approx(value, rel=1e-12, abs=0), name
    assert len(program["moments"]) == 3
