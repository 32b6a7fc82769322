"""The readings a cell's limits are set from, one seed a process (the
chip is one process's at a time, and a process's peak of host memory
never falls again):

    python3 benchmarks/limits.py --workload <cell> --seeds 11,12,13 [--control-seeds 3]

For each seed: one short run of the cell as ``run.py`` makes it (the
program's readings against the reference's: the *lower* readings), and
for the first ``--control-seeds`` of them the reference put in the
program's place: computed in the control's precision (fp8 for the
bfloat16 the configurations state), with half of the batch left out and
the mean taken over the rest, and with its state left unchanged (the
*upper* readings). Each stand-in's trees are made into the program's
readings (:class:`InProgramPlace`), and then the sound reference is run
again into ``compare.Fold``, as ``run.py`` does. While a stand-in is
made the host holds its parameters before the steps and its first
moments (up to 4P float32 at P parameters, the parameters after the
steps coming once the moments' updates are done) and Adam's two moments
(2P): 6P at the most, as much as a run's comparison (counted, not
measured). A seed with the controls costs seven references (the sound
one, then each stand-in and the sound one again) where one without
costs one. Each set of numbers also goes
through ``compare.verdict`` with the limits the cell's file holds, so a
line says what ``correct`` the program, the control and each fault would
get at the cell's own size: the program's has to be true and every other
false. Prints one JSON line per seed and a summary.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class InProgramPlace:
    """A sink for ``references/common.py: follow`` that keeps what the
    program's observer keeps, in its arithmetic: the parameters before
    step 1 and after the last, Adam's first moment after each step in
    float32 (m_t = β₁·m_{t−1} + (1 − β₁)·g_t) and each loss."""

    def __init__(self):
        self.before, self.after, self.moments, self.losses = None, None, [], []

    def initial(self, params) -> None:
        from benchmarks.compare import host
        self.before = {k: host(v) for k, v in params.items()}

    def logit(self, tree) -> None:
        pass

    def half(self, step: int, tree) -> None:
        pass

    def gradient(self, step: int, loss, tree) -> None:
        from benchmarks.compare import ADAM_B1, host
        last = self.moments[-1] if self.moments else None
        self.moments.append({
            k: (ADAM_B1 * last[k] if last else 0.0)
            + (1.0 - ADAM_B1) * host(g) for k, g in tree.items()})
        self.losses.append(float(loss))

    def final(self, after, before) -> None:
        from benchmarks.compare import host
        self.after = {k: host(v) for k, v in after.items()}

    def readings(self) -> dict:
        return {"params_before": self.before, "moments": self.moments,
                "params_after": self.after, "losses": self.losses}


STAND_INS = (("fp8", {"precision": "fp8"}),
             ("half_batch", {"keep_rows": 0.5}),
             ("unchanged", {"frozen": True}))


def _judged(found: dict, limits: dict) -> dict:
    from benchmarks import compare
    correct, _ = compare.verdict(found, limits)
    return {"correct": correct, **{k: v[0] for k, v in found.items()}}


def program_only(ctx: dict) -> dict:
    return {"program": _judged(ctx["found"], ctx["limits"])}


def control_study(ctx: dict) -> dict:
    from benchmarks import compare
    out = program_only(ctx)
    reference, args = ctx["reference"], (
        ctx["spec"], ctx["arrays"], ctx["seed"], ctx["steps"])
    for name, kwargs in STAND_INS:
        placed = reference.readings(*args, InProgramPlace(), **kwargs)
        found = reference.readings(
            *args, compare.Fold(placed.readings())).numbers()
        out[name] = _judged(found, ctx["limits"])
    return out


def one_seed(args) -> int:
    from benchmarks import run
    import jax

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("limits are read on the chip", file=sys.stderr)
        return run.NO_CHIP
    run.enable_compilation_cache()
    seed = int(args.seeds)
    result = run.run_cell(
        args.workload, seed, args.seconds, False, rehearse=args.rehearse,
        study=control_study if args.control_seeds else program_only)
    line = {"seed": seed, **result["study"]}
    line["train_samples_per_s"] = result["metrics"][
        "train_samples_per_s"]["value"]
    print(json.dumps(line), flush=True)
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--seconds", type=float, default=1.0)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--one", action="store_true",
                        help="read the one seed in this process")
    args = parser.parse_args(argv)
    if args.one:
        return one_seed(args)

    # This process touches no JAX: each seed's process has the chip.
    lower, upper, verdicts = {}, {}, {}
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        done = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--one",
             "--workload", args.workload, "--seeds", str(seed),
             "--control-seeds", str(int(i < args.control_seeds)),
             "--seconds", str(args.seconds)]
            + (["--rehearse"] if args.rehearse else []),
            stdout=subprocess.PIPE, text=True)
        if done.returncode:
            return done.returncode
        line = json.loads(done.stdout.strip().splitlines()[-1])
        for who, found in line.items():
            if not isinstance(found, dict):
                continue
            verdicts.setdefault(who, []).append(found["correct"])
            for k, v in found.items():
                if k == "correct":
                    continue
                if who == "program":
                    lower[k] = max(lower.get(k, 0.0), v)
                else:
                    upper[f"{who}.{k}"] = min(
                        upper.get(f"{who}.{k}", float("inf")), v)
        print(json.dumps(line), flush=True)
    print(json.dumps({"largest_program_reading": lower,
                      "smallest_control_reading": upper,
                      "correct_under_the_cells_limits": verdicts}))
    # The cell's limits hold where every sound run is correct and no
    # control or fault is.
    held = all(verdicts.pop("program")) and not any(
        any(v) for v in verdicts.values())
    return 0 if held else 1


if __name__ == "__main__":
    sys.exit(main())
