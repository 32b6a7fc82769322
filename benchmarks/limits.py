"""The readings a cell's limits are set from, several seeds in one
process (set-up is long; the chip is one process's at a time):

    python3 benchmarks/limits.py --workload <cell> --seeds 11,12,13 [--control-seeds 3]

For each seed: one short run of the cell as ``run.py`` makes it (the
program's readings against the reference's: the *lower* readings), and
for the first ``--control-seeds`` of them the reference put in the
program's place: computed in the control's precision (fp8 for the
bfloat16 the configurations state), with half of the batch left out and
the mean taken over the rest, and with its state left unchanged (the
*upper* readings). Each set of numbers also goes through
``compare.verdict`` with the limits the cell's file holds, so a line
says what ``correct`` the program, the control and each fault would get
at the cell's own size: the program's has to be true and every other
false. Prints one JSON line per seed and a summary.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def in_program_place(readings: dict) -> dict:
    """A reference's readings in the shape the program's come in."""
    from benchmarks import compare
    moments, before = [], None
    for grads in readings["grads"]:
        before = {k: (compare.ADAM_B1 * before[k] if before else 0.0)
                  + (1.0 - compare.ADAM_B1) * g for k, g in grads.items()}
        moments.append(before)
    return {"params_before": readings["params_before"],
            "moments": moments,
            "params_after": readings["params_after"],
            "losses": readings["losses"]}


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
    for name, kwargs in STAND_INS:
        stand_in = ctx["reference"].readings(
            ctx["spec"], ctx["arrays"], ctx["seed"], ctx["steps"], **kwargs)
        out[name] = _judged(compare.numbers(
            in_program_place(stand_in), ctx["followed"]), ctx["limits"])
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--seconds", type=float, default=1.0)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)

    from benchmarks import run
    import jax

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("limits are read on the chip", file=sys.stderr)
        return run.NO_CHIP
    run.enable_compilation_cache()

    lower, upper, verdicts = {}, {}, {}
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        result = run.run_cell(
            args.workload, seed, args.seconds, False, rehearse=args.rehearse,
            study=control_study if i < args.control_seeds else program_only)
        line = {"seed": seed, **result["study"]}
        for who, found in result["study"].items():
            verdicts.setdefault(who, []).append(found["correct"])
            for k, v in found.items():
                if k == "correct":
                    continue
                if who == "program":
                    lower[k] = max(lower.get(k, 0.0), v)
                else:
                    upper[f"{who}.{k}"] = min(
                        upper.get(f"{who}.{k}", float("inf")), v)
        line["train_samples_per_s"] = result["metrics"][
            "train_samples_per_s"]["value"]
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
