"""Puts the stand-in kind ``wide_mlp`` in place the way a later PR adds
a model kind: new files and new entries, no edit to a file that is
there. The kind's runner, reference and counts are found beside the
harness's own by name; its configuration, its cell and the two entries
in ``BENCHMARK.json`` go into a scratch root that ``run.py`` reads in
the checkout's place.

On the chip, once, at the configuration's own sizes (0.45B parameters;
not a cell, PERF.md section 6 has the reading):

    python3 benchmarks/tests/standin/place.py --seed <n>
"""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL, CONFIG = "wide-mlp.train", "wide-mlp"


@contextlib.contextmanager
def placed(scratch: str):
    """Inside: ``run.run_cell(CELL, ...)`` finds the stand-in."""
    import benchmarks.counts
    import benchmarks.references
    import benchmarks.runners
    from benchmarks import run

    bench = run.load_json("BENCHMARK.json")
    bench["configs"].append({
        "name": CONFIG, "source": "benchmarks/tests/standin",
        "file": f"benchmarks/configs/{CONFIG}.json", "reduced": [],
        "why": "stand-in for a model kind whose state is large"})
    bench["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": "train", "chips": 1,
        "why": "stand-in for the tests, never a cell"})
    for group, name in (("configs", CONFIG), ("workloads", CELL)):
        os.makedirs(os.path.join(scratch, "benchmarks", group), exist_ok=True)
        shutil.copy(os.path.join(HERE, group, f"{name}.json"),
                    os.path.join(scratch, "benchmarks", group))
    with open(os.path.join(scratch, "BENCHMARK.json"), "w",
              encoding="utf-8") as fh:
        json.dump(bench, fh)

    packages = (benchmarks.runners, benchmarks.references, benchmarks.counts)
    was_root = run.ROOT
    for package in packages:
        package.__path__.append(os.path.join(
            HERE, package.__name__.rsplit(".", 1)[1]))
    run.ROOT = scratch
    try:
        yield
    finally:
        run.ROOT = was_root
        for package in packages:
            package.__path__.pop()


def main(argv=None) -> int:
    import argparse
    import importlib
    import tempfile
    import time

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=5.0)
    args = parser.parse_args(argv)

    import jax

    from benchmarks import compare, instrument, run

    if jax.devices()[0].platform != "tpu":
        print("the stand-in's one reading is made on the chip",
              file=sys.stderr)
        return run.NO_CHIP
    run.enable_compilation_cache()
    taken = {}

    def noted(function, label: str):
        """``function``, with its seconds and the resident set after it
        noted under ``label`` (the last call's, where there are many)."""
        def wrapper(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                return function(*args, **kwargs)
            finally:
                taken[label] = {"seconds": time.perf_counter() - t0,
                                "rss_after_bytes": _rss_now()}
                print(f"{label}: {taken[label]}", file=sys.stderr)
        return wrapper

    compare.Fold.numbers = noted(compare.Fold.numbers, "comparison")
    instrument._fetch = noted(instrument._fetch, "observer_fetch")
    with tempfile.TemporaryDirectory(dir=ROOT) as scratch, placed(scratch):
        reference = importlib.import_module(
            "benchmarks.references.wide_mlp")
        reference.readings = noted(reference.readings, "reference")
        result = run.run_cell(CELL, args.seed, args.seconds, False)
    with open("/proc/meminfo", encoding="ascii") as fh:
        mem_total_kb = int(fh.readline().split()[1])
    result["host"] = {"mem_total_bytes": mem_total_kb * 1024, **taken}
    print(json.dumps(result))
    return 0


def _rss_now() -> int:
    with open("/proc/self/statm", encoding="ascii") as fh:
        return int(fh.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


if __name__ == "__main__":
    sys.exit(main())
