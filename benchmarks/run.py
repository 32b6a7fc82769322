"""One run of one benchmark cell in one process.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration, model kind or metric
is a file found by the name ``BENCHMARK.json`` gives it (see README.md
beside this file); nothing here names one. The last line of standard
output is the result object the benchmark's contract describes.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

NO_CHIP = 3


def load_json(*parts):
    with open(os.path.join(ROOT, *parts), encoding="utf-8") as fh:
        return json.load(fh)


def merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = merged(out[key], value)
        else:
            out[key] = value
    return out


def load_cell(name: str, rehearse: bool):
    """The cell's entry in BENCHMARK.json, its workload file and its
    configuration's file, folded into the one ``spec`` that the runner,
    the reference and the count functions read."""
    bench = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                         f"{sorted(cells)}")
    cell = cells[name]
    workload = load_json("benchmarks", "workloads", f"{name}.json")
    for key in ("config", "chips", "traffic"):
        if workload[key] != cell[key]:
            raise SystemExit(f"{name}: {key} is {cell[key]!r} in "
                             f"BENCHMARK.json and {workload[key]!r} in its file")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(entry["file"])
    if rehearse:
        config = merged(config, config["rehearse"])
    spec = merged(config, {
        "epochs": workload["epochs"],
        "eval_fraction": workload["eval_fraction"],
        "batch": config["batch"] * (cell["chips"]
                                    if workload["batch_per_chip"] else 1)})
    return bench, cell, workload, spec


def metric_names(bench: dict, cell: str, group: str) -> list:
    return [m["name"] for m in bench[group]
            if cell in m.get("workloads", [cell])]


def enable_compilation_cache() -> None:
    """JAX's persistent compilation cache: where the environment says,
    else at a fixed path inside the checkout (the program's own rule,
    ``utils/compilecache.py``), and every program kept."""
    import jax

    where = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    os.makedirs(where, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # The ``df2.*`` scopes that metrics read are operation metadata,
    # which JAX leaves out of the key unless told: without this a
    # program cached by another build comes back with that build's names.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             rehearse: bool = False, wrap_fault=None, devices=None,
             study=None) -> dict:
    """Everything after the look for a chip. ``wrap_fault`` (tests only)
    stands between the program's compiled step and the observer;
    ``study`` (limits.py) gets the two sets of readings in a dict."""
    import jax

    from benchmarks import compare, instrument, peaks
    from benchmarks import trace as tracing

    bench, cell, workload, spec = load_cell(name, rehearse)
    kind, chips = spec["kind"], cell["chips"]
    runner = importlib.import_module(f"benchmarks.runners.{kind}")
    reference = importlib.import_module(f"benchmarks.references.{kind}")
    counts = importlib.import_module(f"benchmarks.counts.{kind}")

    devices = list(devices if devices is not None else jax.devices())[:chips]
    kind_of_chip = devices[0].device_kind
    chip_peaks = peaks.peaks_for("TPU v5 lite" if rehearse else kind_of_chip)

    from dragonfly2_tpu.parallel import data_parallel_mesh
    mesh = data_parallel_mesh(devices=devices)

    # The program's own seed arguments end in 32-bit keys; the kind's
    # traffic takes the whole seed.
    program_seed = seed % (2**31 - 2)
    arrays = runner.traffic(spec, seed)

    trace_dir = os.path.join(ROOT, ".bench_trace", name)
    run = {"chips": chips}

    def on_open():
        if trace:
            # One trace per cell stays on disk, the newest.
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=options)

    def on_close():
        if trace:
            jax.profiler.stop_trace()
        # The peak on the fullest chip. The allocator's
        # ``peak_bytes_in_use`` leaves out what a loaded program holds
        # reserved for its temporaries (``bytes_reserved``: the
        # compiler's temp_size, held from the first step on), so the
        # peak is the larger of that statistic and what is in use plus
        # what is reserved as the window closes.
        stats = [d.memory_stats() or {} for d in devices]
        run["memory_peak_bytes"] = max(max(
            int(s.get("peak_bytes_in_use", 0)),
            int(s.get("bytes_in_use", 0)) + int(s.get("bytes_reserved", 0)))
            for s in stats)
        run["memory_stats"] = stats[0]

    plan = instrument.WindowPlan(
        seconds, workload["compare_steps"], workload["warm_steps"],
        on_open, on_close)
    observers = []

    def wrap_step(jitted):
        if wrap_fault is not None:
            jitted = wrap_fault(jitted)
        observers.append(instrument.StepObserver(
            jitted, workload["compare_steps"]))
        return observers[-1]

    runner.drive(spec, arrays, program_seed, plan, mesh, wrap_step)
    if len(observers) != 1:
        raise RuntimeError(f"{len(observers)} step programs were built; the "
                           "runner observes exactly one")
    run.update(steps=plan.steps, samples=plan.samples,
               window_seconds=plan.window_seconds,
               setup_seconds=plan.t_open - T0,
               compile_seconds=plan.compile_seconds)
    observer = observers.pop()
    program = observer.readings()
    # The comparison drops the program's trees as it takes their numbers;
    # nothing else may hold them.
    observer.params_before = observer.params_after = None
    observer.moments = []
    del observer

    t_ref = time.perf_counter()
    found = reference.readings(
        spec, arrays, program_seed, workload["compare_steps"],
        compare.Fold(program)).numbers()
    # Rounding and sampling noise depend on the sizes, so the tiny
    # rehearsal sizes have limits of their own (read on the CPU).
    limits = workload["rehearse_limits" if rehearse else "limits"]
    correct, compared = compare.verdict(found, limits)
    run["reference_seconds"] = time.perf_counter() - t_ref

    reduced = None
    if trace:
        reduced = tracing.reduce(tracing.find_xplane(trace_dir),
                                 rehearse=rehearse)
    ctx = {"trace": reduced, "spec": spec, "counts": counts, "run": run,
           "peaks": chip_peaks}
    group = "per_layer" if trace else "end_to_end"
    units = {m["name"]: m["unit"] for m in bench[group]}
    metrics, selects = {}, None
    for metric in metric_names(bench, name, group):
        reader = importlib.import_module(f"benchmarks.metrics.{metric}")
        value = reader.read(ctx)
        if value is not None:
            metrics[metric] = {"value": float(value), "unit": units[metric]}
        # A kernel metric's selector splits the breakdown's ``other``.
        selects = selects or getattr(reader, "selects", None)

    device = {"platform": devices[0].platform, "kind": kind_of_chip,
              "count": chips, "memory_peak_bytes": run["memory_peak_bytes"]}
    result = {"correct": bool(correct), "attempted": plan.steps, "failed": 0,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = run["window_seconds"]
        result["breakdown"] = tracing.breakdown(reduced, selects)
    # What the host held at the most (the observer's trees and those the
    # comparison holds of the reference's are host memory): kilobytes on
    # Linux.
    run["host_peak_rss_bytes"] = 1024 * resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss
    result["run"] = {k: run[k] for k in (
        "steps", "samples", "window_seconds", "setup_seconds",
        "compile_seconds", "reference_seconds", "host_peak_rss_bytes")}
    if trace:
        result["run"]["memory_stats"] = run["memory_stats"]
    if study is not None:
        result["study"] = study({
            "reference": reference, "spec": spec, "arrays": arrays,
            "seed": program_seed, "steps": workload["compare_steps"],
            "found": found, "limits": limits})
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument(
        "--rehearse", action="store_true",
        help="run the configuration's tiny 'rehearse' sizes on whatever "
             "backend JAX has; the numbers are not readings")
    args = parser.parse_args(argv)

    _, cell, _, _ = load_cell(args.workload, args.rehearse)
    import jax

    devices = jax.devices()
    if not args.rehearse and (devices[0].platform != "tpu"
                              or len(devices) < cell["chips"]):
        print(f"{args.workload} needs {cell['chips']} TPU chip(s); JAX has "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return NO_CHIP
    enable_compilation_cache()
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), rehearse=args.rehearse)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']:.6g} (limit {c['limit']:.6g}, "
              f"at {c['at']})", file=sys.stderr)
    if args.rehearse:
        print("REHEARSAL on", result["device"]["platform"],
              "at the configuration's tiny sizes: nothing on the next line "
              "is a reading")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
