"""The harness end to end at the configurations' tiny ``rehearse``
sizes, the trace reduction on a recorded trace, and the count functions
against numbers worked by hand."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _env(tmp_path, **more):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"), **more)
    env.pop("XLA_FLAGS", None)
    env.update(more)
    return env


def _run(args, env):
    return subprocess.run(
        [sys.executable, *BENCH["command"][1:], *args], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)


def _expected(cell, group):
    return {m["name"] for m in BENCH[group]
            if cell in m.get("workloads", [cell])}


def _chip_only(names):
    """The metrics whose reader says that only a chip feeds it
    (``chip_only = True`` in ``metrics/<name>.py``): allocator
    statistics, scope paths of a TPU trace."""
    import importlib

    return {n for n in names if getattr(importlib.import_module(
        f"benchmarks.metrics.{n}"), "chip_only", False)}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses(cell, trace, tmp_path):
    done = _run(["--workload", cell, "--seed", "3000000019", "--seconds",
                 "1", "--trace", str(trace), "--rehearse"], _env(tmp_path))
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    assert lines[-2].startswith("REHEARSAL")
    result = json.loads(lines[-1])
    assert RESULT_KEYS <= set(result)
    assert list(result)[-1] == "compared"
    group = "per_layer" if trace else "end_to_end"
    # A reader that only a chip feeds finds nothing to read here and the
    # metric is left out, as the rule is; every other one is reported.
    listed = _expected(cell, group)
    assert set(result["metrics"]) == listed - _chip_only(listed)
    units = {m["name"]: m["unit"] for m in BENCH[group]}
    for name, metric in result["metrics"].items():
        assert metric["unit"] == units[name] and metric["value"] > 0
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["correct"] is True, result["compared"]
    for number in result["compared"].values():
        assert {"value", "limit"} <= set(number)
    for name in result["compared"]:
        assert f"compared {name}:" in done.stderr
    if trace:
        assert result["device"]["busy_s"] > 0
        assert result["device"]["window_s"] >= 1.0
        assert len(result["breakdown"]["device_ops"]) <= 10
        assert result["breakdown"]["device_ops"][-1][0] == "other"
        assert len(result["breakdown"]["idle_gaps"]) <= 10
        for share in ("step_mfu", "gather_roofline"):
            assert result["metrics"][share]["value"] <= 100.0


def test_without_a_chip_there_is_no_result(tmp_path):
    done = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                 "--trace", "0"], _env(tmp_path))
    assert done.returncode != 0
    assert done.stdout.strip() == ""


def test_four_chip_path_on_virtual_devices(tmp_path):
    """A ``chips: 4`` cell is a workload file and nothing else: the mesh
    comes from the cell's ``chips``, the batch from ``batch_per_chip``."""
    code = """
import json, sys
sys.path.insert(0, %r)
import jax
from benchmarks import run
plain = run.load_cell
def four(name, rehearse):
    bench, cell, workload, spec = plain(name, rehearse)
    cell, workload = dict(cell, chips=4), dict(workload, batch_per_chip=True)
    return bench, cell, workload, dict(spec, batch=spec["batch"] * 4)
run.load_cell = four
assert len(jax.devices()) == 4
out = run.run_cell("sage-fleet100k.train", 5, 1.0, False, rehearse=True)
print(json.dumps(out))
""" % ROOT
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=900, env=_env(
            tmp_path, XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert done.returncode == 0, done.stderr[-3000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["device"]["count"] == 4
    assert result["correct"] is True, result["compared"]
    assert result["run"]["samples"] == result["run"]["steps"] * 128 * 4


def test_trace_reduction_on_a_recorded_trace():
    """``data/tpu_v5e_small.xplane.pb``: the GraphTransformer cell at its
    rehearsal size, traced on a TPU v5e (PR 23)."""
    from jax.profiler import ProfileData

    from benchmarks import trace
    from benchmarks.metrics import gather_roofline

    path = os.path.join(os.path.dirname(__file__), "data",
                        "tpu_v5e_small.xplane.pb")
    reduced = trace.reduce(path)
    assert reduced.chips == 1
    span = (reduced.last_ns - reduced.first_ns) * 1e-9
    assert 0 < reduced.busy_s <= span
    # The module line brackets each program's operations, so its total
    # bounds the union of the operation line from above, closely.
    plane = next(p for p in ProfileData.from_file(path).planes
                 if p.name == "/device:TPU:0")
    modules = next(ln for ln in plane.lines if ln.name == "XLA Modules")
    module_s = sum(ev.duration_ns for ev in modules.events) * 1e-9
    assert 0.5 * module_s <= reduced.busy_s <= module_s * 1.001
    gathers = reduced.seconds_where(gather_roofline.selects)
    assert 0 < gathers < sum(reduced.op_seconds.values())
    out = trace.breakdown(reduced, gather_roofline.selects)
    assert len(out["device_ops"]) == 10 and len(out["idle_gaps"]) == 5
    seconds = [s for _, s in out["device_ops"][:-1]]
    assert seconds == sorted(seconds, reverse=True)
    assert all(len(name) <= 160 for name, _ in out["device_ops"])


def test_union_of_intervals():
    from benchmarks import trace

    total, gaps = trace._union([(0, 10), (5, 12), (20, 30), (21, 22)])
    assert total == 22 and gaps == [(12, 20)]


def test_gather_selector():
    from benchmarks.metrics.gather_roofline import selects

    assert selects(
        "%fusion.12 = bf16[2550000,256]{1,0:T(8,128)(2,1)} fusion(bf16[2550000"
        ",256]{1,0:T(8,128)(2,1)} %copy.2285, s32[2550784]{0:T(1024)S(1)} "
        "%pad_clamp_fusion), kind=kCustom, calls=%fused_computation.4.clone")
    assert selects("%gather.5 = f32[100,8]{1,0} gather(f32[1000,8]{1,0} %p, "
                   "s32[100,1]{1,0} %i), offset_dims={1}")
    assert selects("copy_gather_fusion.3")
    assert selects("%table_gather_fwd.1 = bf16[8,8]{1,0} custom-call(%x)")
    assert not selects("%reshape.1970 = bf16[1,1,2550000]{2,1,0} reshape("
                       "bf16[2550000]{0} %dynamic-slice.4)")
    assert not selects("%copy.1 = f32[8]{0} copy(f32[8]{0} %gather.5)")
    assert not selects(
        "%fusion.429 = bf16[4,64,50000,51]{3,2,1,0} fusion(bf16[50000,51,4,32]"
        "{1,0,3,2} %a, bf16[50000,51,4,32]{1,0,3,2} %b), kind=kLoop, calls=%f")
    assert not selects("%fusion.99 = f32[8]{0} fusion(f32[8]{0} %a, u32[] %c)"
                       ", kind=kCustom, calls=%f")


def test_counts_against_hand_worked_numbers():
    from benchmarks.counts import graph_transformer, graphsage

    fleet = {"hosts": 10, "probe_count": 1, "rounds": [2, 2],
             "probed": [1, 3]}
    gat = {"fleet": fleet, "batch": 3,
           "model": {"hidden": 4, "embed": 2, "layers": 1, "heads": 2,
                     "neighbor_cap": 64}}
    # 10 hosts send 2 probes each: 20 records, so 10 + 2*20 = 50 filled
    # slots (under 10*64). One block: projections 4*2*10*4*4 = 1280,
    # MLP 2*2*10*4*8 = 1280, attention 2*2*50*4 = 800 -> 3360; embedding
    # 2*10*4*2 = 160; head 2*3*4*2 + 2*3*2 = 60 -> 3580 with input
    # gradients (x3) and the input projection 2*10*8*4 = 640 without (x2).
    assert graph_transformer.shapes(gat)["list_slots"] == 50
    assert graph_transformer.flops_per_step(gat) == 3 * 3580 + 2 * 640
    # [k|v] rows of 2*4*2 = 16 B: 1 layer * 2 ways * 50 slots = 1600 B;
    # head 2 ways * 2*3 rows * 2*2 B = 48 B.
    assert graph_transformer.gather_bytes_per_step(gat) == 1648
    # The cap bounds the slots: 10 hosts * 4.
    capped = dict(gat, model=dict(gat["model"], neighbor_cap=4))
    assert graph_transformer.shapes(capped)["list_slots"] == 40

    sage = {"batch": 3, "model": {"hidden": 4, "embed": 2, "fanouts": [2, 2]}}
    # Layer 1 on 2*3*2 + 2*3 = 18 rows of 18 -> 4: 2*18*18*4 = 2592 (x2);
    # layer 2 2*6*8*2 = 192, head 2*3*8*4 + 2*3*4 = 216 (x3).
    assert graphsage.flops_per_step(sage) == 2 * 2592 + 3 * (192 + 216)
    # Per edge 12 B; centres 2*(8+32) = 80; hop 1 4*(8+32+8) = 192;
    # hop 2 8*(8+32) = 320 -> 604 B, times 3 edges.
    assert graphsage.gather_bytes_per_step(sage) == 3 * 604


def test_peaks_table():
    from benchmarks import peaks

    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["source"]
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")


FLEET = {"hosts": 300, "probe_count": 2, "rounds": [1, 5], "probed": [4, 8],
         "regions": 4, "zones_per_region": 4, "racks_per_zone": 8,
         "seed_fraction": 0.05, "rtt_noise_sigma": 0.25}


def test_same_seed_same_graph_and_every_seed_the_same_sizes():
    import numpy as np

    from benchmarks import traffic

    a, b = traffic.probe_graph(FLEET, 2**31 + 7), traffic.probe_graph(
        FLEET, 2**31 + 7)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    sent, received = traffic.degree_sequences(FLEET)
    assert sent.sum() == received.sum() == 2 * 3 * 300
    for seed in (1, 2, 2**31 + 7):
        g = traffic.probe_graph(FLEET, seed)
        # The same sizes for every seed, dealt to other hosts: skewed
        # sending (2-10), receiving within its band (4-8).
        out = np.bincount(g["edge_src"], minlength=300)
        into = np.bincount(g["edge_dst"], minlength=300)
        assert np.array_equal(np.sort(out), np.sort(sent))
        assert np.array_equal(np.sort(into), np.sort(received))
        assert not np.any(g["edge_src"] == g["edge_dst"])


@pytest.mark.parametrize("config", [c["file"] for c in BENCH["configs"]])
def test_no_seed_changes_a_compiled_shape(config):
    """The widest neighbour list and the most lists a host appears in
    are shapes of the GraphTransformer's step: at the cell's own fleet
    they are the same for every seed, with lists ragged below the cap
    and cut above it."""
    import numpy as np

    from benchmarks import traffic
    from benchmarks.references import graph_transformer

    spec = json.load(open(os.path.join(ROOT, config)))
    if spec["kind"] != "graph_transformer":
        pytest.skip("no shape of this kind's step depends on degrees")
    fleet, cap = spec["fleet"], spec["model"]["neighbor_cap"]
    sent, received = traffic.degree_sequences(fleet)
    shapes = set()
    for seed in (1, 2, 3, 2**31 + 7, 3000000019):
        g = traffic.probe_graph(fleet, seed)
        nbr, _ = graph_transformer.neighbour_lists(
            fleet["hosts"], g["edge_src"], g["edge_dst"], g["edge_rtt_ns"],
            cap)
        listed = np.bincount(nbr[nbr >= 0], minlength=fleet["hosts"])
        shapes.add((nbr.shape[1], int(listed.max())))
        filled = (nbr >= 0).sum(1)
        assert filled.min() < cap / 2 and 0.1 < (filled == cap).mean() < 0.5
    assert shapes == {(cap, int(sent.max() + received.max()) + 1)}
