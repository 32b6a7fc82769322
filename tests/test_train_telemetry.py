"""The train loops measured from inside (docs/OBSERVABILITY.md "Training
loops"): ``df2.*`` scopes on the step programs' operations,
``df2.train.*`` host spans on the profiler's clock, the ``training``
counter block, and ``df2-trace-tool train`` with its XSpace decoder.

One profiler session for the whole file (the ``traced`` fixture): a tiny
``train_gnn`` and a tiny ``train_gat`` on one CPU device.
"""

import json
import os
import re
import time

import jax
import numpy as np
import pytest

from dragonfly2_tpu import traintrace
from dragonfly2_tpu.data import SyntheticCluster
from dragonfly2_tpu.parallel import data_parallel_mesh
from dragonfly2_tpu.train import (
    GNNTrainConfig,
    fused_sampling,
    gat_trainer,
    train_gnn,
)
from dragonfly2_tpu.train.gat_trainer import GATTrainConfig, train_gat
from dragonfly2_tpu.train.step_budget import TRAINING, StepBudget
from dragonfly2_tpu.utils import xplane

RECORDED = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "tests", "data",
    "tpu_v5e_small.xplane.pb")

SAGE_SCOPES = ("df2.batch", "df2.sample.hop1", "df2.sample.hop2",
               "df2.features", "df2.model", "df2.loss", "df2.optimizer")
GAT_SCOPES = ("df2.attn.gather", "df2.attn.gather_bwd", "df2.model",
              "df2.loss", "df2.optimizer")
GNN_SPANS = ("df2.train.step", "df2.train.wait_input", "df2.train.input",
             "df2.train.dispatch", "df2.train.tick", "df2.train.epoch_end",
             "df2.train.drain", "df2.train.epoch_order")
SETUP_PHASES = ("data", "state", "tables")
GAT_SPANS = tuple(s for s in GNN_SPANS if s != "df2.train.wait_input")

GNN_CONFIG = dict(hidden=32, embed=16, batch_size=512, epochs=2,
                  eval_fraction=0.0, eval_max_seconds=0.0)
GAT_CONFIG = dict(hidden=32, embed=16, layers=1, heads=2,
                  edge_batch_size=512, epochs=2, neighbor_cap=16,
                  eval_fraction=0.0)


class _CompiledStepText:
    """``jax`` for one module of the program, as the benchmark's harness
    swaps it (so this also holds the names the harness goes by:
    ``jax.jit`` of a function called ``train_step``, through the
    module's own ``jax``): the first call of that step also keeps its
    compiled text."""

    def __init__(self):
        self.text = None

    def __getattr__(self, name):
        return getattr(jax, name)

    def jit(self, fun, **kwargs):
        jitted = jax.jit(fun, **kwargs)
        if fun.__name__ != "train_step":
            return jitted

        def step(*args):
            if self.text is None:
                self.text = jitted.lower(*args).compile().as_text()
            return jitted(*args)
        return step


@pytest.fixture(scope="module")
def graph():
    return SyntheticCluster(n_hosts=100, seed=0).probe_graph(10000)


@pytest.fixture(scope="module")
def mesh():
    return data_parallel_mesh(devices=jax.devices()[:1])


@pytest.fixture(scope="module")
def traced(graph, mesh, tmp_path_factory):
    """Both loops once, under one profiler session with the harness's
    profiler options; each run's result, its share of the ``training``
    block, its step's compiled text, and the dump decoded."""
    out = tmp_path_factory.mktemp("profile")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    runs = {}
    jax.profiler.start_trace(str(out), profiler_options=options)
    try:
        for name, module, run in (
                ("gnn", fused_sampling, lambda: train_gnn(
                    graph, GNNTrainConfig(**GNN_CONFIG), mesh)),
                ("gat", gat_trainer, lambda: train_gat(
                    graph, GATTrainConfig(**GAT_CONFIG), mesh))):
            recorder, before = _CompiledStepText(), TRAINING.snapshot()
            real, module.jax = module.jax, recorder
            start = time.perf_counter()
            try:
                result = run()
            finally:
                module.jax = real
            wall = time.perf_counter() - start
            after = TRAINING.snapshot()
            runs[name] = {
                "result": result, "text": recorder.text, "wall": wall,
                "block": {k: after[k] - before[k] for k in after}}
    finally:
        jax.profiler.stop_trace()
    planes = xplane.read_xspace(xplane.find_xplane(str(out)))
    host = next(p for p in planes if p.name == traintrace.HOST_PLANE)
    loop = max(host.lines, key=lambda ln: sum(
        ev.name == "df2.train.step" for ev in ln.events))
    workers = [ln for ln in host.lines if ln is not loop and any(
        ev.name == "df2.train.input" for ev in ln.events)]
    starts = [ev.start_ns for ev in loop.events
              if ev.name == "df2.train.step" and ev.stats["step_num"] == 0]
    assert len(starts) == 2
    # The two loops ran one after the other on this thread: split its
    # spans where the second call's set-up starts.
    cut = sorted(ev.start_ns for ev in loop.events
                 if ev.name == "df2.setup.data")[1]
    assert cut < starts[1]
    for name, inside in (("gnn", lambda ev: ev.start_ns < cut),
                         ("gat", lambda ev: ev.start_ns >= cut)):
        runs[name]["loop"] = [ev for ev in loop.events if inside(ev)
                              and ev.name.startswith("df2.train.")]
        runs[name]["setup"] = [ev for ev in loop.events if inside(ev)
                               and ev.name.startswith("df2.setup.")]
    runs["gnn"]["workers"] = [ev for ln in workers for ev in ln.events
                              if ev.name == "df2.train.input"]
    runs["gat"]["workers"] = []
    runs["dump"] = str(out)
    return runs


def _op_names(text: str) -> list:
    return re.findall(r'op_name="([^"]*)"', text)


# -- (a) device scopes -------------------------------------------------------

@pytest.mark.parametrize("scope", SAGE_SCOPES)
def test_fused_graphsage_step_carries_scope(traced, scope):
    names = _op_names(traced["gnn"]["text"])
    assert any(scope in traintrace.scopes_of(n) for n in names), scope


@pytest.mark.parametrize("scope", GAT_SCOPES)
def test_graph_transformer_step_carries_scope(traced, scope):
    names = _op_names(traced["gat"]["text"])
    assert any(scope in traintrace.scopes_of(n) for n in names), scope


def test_gather_bwd_scope_is_on_the_backward_pass_only(traced):
    names = _op_names(traced["gat"]["text"])
    backward = [n for n in names
                if "df2.attn.gather_bwd" in traintrace.scopes_of(n)]
    assert backward and all("transpose(" in n for n in backward)
    # The forward's scope does not reach the custom backward, and the
    # backward's is not on the forward gathers.
    forward = [n for n in names
               if "df2.attn.gather" in traintrace.scopes_of(n)]
    assert forward and not any("transpose(" in n for n in forward)


def test_scope_names_are_read_under_transformation_wrappers():
    path = ("jit(train_step)/while/body/closed_call/transpose(jvp(df2.model"
            "))/GraphTransformer/blocks_1/df2.attn.gather_bwd/gather")
    assert traintrace.scopes_of(path) == ["df2.model", "df2.attn.gather_bwd"]
    assert traintrace.scopes_of("jit(f)/jit(main)/mul") == []
    assert traintrace.scopes_of(None) == []
    # A name inside another word is not a scope.
    assert traintrace.scopes_of("jit(f)/xdf2.model/mul") == []


# -- (b) host spans ----------------------------------------------------------

@pytest.mark.parametrize("loop,spans", [("gnn", GNN_SPANS),
                                        ("gat", GAT_SPANS)])
def test_every_host_span_is_in_the_dump(traced, loop, spans):
    run = traced[loop]
    seen = {ev.name for ev in run["loop"]} | {
        ev.name for ev in run["workers"]}
    assert set(spans) <= seen
    if loop == "gat":
        assert "df2.train.wait_input" not in seen


@pytest.mark.parametrize("loop", ["gnn", "gat"])
def test_dispatch_and_tick_nest_in_step_once_per_step(traced, loop):
    run = traced[loop]
    steps = [(ev.start_ns, ev.start_ns + ev.duration_ns)
             for ev in run["loop"] if ev.name == "df2.train.step"]
    assert len(steps) == run["result"].steps
    for inner in ("df2.train.dispatch", "df2.train.tick"):
        spans = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                 for ev in run["loop"] if ev.name == inner]
        assert len(spans) == run["result"].steps
        for (a, b), (lo, hi) in zip(sorted(spans), sorted(steps)):
            assert lo <= a and b <= hi
    numbers = [ev.stats["step_num"] for ev in run["loop"]
               if ev.name == "df2.train.step"]
    assert numbers == list(range(len(steps)))


@pytest.mark.parametrize("loop", ["gnn", "gat"])
def test_input_span_carries_epoch_and_step(traced, loop):
    run = traced[loop]
    inputs = [ev for ev in run["loop"] + run["workers"]
              if ev.name == "df2.train.input"]
    assert len(inputs) == run["result"].steps
    keys = sorted((int(ev.stats["epoch"]), int(ev.stats["step"]))
                  for ev in inputs)
    per_epoch = run["result"].steps // 2
    assert keys == [(e, s) for e in range(2) for s in range(per_epoch)]
    if loop == "gnn":
        # Built ahead, on the prefetch workers' threads.
        assert len(run["workers"]) == len(inputs)


@pytest.mark.parametrize("loop", ["gnn", "gat"])
def test_epoch_order_is_a_span_per_epoch_outside_the_steps(traced, loop):
    """The epoch's permutation: in ``train_gnn`` the task generator draws
    it on the loop's thread, inside ``df2.train.wait_input``, so an idle
    gap at an epoch's edge is named by it and ``input_wait_ms`` still
    holds it; in no loop is it inside a ``df2.train.step``."""
    events = traced[loop]["loop"]

    def spans(name):
        return [(ev.start_ns, ev.start_ns + ev.duration_ns)
                for ev in events if ev.name == name]

    orders = spans("df2.train.epoch_order")
    assert len(orders) == 2
    for a, b in orders:
        assert not any(lo <= a and b <= hi
                       for lo, hi in spans("df2.train.step"))
        inside_wait = any(lo <= a and b <= hi
                          for lo, hi in spans("df2.train.wait_input"))
        assert inside_wait == (loop == "gnn")


def test_epoch_end_is_a_span_per_epoch(traced):
    for loop in ("gnn", "gat"):
        ends = [ev for ev in traced[loop]["loop"]
                if ev.name == "df2.train.epoch_end"]
        assert len(ends) == 2 == len(traced[loop]["result"].history)


# -- (c) the training block --------------------------------------------------

@pytest.mark.parametrize("loop,batch", [("gnn", 512), ("gat", 512)])
def test_training_block_agrees_with_the_result(traced, loop, batch):
    block, result = traced[loop]["block"], traced[loop]["result"]
    assert block["loops_started"] == 1
    assert block["steps"] == block["dispatches"] == result.steps
    # The first step's samples go with its compile.
    assert block["samples"] == (result.steps - 1) * batch
    assert block["compile_seconds"] == pytest.approx(result.compile_seconds)
    # The step program (and this file's second look at it) at least.
    assert block["loop_compiles"] >= 1
    assert block["steady_compiles"] == 0


@pytest.mark.parametrize("loop", ["gnn", "gat"])
def test_set_up_phases_fit_in_the_call(traced, loop):
    """Each phase once, before the loop, on the loop's thread; the
    block's phase seconds and the loop's compile seconds are positive
    and together no more than the call took."""
    block, run = traced[loop]["block"], traced[loop]
    seconds = [block[f"setup_{p}_seconds"] for p in SETUP_PHASES]
    assert all(s > 0 for s in seconds)
    assert 0 < block["loop_compile_seconds"]
    assert sum(seconds) + block["loop_compile_seconds"] <= run["wall"]
    setup = run["setup"]
    assert sorted(ev.name for ev in setup) == sorted(
        f"df2.setup.{p}" for p in SETUP_PHASES)
    first_step = min(ev.start_ns for ev in run["loop"])
    assert all(ev.start_ns + ev.duration_ns <= first_step for ev in setup)
    for ev in setup:
        phase = ev.name.split(".")[-1]
        assert ev.duration_ns * 1e-9 <= block[f"setup_{phase}_seconds"]


def test_steps_counts_optimizer_steps_of_a_multi_step_dispatch(graph, mesh):
    before = TRAINING.snapshot()
    result = train_gnn(graph, GNNTrainConfig(
        steps_per_call=4, **GNN_CONFIG), mesh)
    after = TRAINING.snapshot()
    assert after["dispatches"] - before["dispatches"] == result.steps
    assert after["steps"] - before["steps"] == 4 * result.steps


def test_tail_program_compiled_mid_run_is_a_steady_compile(graph, mesh):
    """19 steps an epoch in groups of 4 leave a tail of 3: a second scan
    program, compiled after the loop's first tick."""
    before = TRAINING.snapshot()
    result = train_gat(graph, GATTrainConfig(
        steps_per_call=4, **dict(GAT_CONFIG, epochs=1)), mesh)
    after = TRAINING.snapshot()
    assert result.steps == 5
    assert after["steady_compiles"] - before["steady_compiles"] >= 1
    assert after["steps"] - before["steps"] == 19


def test_compiles_outside_a_loop_are_not_counted():
    before = TRAINING.snapshot()
    jax.jit(lambda x: x * 3 + 1)(np.arange(7.0)).block_until_ready()
    assert TRAINING.snapshot() == before


def test_a_loop_that_never_finishes_stops_counting():
    before = TRAINING.snapshot()["loop_compiles"]
    budget = StepBudget()
    jax.jit(lambda x: x * 5 + 1)(np.arange(7.0)).block_until_ready()
    assert TRAINING.snapshot()["loop_compiles"] == before + 1
    del budget  # the loop raised: no finish()
    jax.jit(lambda x: x * 7 + 1)(np.arange(7.0)).block_until_ready()
    assert TRAINING.snapshot()["loop_compiles"] == before + 1


def test_loop_compiles_is_the_same_cold_and_warm(graph, mesh, tmp_path):
    """A cache load fires the backend-compile event like a compile does,
    so the count does not depend on what the persistent cache holds."""
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    hits = []

    def on_event(event, **_):
        if event.endswith("/cache_hits"):
            hits.append(event)

    jax.monitoring.register_event_listener(on_event)
    try:
        jax.config.update(names[0], str(tmp_path))
        jax.config.update(names[1], 0.0)
        jax.config.update(names[2], -1)
        compilation_cache.reset_cache()
        counts = []
        for _ in ("cold", "warm"):
            before, n_hits = TRAINING.snapshot(), len(hits)
            train_gnn(graph, GNNTrainConfig(
                **dict(GNN_CONFIG, epochs=1, hidden=24)), mesh)
            after = TRAINING.snapshot()
            counts.append((after["loop_compiles"] - before["loop_compiles"],
                           len(hits) - n_hits))
    finally:
        jax.monitoring.unregister_event_listener(on_event)
        for name, value in saved.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
    (cold, cold_hits), (warm, warm_hits) = counts
    assert cold == warm >= 1
    assert cold_hits == 0 and warm_hits >= 1


def test_training_block_loses_no_update_under_threads():
    import sys
    import threading

    workers, each = 4 * (os.cpu_count() or 4), 2000
    before = TRAINING.snapshot()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(each):
                TRAINING.add(dispatches=1, samples=3)
                TRAINING.executable_built()  # no loop is open: counts nothing

        threads = [threading.Thread(target=work) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    after = TRAINING.snapshot()
    assert after["dispatches"] - before["dispatches"] == workers * each
    assert after["samples"] - before["samples"] == 3 * workers * each
    assert after["loop_compiles"] == before["loop_compiles"]


def test_training_block_is_exported(traced):
    from dragonfly2_tpu.utils import debugmon, prombridge

    block = debugmon.debug_vars()["training"]
    assert set(block) == set(TRAINING.KEYS)
    assert block["steps"] >= traced["gnn"]["result"].steps
    exported = {family.name: family for family in
                prombridge.DebugVarsCollector().collect()}
    for key in TRAINING.KEYS:
        assert f"df2_training_{key}" in exported
    assert exported["df2_training_steps"].samples[0].value == float(
        TRAINING.snapshot()["steps"])


# -- (d) the result ----------------------------------------------------------

def test_gat_result_has_steps_and_compile_seconds(traced):
    result = traced["gat"]["result"]
    assert result.steps == 2 * (10000 // 512)
    assert result.compile_seconds > 0


# -- (e) df2-trace-tool train and the decoder --------------------------------

@pytest.fixture(scope="module")
def recorded():
    return xplane.read_xspace(RECORDED)


def test_decoder_reads_event_metadata_stats(recorded):
    device = next(p for p in recorded if p.name == "/device:TPU:0")
    ops = next(ln for ln in device.lines if ln.name == "XLA Ops")
    assert len(ops.events) == 14144
    assert sum(bool(ev.stats.get("tf_op")) for ev in ops.events) == 5004
    dispatches = [ev for p in recorded for ln in p.lines for ev in ln.events
                  if ev.name == "bench.dispatch"]
    assert len(dispatches) == 20
    assert all(ev.duration_ns > 0 for ev in dispatches)


def test_decoder_agrees_with_profile_data(recorded):
    from jax.profiler import ProfileData

    theirs = {p.name: p for p in ProfileData.from_file(RECORDED).planes}
    assert [p.name for p in recorded] == list(theirs)
    for plane in recorded:
        lines = list(theirs[plane.name].lines)
        assert [ln.name for ln in plane.lines] == [ln.name for ln in lines]
        for mine, line in zip(plane.lines, lines):
            events = list(line.events)
            assert len(mine.events) == len(events)
            for a, b in list(zip(mine.events, events))[:50]:
                assert a.name == b.name
                assert abs(a.start_ns - b.start_ns) < 1.001
                assert abs(a.duration_ns - b.duration_ns) < 1.001


def test_a_scope_is_a_union_not_a_sum():
    """A ``while`` and the operations of its body are both events: the
    scope's time is what they cover together, once."""
    def op(name, start, length, path):
        return xplane.Event(name, start, length, {"tf_op": path})

    bwd = "jit(train_step)/transpose(jvp(df2.model))/df2.attn.gather_bwd/"
    ops = xplane.Line("XLA Ops", [
        op("%while.5", 0.0, 1000.0, bwd + "while"),
        op("%dynamic-slice.1", 0.0, 400.0, bwd + "while/body/dynamic_slice"),
        op("%reshape.2", 400.0, 600.0, bwd + "while/body/reshape"),
        op("%fusion.7", 1000.0, 500.0, bwd + "gather"),
        op("%copy.1", 1500.0, 100.0, ""),  # the compiler's own: no path
        op("%fusion.9", 1600.0, 300.0, "jit(train_step)/jvp(df2.model)/dot"),
        op("%copy.3", 2000.0, 100.0, ""),
    ])
    modules = xplane.Line("XLA Modules", [
        xplane.Event("jit_train_step(1)", 0.0, 2100.0, {})])
    loop = xplane.Line("python3", [
        xplane.Event("df2.train.step", -50.0, 2500.0, {"step_num": 0}),
        xplane.Event("df2.train.dispatch", -50.0, 40.0, {}),
        xplane.Event("df2.train.tick", 0.0, 2400.0, {})])
    report = traintrace.analyze_planes([
        xplane.Plane("/device:TPU:0", [modules, ops]),
        xplane.Plane("/host:CPU", [loop])])
    (device,) = report["devices"]
    assert device["steps"] == report["host_steps"] == 1
    scopes = device["scopes"]
    assert scopes["df2.attn.gather_bwd"]["ms_per_step"] == pytest.approx(
        1.5e-3)
    assert scopes["df2.attn.gather_bwd"]["summed_ms_per_step"] == (
        pytest.approx(2.5e-3))
    assert scopes["df2.model"]["ms_per_step"] == pytest.approx(1.8e-3)
    assert scopes["df2.model"]["self_ms_per_step"] == pytest.approx(0.3e-3)
    assert device["busy_ms_per_step"] == pytest.approx(2.0e-3)
    assert device["unscoped_ms_per_step"] == pytest.approx(0.2e-3)
    # What has no path is listed by the scope whose operations ran next.
    assert {o["op"]: o["runs_before"] for o in device["unscoped_ops"]} == {
        "%copy.1": "df2.model", "%copy.3": traintrace.NO_SCOPE}
    assert device["unscoped_ms_per_step_before"] == {
        "df2.model": pytest.approx(0.1e-3),
        traintrace.NO_SCOPE: pytest.approx(0.1e-3)}
    # The gap 1900-2000: the loop's thread was in its tick.
    assert [g["host_span"] for g in device["idle_gaps"]] == [
        "df2.train.tick"]
    assert "ran just before df2.model" in traintrace.format_report(
        {"path": "made by hand", **report})


def test_step_facts_reach_the_report(traced, capsys):
    """What ``train_gnn`` writes on its step spans (the sampler's row
    width) is in the report and in the tool's text; a loop that writes
    nothing (``train_gat``) leaves it empty."""
    from dragonfly2_tpu.cmd import tracetool

    def loop(**stats):
        return xplane.Plane("/host:CPU", [xplane.Line("python3", [
            xplane.Event("df2.train.step", 0.0, 10.0,
                         {"_r": 1, "step_num": n, **stats})
            for n in range(2)])])

    report = traintrace.analyze_planes([loop(sampler_row_width=256)])
    assert report["step_facts"] == {"sampler_row_width": 256}
    assert "sampler_row_width 256" in traintrace.format_report(
        {"path": "made by hand", **report})
    assert traintrace.analyze_planes([loop()])["step_facts"] == {}
    # The traced GraphSAGE loop: its longest row is past 127 records.
    assert tracetool.main(["train", traced["dump"], "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["step_facts"] == {
        "sampler_row_width": 256}


def test_scopes_on_the_recorded_trace(recorded):
    """The flax path stands in for a ``df2.*`` scope in a trace recorded
    before they existed."""
    report = traintrace.analyze(RECORDED, scope=re.compile(
        r"GraphTransformer\.node_embeddings"))
    (device,) = report["devices"]
    assert device["steps"] == 20 and "train_step" in device["step_program"]
    (scope,) = device["scopes"].values()
    assert 0 < scope["ms_per_step"] <= device["busy_ms_per_step"]
    assert scope["ms_per_step"] <= scope["summed_ms_per_step"]
    assert 0.5 < device["scoped_share_of_busy"] <= 1.0
    assert device["unscoped_ms_per_step"] == pytest.approx(
        device["busy_ms_per_step"]
        * (1 - device["scoped_share_of_busy"]))
    assert len(device["idle_gaps"]) == 5
    # Recorded before the loops had spans of their own.
    assert {g["host_span"] for g in device["idle_gaps"]} == {
        traintrace.NO_SPAN}
    assert traintrace.analyze(RECORDED)["devices"][0]["scopes"] == {}


@pytest.mark.parametrize("as_json", [True, False])
def test_trace_tool_train(capsys, as_json):
    from dragonfly2_tpu.cmd import tracetool

    argv = ["train", RECORDED, "--scope", r"blocks_\d"]
    assert tracetool.main(argv + (["--json"] if as_json else [])) == 0
    out = capsys.readouterr().out
    if as_json:
        report = json.loads(out)
        assert set(report["devices"][0]["scopes"]) == {"blocks_0", "blocks_1"}
    else:
        assert "blocks_0" in out and "longest idle gaps" in out


def test_trace_tool_train_on_a_cpu_dump(traced, capsys):
    """No device plane on the CPU: the host part still reads."""
    from dragonfly2_tpu.cmd import tracetool

    assert tracetool.main(["train", traced["dump"], "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["devices"] == []
    steps = traced["gnn"]["result"].steps + traced["gat"]["result"].steps
    assert report["host_steps"] == steps
    (loop,) = [t for t in report["threads"] if t["loop"]]
    assert loop["spans"]["df2.train.dispatch"]["count"] == steps
    assert any("df2.train.input" in t["spans"] for t in report["threads"]
               if not t["loop"])


@pytest.mark.parametrize("as_json", [True, False])
def test_trace_tool_lists_the_set_up_phases(traced, capsys, as_json):
    """Beside the loop's spans, each set-up phase of both trainers with
    its total, not per step."""
    from dragonfly2_tpu.cmd import tracetool

    argv = ["train", traced["dump"]] + (["--json"] if as_json else [])
    assert tracetool.main(argv) == 0
    out = capsys.readouterr().out
    names = [f"df2.setup.{p}" for p in SETUP_PHASES]
    if not as_json:
        lines = [ln for ln in out.splitlines() if "(set-up)" in ln]
        assert sorted(ln.split()[0] for ln in lines) == names
        return
    (loop,) = [t for t in json.loads(out)["threads"] if t["loop"]]
    assert sorted(loop["setup"]) == names
    for name in names:
        assert loop["setup"][name]["count"] == 2
        assert loop["setup"][name]["total_ms"] > 0
        assert "ms_per_step" not in loop["setup"][name]
        assert name not in loop["spans"]
