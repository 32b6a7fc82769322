"""On-device sampling (train/fused_sampling.py) + bench-accounting hooks.

Covers the round-3 verdict items: device-side fanout sampling correctness
vs the host CSR semantics, collective-free RNG (the hashed offsets),
StepBudget progress/compile callbacks, eval wall-cap, and the persistent
compilation cache helper.
"""

import numpy as np
import pytest

from dragonfly2_tpu.data import SyntheticCluster
from dragonfly2_tpu.data.graph_sampler import CSRGraph
from dragonfly2_tpu.parallel import data_parallel_mesh
from dragonfly2_tpu.train import GNNTrainConfig, train_gnn


@pytest.fixture(scope="module")
def graph():
    return SyntheticCluster(n_hosts=100, seed=0).probe_graph(10000)


@pytest.fixture(scope="module")
def csr(graph):
    return CSRGraph.from_graph(graph)


@pytest.fixture(scope="module")
def mesh():
    return data_parallel_mesh()


class TestDeviceSampling:
    def test_neighbors_are_real_and_masked(self, graph, csr, mesh):
        import jax

        from dragonfly2_tpu.train.fused_sampling import (
            put_graph_tables, sample_neighbors)

        gt = put_graph_tables(csr, mesh)
        # Fan-out leading, the batch trailing: four edges' (src, dst).
        nodes = np.array([[0, 2, 4, 6], [1, 3, 5, 7]], np.int32)
        nbr, rtt, mask = jax.jit(
            lambda n, s: sample_neighbors(gt, n, 7, s)
        )(mesh.put_replicated(nodes), np.uint32(42))
        nbr, rtt, mask = map(np.asarray, (nbr, rtt, mask))
        assert nbr.shape == rtt.shape == mask.shape == (7, 2, 4)
        for j in range(2):
            for i in range(4):
                v = nodes[j, i]
                real = set(csr.indices[csr.indptr[v]:csr.indptr[v + 1]])
                deg = len(csr.indices[csr.indptr[v]:csr.indptr[v + 1]])
                if deg == 0:
                    assert mask[:, j, i].sum() == 0
                else:
                    assert mask[:, j, i].sum() == 7  # replacement fills all
                    for k in range(7):
                        assert nbr[k, j, i] in real

    def test_zero_degree_last_node_padded(self, graph, mesh):
        """The highest-indexed node with no out-edges hits the CSR
        out-of-bounds trap (offset == n_edges) — must pad, not crash."""
        import jax

        from dragonfly2_tpu.data.features import Graph
        from dragonfly2_tpu.train.fused_sampling import (
            put_graph_tables, sample_neighbors)

        g = graph
        last = g.n_nodes - 1
        keep = (g.edge_src != last)
        g2 = Graph(g.node_ids, g.node_features, g.edge_src[keep],
                   g.edge_dst[keep], g.edge_rtt_ns[keep])
        gt = put_graph_tables(CSRGraph.from_graph(g2), mesh)
        nbr, rtt, mask = jax.jit(
            lambda n, s: sample_neighbors(gt, n, 5, s)
        )(mesh.put_replicated(np.array([last], np.int32)), np.uint32(0))
        assert np.asarray(mask).sum() == 0
        assert np.asarray(nbr).sum() == 0

    def test_salt_determinism(self, csr, mesh):
        import jax

        from dragonfly2_tpu.train.fused_sampling import (
            put_graph_tables, sample_neighbors)

        gt = put_graph_tables(csr, mesh)
        nodes = mesh.put_replicated(np.arange(16, dtype=np.int32))
        f = jax.jit(lambda n, s: sample_neighbors(gt, n, 5, s))
        a1, _, _ = f(nodes, np.uint32(7))
        a2, _, _ = f(nodes, np.uint32(7))
        b, _, _ = f(nodes, np.uint32(8))
        np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
        assert not np.array_equal(np.asarray(a1), np.asarray(b))

    def test_no_collectives_in_sampling(self, csr, mesh):
        """The sampling subprogram must partition with zero collectives —
        threefry over sharded shapes all-gathers inside its loop (and
        deadlocks XLA:CPU); the hashed-offset design may not regress."""
        import jax

        from dragonfly2_tpu.train.fused_sampling import (
            put_graph_tables, sample_neighbors)

        gt = put_graph_tables(csr, mesh)
        nodes_shaped = np.arange(mesh.n_data * 4, dtype=np.int32)
        f = jax.jit(
            lambda n, s: sample_neighbors(gt, n, 5, s, mesh.batch_sharding),
            in_shardings=(mesh.batch_sharding, None),
        )
        txt = f.lower(
            jax.device_put(nodes_shaped, mesh.batch_sharding), np.uint32(1)
        ).compile().as_text()
        for op in ("all-gather", "all-reduce", "collective-permute",
                   "all-to-all"):
            assert op not in txt, f"sampling program contains {op}"

    def test_hashed_bits_uniformity(self):
        """Counter-hash offsets must look uniform enough for replacement
        sampling: mod-8 buckets of a large draw within 5% of uniform."""
        import jax

        import jax.numpy as jnp

        from dragonfly2_tpu.train.fused_sampling import _hash_at

        draw = jax.jit(lambda s: _hash_at(
            s, jnp.arange(1 << 16, dtype=jnp.uint32)))
        bits = np.asarray(draw(np.uint32(123)))
        counts = np.bincount(bits % 8, minlength=8) / len(bits)
        assert np.all(np.abs(counts - 1 / 8) < 0.05 / 8 + 0.01)
        # And successive salts decorrelate.
        bits2 = np.asarray(draw(np.uint32(124)))
        assert (bits == bits2).mean() < 0.01


def _skewed_csr():
    """Five hosts whose rows run 0, 1, 3, 40 and 127 long: a zero-degree
    host in the middle and at the end, and a host whose row fills lane
    126, the last before the degree's lane of a 128-lane row."""
    degrees = np.array([3, 0, 127, 1, 40, 0])
    n = len(degrees)
    rng = np.random.default_rng(5)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(degrees, out=indptr[1:])
    return CSRGraph(
        indptr=indptr,
        indices=rng.integers(0, n, indptr[-1]).astype(np.int32),
        edge_rtt=rng.lognormal(0.0, 1.0, indptr[-1]).astype(np.float32),
        node_features=rng.normal(size=(n, 8)).astype(np.float32))


def _csr_form(fs, monkeypatch, csr, mesh):
    """The tables of the path no fleet takes: both limits at 0."""
    with monkeypatch.context() as m:
        m.setattr(fs, "ROW_PAD_FACTOR", 0)
        m.setattr(fs, "ROW_PAD_FREE_BYTES", 0)
        return fs.put_graph_tables(csr, mesh)


def _lowbias32(x):
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> np.uint32(15))
    x = x * np.uint32(0x846CA68B)
    return x ^ (x >> np.uint32(16))


def _host_draw(csr, nodes, fanout, salt):
    """The host sampler's tensors ``nodes.shape + (fanout,)`` for the
    device's hash: a slot's offset is the hash of its row-major position
    there, modulo the node's degree
    (``benchmarks/references/graphsage.py: _draw``, in numpy)."""
    shape = nodes.shape + (fanout,)
    position = np.arange(np.prod(shape), dtype=np.uint32).reshape(shape)
    salt = np.uint32(salt)
    with np.errstate(over="ignore"):
        bits = _lowbias32(_lowbias32(position + salt)
                          ^ (salt * np.uint32(0x9E3779B9)))
    start = csr.indptr[nodes][..., None]
    deg = (csr.indptr[nodes + 1] - csr.indptr[nodes])[..., None]
    pos = np.minimum(start + bits % np.maximum(deg, 1).astype(np.uint32),
                     len(csr.indices) - 1)
    mask = np.broadcast_to(deg > 0, shape).astype(np.float32)
    return (np.where(mask > 0, csr.indices[pos], 0).astype(np.int32),
            (csr.edge_rtt[pos] * mask).astype(np.float32), mask)


class TestRowTables:
    @pytest.mark.parametrize("sliced", [False, True],
                             ids=["one_piece", "sliced"])
    @pytest.mark.parametrize("devices", [1, 4, 8])
    @pytest.mark.parametrize("form", ["rows", "csr"])
    def test_both_forms_draw_the_host_samplers_slots(self, monkeypatch, form,
                                                     devices, sliced):
        """Both hops at fan-outs (10, 5), as ``sample_two_hops`` chains
        them, on either form of the graph: fan-outs leading and the batch
        trailing (``[10, 2, B]``, ``[5, 10, 2, B]``), and slot for slot the
        ids, RTTs and masks of the host sampler's row-major positions
        (``result.T`` is its ``[B, 2, 10(, 5)]`` tensor bit for bit),
        under jit on one device and under the batch sharding of a four-
        and an eight-device mesh, in one piece and in slices of the
        batch."""
        import jax

        from dragonfly2_tpu.train import fused_sampling as fs

        mesh = data_parallel_mesh(devices=jax.devices()[:devices])
        csr = _skewed_csr()
        if form == "rows":
            graph = fs.put_graph_tables(csr, mesh)
            assert isinstance(graph, fs.RowTables)
            assert graph.nbr_rows.shape == graph.rtt_rows.shape == (6, 128)
        else:
            graph = _csr_form(fs, monkeypatch, csr, mesh)
            assert isinstance(graph, fs.GraphTables)
        if sliced:
            # 64 batch rows of 2 and of 20 nodes: hop 2 in 16 slices on
            # one device, 4 on four and 2 on eight; hop 1 in 2 on one.
            monkeypatch.setattr(fs, "ROW_CHUNK_BYTES", 80 * 1024)
        b = mesh.batch_sharding if devices > 1 else None
        centers = np.random.default_rng(1).integers(
            0, 6, (64, 2)).astype(np.int32)
        centers[0] = (1, 5)                      # the zero-degree hosts
        centers[1] = (2, 2)                      # the full row

        def two_hops(graph, src, dst, s1, s2):
            import jax.numpy as jnp

            nodes = jnp.stack([src, dst], axis=0)
            nbr1, rtt1, mask1 = fs.sample_neighbors(graph, nodes, 10, s1, b)
            nbr2, rtt2, mask2 = fs.sample_neighbors(graph, nbr1, 5, s2, b)
            return nbr1, rtt1, mask1, nbr2, rtt2, mask2

        run = jax.jit(two_hops, in_shardings=(
            mesh.replicated, b or mesh.replicated, b or mesh.replicated,
            None, None))
        s1, s2 = 0xDEADBEEF, 77
        args = (*(mesh.put_batch(c) if b else c for c in centers.T.copy()),
                np.uint32(s1), np.uint32(s2))
        got = run(graph, *args)
        want1 = _host_draw(csr, centers, 10, s1)
        want = want1 + _host_draw(csr, want1[0], 5, s2)
        for name, x, y in zip("nbr1 rtt1 mask1 nbr2 rtt2 mask2".split(),
                              got, want):
            assert x.shape == y.shape[::-1] and x.dtype == y.dtype, name
            np.testing.assert_array_equal(
                np.asarray(x).T.view(np.int32), y.view(np.int32),
                err_msg=name)
        nbr1, _, mask1 = (np.asarray(x).T for x in got[:3])
        assert mask1[0].sum() == 0 and nbr1[0].sum() == 0
        assert mask1[1].sum() == 20
        # Offsets reach the row's last entry (lane 126 of the full row).
        last = csr.indices[csr.indptr[3] - 1]
        assert last in nbr1[1]
        text = run.lower(graph, *args).compile().as_text()
        if form == "rows":
            assert ("while" in text) == sliced
        for op in ("all-gather", "all-reduce", "collective-permute",
                   "all-to-all"):
            assert op not in text, f"{form}-path sampling contains {op}"

    def test_a_hub_keeps_the_csr_tables(self, monkeypatch, mesh):
        """One host of 300 records among 200 of 2: padding every row to
        384 lanes is 109 times the CSR. Past both limits the graph keeps
        its CSR form; under the byte limit alone it pads (a small graph
        pads for nothing)."""
        from dragonfly2_tpu.train import fused_sampling as fs
        from dragonfly2_tpu.train.step_budget import TRAINING

        degrees = np.full(201, 2)
        degrees[17] = 300
        indptr = np.zeros(202, np.int64)
        np.cumsum(degrees, out=indptr[1:])
        rng = np.random.default_rng(2)
        csr = CSRGraph(indptr, rng.integers(0, 201, indptr[-1]).astype(
            np.int32), rng.random(indptr[-1]).astype(np.float32),
            rng.normal(size=(201, 8)).astype(np.float32))
        assert fs.row_width(csr) == 384
        padded_bytes = 2 * 4 * 201 * 384
        assert 201 * 384 > fs.ROW_PAD_FACTOR * indptr[-1]
        assert isinstance(fs.put_graph_tables(csr, mesh), fs.RowTables)
        assert TRAINING.snapshot()["sampler_row_width"] == 384
        monkeypatch.setattr(fs, "ROW_PAD_FREE_BYTES", padded_bytes - 1)
        assert fs.row_width(csr) == 0
        tables = fs.put_graph_tables(csr, mesh)
        assert isinstance(tables, fs.GraphTables)
        assert TRAINING.snapshot()["sampler_row_width"] == 0
        np.testing.assert_array_equal(np.asarray(tables.indices),
                                      csr.indices)
        # Rows of one order pad whatever their bytes.
        monkeypatch.setattr(fs, "ROW_PAD_FACTOR", 200)
        assert fs.row_width(csr) == 384

    def test_rehearsal_fleet_takes_the_row_path(self, mesh):
        """``benchmarks/tests/`` rehearse the ``sage`` cells on a fleet of
        160 hosts: it must run the program the chip runs."""
        import json
        import os

        from benchmarks.traffic import probe_graph
        from dragonfly2_tpu.data.features import Graph
        from dragonfly2_tpu.train import fused_sampling as fs

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "benchmarks", "configs",
                               "sage-fleet100k.json")) as fh:
            config = json.load(fh)
        fleet = {**config["fleet"], **config["rehearse"]["fleet"]}
        arrays = probe_graph(fleet, seed=3)
        csr = CSRGraph.from_graph(Graph(
            node_ids=np.arange(fleet["hosts"]).astype(str),
            node_features=arrays["node_features"],
            edge_src=arrays["edge_src"], edge_dst=arrays["edge_dst"],
            edge_rtt_ns=arrays["edge_rtt_ns"]))
        assert np.diff(csr.indptr).max() < 128
        tables = fs.put_graph_tables(csr, mesh)
        assert isinstance(tables, fs.RowTables)
        assert tables.nbr_rows.shape == (fleet["hosts"], 128)
        rows = np.asarray(tables.nbr_rows)
        np.testing.assert_array_equal(rows[:, -1], np.diff(csr.indptr))
        host = int(np.argmax(np.diff(csr.indptr)))
        lo, hi = csr.indptr[host], csr.indptr[host + 1]
        np.testing.assert_array_equal(rows[host, :hi - lo],
                                      csr.indices[lo:hi])
        np.testing.assert_array_equal(
            np.asarray(tables.rtt_rows)[host, :hi - lo].view(np.float32),
            csr.edge_rtt[lo:hi])
        assert not rows[host, hi - lo:-1].any()


class TestFusedTraining:
    @pytest.mark.parametrize("devices", [1, 8])
    def test_host_and_device_paths_agree_on_one_batch(self, csr, devices):
        """One model, one contract: the device path hands its tensors
        over as it sampled them (fan-outs leading), the host path
        (``apply_indexed``) takes the same neighbourhood batch-major, as
        ``data/graph_sampler.py`` makes them, and turns it over at its
        own edge. Same logits, on one device and on the virtual mesh."""
        import jax
        import jax.numpy as jnp

        from dragonfly2_tpu.models.graphsage import GraphSAGE, nodes_last
        from dragonfly2_tpu.train import fused_sampling as fs
        from dragonfly2_tpu.train.gnn_trainer import apply_indexed

        mesh = data_parallel_mesh(devices=jax.devices()[:devices])
        b = mesh.batch_sharding
        graph = fs.put_graph_tables(csr, mesh)
        rng = np.random.default_rng(3)
        src, dst = (mesh.put_batch(rng.integers(0, csr.n_nodes, 64).astype(
            np.int32)) for _ in range(2))
        model = GraphSAGE(hidden=16, embed=8, dtype=jnp.float32)
        fanouts = (10, 5)
        z = np.zeros
        params = model.init(jax.random.key(0), *nodes_last(
            z((2, 2, 8)), z((2, 2, 10, 8)), z((2, 2, 10)), z((2, 2, 10)),
            z((2, 2, 10, 5, 8)), z((2, 2, 10, 5)), z((2, 2, 10, 5))))
        key = mesh.put_replicated(jax.random.key(11))

        device = jax.jit(lambda p, g, s, d, k: fs.sample_and_apply(
            model, p, g, s, d, k, fanouts, b))(params, graph, src, dst, key)

        def host(p, g, s, d, k):
            *hop1, nbr2, rtt2, mask2 = fs.sample_two_hops(
                g, s, d, k, fanouts, b)
            # What the host sampler would have shipped: batch-major, the
            # second hop's RTTs zero under a padded slot.
            return apply_indexed(
                model, p, g.node_features,
                *(x.T for x in (*hop1, nbr2, rtt2 * mask2, mask2)),
                out_sharding=b)

        np.testing.assert_allclose(
            np.asarray(jax.jit(host)(params, graph, src, dst, key)),
            np.asarray(device), rtol=1e-5, atol=1e-6)
        assert np.asarray(device).shape == (64,)
        assert np.abs(np.asarray(device)).max() > 0

    def test_device_and_host_paths_both_learn(self, graph, mesh):
        cfg = dict(hidden=32, embed=16, batch_size=512, epochs=10,
                   learning_rate=1e-2)
        fused = train_gnn(graph, GNNTrainConfig(**cfg), mesh)
        host = train_gnn(
            graph, GNNTrainConfig(device_sample=False, **cfg), mesh)
        assert fused.f1 > 0.9, f"fused path f1={fused.f1}"
        assert host.f1 > 0.9
        assert fused.steps == host.steps

    def test_multi_step_scan_learns_and_counts(self, graph, mesh):
        """steps_per_call>1: K optimizer updates per dispatch — same
        learning outcome, sample accounting scaled by K."""
        cfg = dict(hidden=32, embed=16, batch_size=512, epochs=10,
                   learning_rate=1e-2)
        multi = train_gnn(graph, GNNTrainConfig(steps_per_call=4, **cfg),
                          mesh)
        assert multi.f1 > 0.9, f"scan path f1={multi.f1}"
        single = train_gnn(graph, GNNTrainConfig(**cfg), mesh)
        # steps counts DISPATCHES: one per K-group (within-epoch
        # remainder dropped), so it sits in [single/4 - epochs, single/4].
        assert single.steps // 4 - 10 <= multi.steps <= single.steps // 4
        assert multi.samples_per_sec > 0

    def test_multi_step_state_advances_k_per_dispatch(self, graph, mesh):
        res = train_gnn(
            graph,
            GNNTrainConfig(hidden=8, embed=4, batch_size=256, epochs=1,
                           steps_per_call=3, eval_max_seconds=0.0),
            mesh,
        )
        # dispatches = floor(steps_per_epoch / 3); each carries 3 updates
        assert res.steps >= 1
        assert res.history and all(
            h == h for h in res.history)  # finite losses

    def test_progress_and_compile_callbacks(self, graph, mesh):
        rates, compiles = [], []
        train_gnn(
            graph,
            GNNTrainConfig(hidden=16, embed=8, batch_size=256, epochs=2,
                           progress_callback=lambda s, r: rates.append((s, r)),
                           compile_callback=compiles.append),
            mesh,
        )
        assert len(compiles) == 1 and compiles[0] > 0
        assert rates, "progress callback never fired"
        steps = [s for s, _ in rates]
        assert steps == sorted(steps)
        assert all(r > 0 for _, r in rates)

    def test_eval_wall_cap_truncates(self, graph, mesh):
        """A tiny positive cap scores at least one chunk and returns
        metrics from the scored prefix."""
        res = train_gnn(
            graph,
            GNNTrainConfig(hidden=16, embed=8, batch_size=256, epochs=1,
                           eval_max_seconds=0.001),
            mesh,
        )
        assert 0.0 <= res.f1 <= 1.0

    def test_eval_zero_skips_entirely(self, graph, mesh):
        """eval_max_seconds=0 skips the eval pass (no second compile) —
        the sweep/bench fast path."""
        res = train_gnn(
            graph,
            GNNTrainConfig(hidden=16, embed=8, batch_size=256, epochs=1,
                           eval_max_seconds=0.0),
            mesh,
        )
        assert res.f1 == 0.0 and res.steps >= 1


class TestCompileCache:
    @pytest.fixture(autouse=True)
    def _restore_jax_config(self):
        import jax

        names = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes")
        saved = {n: getattr(jax.config, n) for n in names}
        yield
        for name, value in saved.items():
            jax.config.update(name, value)

    @pytest.mark.parametrize("from_env", [True, False])
    def test_env_var_else_checkout_dir(self, tmp_path, monkeypatch,
                                       from_env):
        import os

        import jax

        import dragonfly2_tpu
        from dragonfly2_tpu.utils.compilecache import enable_compilation_cache

        if from_env:
            want = str(tmp_path / "cache")
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(dragonfly2_tpu.__file__))), ".jax_cache")
        assert enable_compilation_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert os.path.isdir(want)

    def test_unwritable_dir_raises(self, monkeypatch):
        from dragonfly2_tpu.utils.compilecache import enable_compilation_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/proc/nope/cache")
        with pytest.raises(OSError):
            enable_compilation_cache()
