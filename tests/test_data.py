"""Tests for synthetic generation, feature extraction, and batching."""

import numpy as np
import pytest

from dragonfly2_tpu.data import (
    ArrayDataset,
    SyntheticCluster,
    graph_from_table,
    pair_examples_from_table,
    shard_batch,
)
from dragonfly2_tpu.schema import Download, NetworkTopology
from dragonfly2_tpu.schema.io import records_to_table
from dragonfly2_tpu.scheduler.evaluator.scoring import FEATURE_DIM


@pytest.fixture(scope="module")
def cluster():
    return SyntheticCluster(n_hosts=64, seed=7)


class TestSynthetic:
    def test_pair_columns_shapes(self, cluster):
        X, y = cluster.pair_example_columns(1000)
        assert X.shape == (1000, FEATURE_DIM) and X.dtype == np.float32
        assert y.shape == (1000,) and (y > 0).all()

    def test_bandwidth_structure_learnable(self, cluster):
        # Same-rack pairs must be systematically faster than cross-region:
        # otherwise there is no signal for the models to learn.
        X, y = cluster.pair_example_columns(20000)
        near = y[X[:, 10] == 5.0]  # location_matches == 5 → same rack (exact match)
        far = y[X[:, 10] == 0.0]
        assert near.mean() > 2 * far.mean()

    def test_rtt_structure(self, cluster):
        cols = cluster.probe_edge_columns(20000)
        prox = cluster.hosts.proximity(cols["src"], cols["dst"])
        near = cols["rtt_ns"][prox == 0]
        far = cols["rtt_ns"][prox == 3]
        if len(near) and len(far):
            assert np.median(far) > 20 * np.median(near)

    def test_record_paths_valid_schema(self, cluster):
        downloads = cluster.downloads(10)
        topo = cluster.topology(10)
        # Must flatten into valid tables (exercises fixed-arity bounds).
        assert records_to_table(Download, downloads).num_rows == 10
        assert records_to_table(NetworkTopology, topo).num_rows == 10

    @pytest.mark.parametrize("n_dest", [None, 5])
    def test_bulk_topology_csv_is_the_record_path(self, tmp_path, n_dest):
        """``write_topology_csv`` and ``topology`` are one generator: the
        same seed gives, byte for byte, the file ``CsvRecordWriter``
        writes from the record objects — so the trainer parses a bulk
        dataset exactly as it parses a scheduler's."""
        from dragonfly2_tpu.schema.io import CsvRecordWriter, read_csv_records

        records = SyntheticCluster(n_hosts=50, seed=3).topology(200, n_dest)
        bulk, reference = (str(tmp_path / name) for name in ("b.csv", "r.csv"))
        n_edges = SyntheticCluster(n_hosts=50, seed=3).write_topology_csv(
            200, bulk, n_dest)
        with CsvRecordWriter(NetworkTopology, reference) as writer:
            for record in records:
                writer.write(record)
        with open(bulk, "rb") as a, open(reference, "rb") as b:
            assert a.read() == b.read()
        assert list(read_csv_records(NetworkTopology, bulk)) == records
        assert n_edges == sum(len(r.dest_hosts) for r in records)
        assert n_edges == 200 * 5 if n_dest else 200 < n_edges < 200 * 5
        for record in records:
            assert all(d.id != record.host.id and d.probes.average_rtt > 0
                       for d in record.dest_hosts)
        graph = graph_from_table(records_to_table(NetworkTopology, records))
        assert graph.n_edges == n_edges and graph.n_nodes == 50

    def test_deterministic(self):
        a = SyntheticCluster(n_hosts=32, seed=3).pair_example_columns(100)
        b = SyntheticCluster(n_hosts=32, seed=3).pair_example_columns(100)
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])


class TestFeatureExtraction:
    def test_pair_examples_from_records(self, cluster):
        table = records_to_table(Download, cluster.downloads(50))
        X, y = pair_examples_from_table(table)
        assert X.shape[1] == FEATURE_DIM
        assert len(X) == len(y) > 50  # multiple parents per download
        assert (y > 0).all()
        # Sanity: piece bandwidth labels in plausible MB/s range.
        assert y.mean() < 20000

    def test_graph_from_records(self, cluster):
        table = records_to_table(NetworkTopology, cluster.topology(200))
        g = graph_from_table(table)
        assert g.n_nodes <= 64
        assert g.n_edges > 200  # ~3 dests per row avg
        assert g.node_features.shape == (g.n_nodes, 8)
        assert g.edge_src.max() < g.n_nodes and g.edge_dst.max() < g.n_nodes
        labels = g.edge_labels()
        assert set(np.unique(labels)) <= {0, 1}
        assert 0 < labels.mean() < 1  # both classes present

    def test_empty_table(self):
        table = records_to_table(Download, [])
        X, y = pair_examples_from_table(table)
        assert len(X) == 0 and len(y) == 0


class TestPipeline:
    def test_batches_static_shape_and_deterministic(self):
        X = np.arange(103, dtype=np.float32)[:, None]
        y = np.arange(103, dtype=np.float32)
        ds = ArrayDataset(X, y)
        b1 = list(ds.batches(10, seed=1, epoch=0))
        b2 = list(ds.batches(10, seed=1, epoch=0))
        b3 = list(ds.batches(10, seed=1, epoch=1))
        assert len(b1) == 10  # remainder dropped
        assert all(bx.shape == (10, 1) for bx, _ in b1)
        np.testing.assert_array_equal(b1[0][0], b2[0][0])
        assert not np.array_equal(b1[0][0], b3[0][0])  # epoch reshuffles

    def test_split_disjoint(self):
        ds = ArrayDataset(np.arange(100)[:, None], np.arange(100))
        train, ev = ds.split(0.2, seed=0)
        assert len(train) == 80 and len(ev) == 20
        assert not set(train.arrays[1]) & set(ev.arrays[1])

    def test_shard_batch(self):
        X = np.zeros((64, 11))
        sharded = shard_batch(X, 8)
        assert sharded.shape == (8, 8, 11)
        with pytest.raises(AssertionError):
            shard_batch(np.zeros((10, 2)), 8)
