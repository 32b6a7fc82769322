"""``chip_smoke.py`` and ``bench.py`` without a chip, and the smoke's
phases rehearsed on the CPU at ``Sizes.tiny()``.

The scripts themselves have no CPU path (that is the first two tests);
the rehearsal imports the phase functions and calls them with CPU
devices, which finds wrong paths, arguments and control flow before a
chip run is spent on them. It says nothing about the chip
(tests/test_chip_compile.py compiles the cells' programs for a described
v5e), and nothing is timed.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("command", [
    ["chip_smoke.py"],
    ["chip_smoke.py", "--chips", "4"],
    ["bench.py", "gnn"],
    ["bench.py"],
])
def test_no_chip_means_no_result(command, tmp_path):
    """On a machine with no TPU both scripts exit non-zero within
    seconds, before any phase or stage, and print no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               BENCH_STATE_DIR=str(tmp_path))
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable] + command, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert time.monotonic() - t0 < 60
    assert "TPU" in proc.stderr and "cpu" in proc.stderr
    assert '"ok": true' not in proc.stdout
    assert "graphsage_train_samples_per_sec_per_chip" not in proc.stdout
    assert '"phase"' not in proc.stdout
    assert not list(tmp_path.iterdir())


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """Phase 2 once; phase 3 serves what it registered."""
    from dragonfly2_tpu.manager import (
        Database,
        FilesystemObjectStore,
        ManagerService,
    )

    workdir = str(tmp_path_factory.mktemp("chip-smoke-rehearsal"))
    manager = ManagerService(
        Database(os.path.join(workdir, "manager.db")),
        FilesystemObjectStore(os.path.join(workdir, "objects")))
    report = chip_smoke.phase_train(chip_smoke.Sizes.tiny(), 0, workdir,
                                    manager)
    return {"workdir": workdir, "manager": manager, "train": report}


class TestRehearsal:
    sizes = chip_smoke.Sizes.tiny()

    def test_train_registers_every_model(self, rehearsal):
        report = rehearsal["train"]
        for job in ("sched-a_gnn", "sched-a_mlp", "sched-a_gat",
                    "sched-b_gnn", "sched-b_gat"):
            loss = report[job]["loss"]
            assert loss[-1] < loss[0], (job, loss)
            assert report[job]["compile_s"] > 0
            assert report[job]["job_wall_s"] >= report[job]["compile_s"]
        for model in ("gnn", "mlp", "gat"):
            assert rehearsal["manager"].get_active_model(
                model, chip_smoke.SCHEDULER_ID) is not None

    def test_serve_matches_the_host_reference(self, rehearsal):
        report = chip_smoke.phase_serve(
            self.sizes, 0, rehearsal["workdir"], rehearsal["manager"],
            jax.devices()[0])
        assert report["requests"] == 2 * self.sizes.infer_requests
        assert report["mlp_max_rel_err_vs_numpy"] < chip_smoke.SERVE_REL_ERR
        assert report["gat_max_rel_err_vs_numpy"] < chip_smoke.SERVE_REL_ERR

    def test_sink_lands_every_byte(self, tmp_path):
        report = chip_smoke.phase_sink(self.sizes, 0, str(tmp_path),
                                       jax.devices()[0])
        assert report["tensors"] == self.sizes.sink_tensors
        assert report["file_bytes"] > (self.sizes.sink_tensors
                                       * self.sizes.sink_tensor_elems * 2)

    def test_data_parallel_on_four_virtual_devices(self):
        devices = jax.devices()[:4]
        assert len(devices) == 4
        report = chip_smoke.phase_data_parallel(self.sizes, 0, devices)
        ids = [d.id for d in devices]
        wide, one = report["wide"], report["one"]
        assert wide["state_tables_key_replicated_on"] == ids
        assert wide["edge_id_batch_shards"] == {
            "devices": ids,
            "shape": [self.sizes.sage_steps_per_call,
                      self.sizes.sage_batch // 4]}
        assert wide["sampled_index_shard_devices"] == ids
        assert wide["all_reduce_ops"] > 0 and one["all_reduce_ops"] == 0
        assert one["state_tables_key_replicated_on"] == ids[:1]
        assert np.isfinite(report["max_rel_loss_diff"])

    def test_data_parallel_reads_the_trainers_own_arrays(self, monkeypatch):
        """A trainer that leaves its tables on the first device still
        trains (jit copies them over on every call) and still has its
        all-reduce; the phase must fail on what the trainer placed."""
        from dragonfly2_tpu.train import fused_sampling as fs

        put = fs.put_graph_tables
        monkeypatch.setattr(
            fs, "put_graph_tables", lambda csr, mesh: jax.device_put(
                put(csr, mesh), mesh.mesh.devices.flat[0]))
        with pytest.raises(chip_smoke.SmokeFailure, match="graph tables"):
            chip_smoke.phase_data_parallel(self.sizes, 0, jax.devices()[:4])
