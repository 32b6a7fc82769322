"""Config #5 at size: multi-GB safetensors fan-out into the TPU HBM sink.

BASELINE.json config #5 / round-5 verdict item 10: fan a multi-GB
safetensors file across >=2 daemons into the HBM sink on-chip, measuring
pieces->device overlap (time-to-last-tensor vs time-to-last-piece).

Topology (all real OS processes over real sockets, as in
tests/test_p2p_multiproc.py): scheduler + seed daemon + one normal peer
daemon warm the content into the P2P mesh; then the measuring process
joins as an ephemeral peer over the scheduler wire and streams the file
piece-by-piece into an :class:`HBMSink` pointed at the accelerator.

Reported overlap metrics:
- ``t_last_piece_s``       — download complete (last piece staged)
- ``t_last_tensor_s``      — last tensor resident on device
- ``tail_after_last_piece_s`` = the transfer work that could NOT be
  hidden behind the download; with full overlap this approaches one
  tensor's transfer time.
- ``sequential_baseline_s`` — what download-then-transfer would cost
  (measured: the same tensors re-``device_put`` after the fact), i.e.
  ``t_last_piece_s + seq_transfer_s``; ``overlap_saving_s`` is the
  difference.

Usage: python artifacts/hbm_fanout.py [--size-gb 2.1] [--out PATH]
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_port(port: int, timeout: float = 90.0, proc=None) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc is not None and proc.poll() is not None:
            raise RuntimeError(f"process died (rc={proc.returncode})")
        try:
            with socket.create_connection(("127.0.0.1", port), timeout=1):
                return
        except OSError:
            time.sleep(0.2)
    raise TimeoutError(f"port {port} never opened")


class Proc:
    def __init__(self, name: str, args: list, base: str):
        self.name = name
        self.err_path = os.path.join(base, f"{name}.err")
        self._out = open(os.path.join(base, f"{name}.out"), "wb")
        self._err = open(self.err_path, "wb")
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["PYTHONUNBUFFERED"] = "1"
        # The daemons must not grab the (single) TPU — only the measuring
        # process talks to the accelerator.
        env["JAX_PLATFORMS"] = "cpu"
        self.proc = subprocess.Popen([sys.executable, "-m"] + args,
                                     stdout=self._out, stderr=self._err,
                                     env=env, cwd=base)

    def terminate(self) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
        self._out.close()
        self._err.close()


def build_safetensors(path: str, total_bytes: int, seed: int = 0) -> int:
    """Write a synthetic bf16 safetensors file of ~total_bytes; returns
    the tensor count. 64 MB tensors ([512, 65536] bf16) model the large
    contiguous weights of an LLM checkpoint shard."""
    import ml_dtypes

    rows, cols = 512, 65536
    per = rows * cols * 2  # bf16
    n = max(int(total_bytes // per), 1)
    rng = np.random.default_rng(seed)
    specs = {}
    offset = 0
    for i in range(n):
        specs[f"model.layers.{i}.weight"] = {
            "dtype": "BF16", "shape": [rows, cols],
            "data_offsets": [offset, offset + per]}
        offset += per
    header = json.dumps(specs).encode()
    pad = (-(8 + len(header))) % 64
    header += b" " * pad
    with open(path, "wb") as f:
        f.write(len(header).to_bytes(8, "little"))
        f.write(header)
        block = rng.standard_normal((rows, cols)).astype(ml_dtypes.bfloat16)
        for _ in range(n):
            f.write(block.tobytes())
    return n


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--size-gb", type=float, default=2.1)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "hbm_fanout_r5.json"))
    ap.add_argument("--base", default="/tmp/df2-hbm-fanout")
    ap.add_argument("--skip-warm", action="store_true",
                    help="skip the peer warm-up dfget (origin-only seed)")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU device (smoke mode)")
    ap.add_argument("--probe-timeout", type=float, default=120.0)
    args = ap.parse_args()

    # Probe the accelerator in a throwaway subprocess with a timeout
    # and fall back to CPU via jax.config, recording the platform used.
    use_tpu = False
    if not args.cpu:
        try:
            probe = subprocess.run(
                [sys.executable, "-c",
                 "import jax; print(jax.devices()[0].platform)"],
                capture_output=True, text=True, timeout=args.probe_timeout)
            use_tpu = (probe.returncode == 0
                       and probe.stdout.strip() not in ("", "cpu"))
        except subprocess.TimeoutExpired:
            pass
    if not use_tpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
        print("accelerator probe failed — falling back to CPU device",
              flush=True)

    base = args.base
    os.makedirs(base, exist_ok=True)
    origin_root = os.path.join(base, "origin")
    os.makedirs(origin_root, exist_ok=True)

    t_build = time.perf_counter()
    blob = os.path.join(origin_root, "model.safetensors")
    n_tensors = build_safetensors(blob, int(args.size_gb * 1e9))
    content_length = os.path.getsize(blob)
    print(f"built {content_length / 1e9:.2f} GB safetensors "
          f"({n_tensors} tensors) in {time.perf_counter() - t_build:.1f}s",
          flush=True)

    import jax

    device = jax.devices()[0]
    platform = device.platform
    print(f"accelerator: {platform} ({device})", flush=True)

    from tests.fileserver import FileServer

    from dragonfly2_tpu.client.daemon import Daemon, DaemonConfig
    from dragonfly2_tpu.client.hbm_sink import HBMSink
    from dragonfly2_tpu.scheduler.rpcserver import GrpcSchedulerClient

    ports = {"scheduler": free_port(), "seed_rpc": free_port(),
             "peer_rpc": free_port(), "seed_metrics": free_port(),
             "peer_metrics": free_port()}
    procs: list[Proc] = []
    result: dict = {
        "bench": "hbm_fanout", "round": 5, "platform": platform,
        "content_bytes": content_length, "n_tensors": n_tensors,
        "daemons": 3, "ts": time.time(),
    }
    try:
        with FileServer(origin_root) as origin:
            url = origin.url("model.safetensors")
            scheduler = Proc("scheduler", [
                "dragonfly2_tpu.cmd.scheduler", "--host", "127.0.0.1",
                "--port", str(ports["scheduler"]),
                "--data-dir", os.path.join(base, "scheduler-data"),
                "--seed-peer", f"127.0.0.1:{ports['seed_rpc']}",
            ], base)
            procs.append(scheduler)
            wait_port(ports["scheduler"], proc=scheduler.proc)

            for name, rpc, met, typ in (
                    ("seed-1", ports["seed_rpc"], ports["seed_metrics"],
                     "super"),
                    ("peer-a", ports["peer_rpc"], ports["peer_metrics"],
                     "normal")):
                p = Proc(name, [
                    "dragonfly2_tpu.cmd.dfdaemon",
                    "--scheduler", f"127.0.0.1:{ports['scheduler']}",
                    "--rpc-port", str(rpc), "--metrics-port", str(met),
                    "--storage-dir", os.path.join(base, name),
                    "--hostname", name, "--type", typ,
                ], base)
                procs.append(p)
                wait_port(rpc, proc=p.proc)

            # Warm the mesh: peer-a pulls the file through the scheduler
            # (seeded back-to-source at the seed daemon), so the measured
            # run finds the pieces on TWO daemons.
            if not args.skip_warm:
                t0 = time.perf_counter()
                env = dict(os.environ)
                env["PYTHONPATH"] = REPO + os.pathsep + env.get(
                    "PYTHONPATH", "")
                env["JAX_PLATFORMS"] = "cpu"
                # --daemon: the warm copy lands in peer-a's DAEMON
                # storage, so the measured run has two serving daemons
                # (seed-1 + peer-a), not a vanished ephemeral peer.
                warm = subprocess.run(
                    [sys.executable, "-m", "dragonfly2_tpu.cmd.dfget", url,
                     "-O", os.path.join(base, "warm.safetensors"),
                     "--daemon", f"127.0.0.1:{ports['peer_rpc']}"],
                    capture_output=True, text=True, timeout=1800, env=env,
                    cwd=base)
                if warm.returncode != 0:
                    raise RuntimeError(
                        f"warm dfget failed: {warm.stdout} {warm.stderr}")
                result["warm_download_s"] = round(
                    time.perf_counter() - t0, 3)
                os.unlink(os.path.join(base, "warm.safetensors"))
                print(f"mesh warmed in {result['warm_download_s']}s",
                      flush=True)

            # Measured run: ephemeral in-process peer -> HBM sink.
            import faulthandler

            faulthandler.dump_traceback_later(900, repeat=True)
            client = GrpcSchedulerClient(
                f"127.0.0.1:{ports['scheduler']}")
            daemon = Daemon(client, DaemonConfig(
                storage_root=os.path.join(base, "measured-peer"),
                hostname="hbm-peer"))
            daemon.announce()

            timeline: list = []
            sink_box: dict = {"sink": None, "t_last_piece": None,
                              "backlog": []}
            lock = threading.Lock()
            t_start = time.perf_counter()

            def ensure_sink(store):
                if sink_box["sink"] is None:
                    length = store.meta.content_length
                    if length < 0:
                        return None
                    sink_box["sink"] = HBMSink(length, device=device)
                    for num in sink_box["backlog"]:
                        sink_box["sink"].write(store.meta.pieces[num].start,
                                               store.read_piece(num=num))
                    sink_box["backlog"].clear()
                return sink_box["sink"]

            def on_piece(store, piece):
                with lock:
                    sink = ensure_sink(store)
                    if sink is None:
                        sink_box["backlog"].append(piece.num)
                        return
                    sink.write(piece.start, store.read_piece(num=piece.num))
                    if sink._coverage.covered_bytes() >= content_length:
                        sink_box["t_last_piece"] = time.perf_counter()

            stop_mon = threading.Event()

            def monitor():
                while not stop_mon.wait(0.25):
                    sink = sink_box["sink"]
                    if sink is None:
                        continue
                    timeline.append({
                        "t_s": round(time.perf_counter() - t_start, 3),
                        "covered_bytes": sink._coverage.covered_bytes(),
                        "tensors_on_device": sink.tensors_on_device,
                    })

            threading.Thread(target=monitor, daemon=True).start()
            dl = daemon.download_file(url, piece_sink=on_piece)
            print(f"download_file returned at "
                  f"{time.perf_counter() - t_start:.1f}s "
                  f"(success={dl.success})", flush=True)
            if not dl.success:
                raise RuntimeError(f"measured download failed: {dl.error}")
            store = dl.storage
            with lock:
                sink = ensure_sink(store)
                # Reconcile pieces the hook never saw (reuse fast path /
                # races) — same tail download_to_hbm performs.
                if sink._coverage.covered_bytes() < content_length:
                    for num in store.existing_piece_nums():
                        piece = store.meta.pieces[num]
                        if not sink._coverage.covers(
                                piece.start, piece.start + piece.length):
                            sink.write(piece.start,
                                       store.read_piece(num=num))
                if sink_box["t_last_piece"] is None and \
                        sink._coverage.covered_bytes() >= content_length:
                    sink_box["t_last_piece"] = time.perf_counter()
            arrays = sink.wait(timeout=3600)
            t_last_tensor = time.perf_counter() - t_start
            stop_mon.set()
            t_last_piece = (sink_box["t_last_piece"] or time.perf_counter()
                            ) - t_start

            # Integrity: on-device bytes == origin bytes for a probe
            # tensor (full-file sha is already piece-digest-verified by
            # the storage layer).
            name0 = sorted(arrays)[0]
            dev_bytes = np.asarray(arrays[name0]).tobytes()
            with open(blob, "rb") as f:
                hdr = f.read(8)
                hlen = int.from_bytes(hdr, "little")
                f.seek(8 + hlen)
                origin_bytes = f.read(len(dev_bytes))
            assert hashlib.sha256(dev_bytes).hexdigest() == \
                hashlib.sha256(origin_bytes).hexdigest(), \
                "device tensor != origin bytes"

            # Sequential baseline: the same tensors transferred AFTER the
            # download instead of overlapped with it.
            staging = sink._staging
            t0 = time.perf_counter()
            seq = []
            for spec in sink._specs:
                import ml_dtypes

                view = staging[spec.start:spec.end].view(
                    np.dtype(ml_dtypes.bfloat16)).reshape(spec.shape)
                seq.append(jax.device_put(view, device))
            for a in seq:
                a.block_until_ready()
            seq_transfer_s = time.perf_counter() - t0
            del seq, arrays

            result.update({
                "t_last_piece_s": round(t_last_piece, 3),
                "t_last_tensor_s": round(t_last_tensor, 3),
                "tail_after_last_piece_s": round(
                    t_last_tensor - t_last_piece, 3),
                "seq_transfer_s": round(seq_transfer_s, 3),
                "sequential_baseline_s": round(
                    t_last_piece + seq_transfer_s, 3),
                "overlap_saving_s": round(
                    t_last_piece + seq_transfer_s - t_last_tensor, 3),
                "overlap_hidden_fraction": round(
                    1.0 - max(t_last_tensor - t_last_piece, 0.0)
                    / max(seq_transfer_s, 1e-9), 4),
                "download_bandwidth_MBps": round(
                    content_length / 1e6 / t_last_piece, 1),
                "effective_bandwidth_MBps": round(
                    content_length / 1e6 / t_last_tensor, 1),
                "device_put_bandwidth_MBps": round(
                    content_length / 1e6 / seq_transfer_s, 1),
                "timeline": timeline[-200:],
            })
            daemon.stop()
    finally:
        for p in reversed(procs):
            p.terminate()

    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({k: v for k, v in result.items()
                      if k != "timeline"}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
