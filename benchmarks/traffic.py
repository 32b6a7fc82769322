"""The one traffic generator: a probe graph made from a data file's
parameters and ``--seed``.

A training cell's traffic is the probe data set the trainer is handed.
Its parameters (hosts, how many probes a host sends and receives, the
location hierarchy) come from the configuration's ``fleet`` group; this
module turns them and a seed into arrays. Nothing here imports the
program.

The latent model is the repo's ``data/synthetic.py`` one (copied; see
PERF.md Open questions): hosts sit in ``region|zone|rack``, a probe's
RTT is the base RTT of the pair's proximity class times lognormal
noise, node features are the eight observable columns.

Who probes whom follows Dragonfly2's scheduler (v2.1.0
``scheduler/networktopology``) as far as a benchmark's shapes allow. A
host is handed ``probe_count`` targets a round, so a host that has been
up for ``a`` rounds has sent ``probe_count * a`` probes: ages are spread
evenly over ``rounds`` = [fewest, most], which skews out-degree.
``FindProbedHosts`` draws random candidates and takes the least probed
first, which keeps the times a host *is* probed in a band around the
mean: those counts are spread evenly over ``probed`` = [fewest, most].
Both sequences are the same for every seed; the seed decides which host
has which, and who meets whom (the send slots are matched with the
shuffled receive slots; a pair met twice is two records, as repeated
probes of one pair are). So every seed has the same set of sizes in
another order. The widest neighbour list (hosts that sent and received
the most) and the most lists a host appears in are *shapes* of a
compiled step: with hundreds of hosts at both maxima they come out the
same whatever the seed (``tests/test_rehearse.py`` holds the cells'
fleets to that), while lists are ragged, padded and, where a host has
more neighbours than the trainer's ``neighbor_cap``, cut.
"""

from __future__ import annotations

import numpy as np

# Base RTT (ns) by proximity class: same rack / zone / region / other.
BASE_RTT_NS = np.array([200_000, 1_000_000, 10_000_000, 60_000_000])


def _spread(n: int, lo: int, hi: int) -> np.ndarray:
    """``n`` whole numbers evenly over lo..hi, as many of each as of any
    other to within one."""
    return lo + np.arange(n, dtype=np.int64) % (hi - lo + 1)


def degree_sequences(fleet: dict) -> tuple[np.ndarray, np.ndarray]:
    """Probes sent and probes received, per host slot (not yet dealt to
    hosts): the same for every seed."""
    n = int(fleet["hosts"])
    sent = int(fleet["probe_count"]) * _spread(n, *map(int, fleet["rounds"]))
    lo, hi = map(int, fleet["probed"])
    received = _spread(n, lo, hi)
    # Every probe sent is received: the few that the even spread is off
    # by go to hosts inside the band, so both ends of it stay as stated.
    off = int(sent.sum() - received.sum())
    inside = np.flatnonzero((received > lo) & (received < hi))[:abs(off)]
    if len(inside) < abs(off):
        raise ValueError(
            f"hosts send {sent.sum()} probes; the band 'probed' = [{lo}, "
            f"{hi}] cannot receive them (off by {off})")
    received[inside] += np.sign(off)
    return sent, received


def probe_graph(fleet: dict, seed: int) -> dict:
    """``fleet`` → ``{node_features [N, 8] f32, edge_src [E] i32,
    edge_dst [E] i32, edge_rtt_ns [E] i64}`` with E = the probes sent,
    a sender's records together (the trainers draw their own order)."""
    n = int(fleet["hosts"])
    if n < 3:
        raise ValueError(f"fleet too small: {fleet}")
    rng = np.random.default_rng(int(seed))
    region = rng.integers(0, int(fleet["regions"]), n)
    zone = rng.integers(0, int(fleet["zones_per_region"]), n)
    rack = rng.integers(0, int(fleet["racks_per_zone"]), n)
    is_seed = rng.random(n) < float(fleet["seed_fraction"])
    idc = region * int(fleet["zones_per_region"]) + zone

    sent, received = degree_sequences(fleet)
    src = np.repeat(rng.permutation(n), sent)
    dst = rng.permutation(np.repeat(rng.permutation(n), received))
    # No host probes itself: such a slot trades targets with another.
    for _ in range(64):
        own = np.flatnonzero(src == dst)
        if not len(own):
            break
        other = rng.integers(0, len(dst), len(own))
        dst[own], dst[other] = dst[other], dst[own]
    else:
        raise ValueError(f"fleet too small to match without self-probes: "
                         f"{fleet}")

    same_region = region[src] == region[dst]
    same_zone = same_region & (zone[src] == zone[dst])
    same_rack = same_zone & (rack[src] == rack[dst])
    prox = np.where(same_rack, 0,
                    np.where(same_zone, 1, np.where(same_region, 2, 3)))
    noise = rng.lognormal(0.0, float(fleet["rtt_noise_sigma"]), len(src))
    rtt_ns = (BASE_RTT_NS[prox] * noise).astype(np.int64)

    features = np.stack([
        is_seed.astype(float),
        np.where(is_seed, 300, 50) / 100.0,
        (idc % 16) / 16.0,
        (region % 16) / 16.0,
        (zone % 16) / 16.0,
        (rack % 16) / 16.0,
        np.zeros(n),
        np.ones(n),
    ], axis=1).astype(np.float32)
    return {
        "node_features": features,
        "edge_src": src.astype(np.int32),
        "edge_dst": dst.astype(np.int32),
        "edge_rtt_ns": rtt_ns,
    }
