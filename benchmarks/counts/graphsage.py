"""Work one optimizer step of the ``graphsage`` kind requires, from the
configuration's shapes alone (never from the HLO)."""

from __future__ import annotations

F32 = I32 = 4
FEATURES = 8


def flops_per_step(spec: dict) -> float:
    """Dense products, forward and backward. A product is 2·m·n·k
    forward, as much again for its weight's gradient, and as much again
    for its input's gradient where the input has one (layer 1 reads
    gathered features and RTTs, which have none)."""
    m = spec["model"]
    b, (f1, _f2) = spec["batch"], m["fanouts"]
    h, e = m["hidden"], m["embed"]
    layer1 = 2 * (2 * b * f1 + 2 * b) * (2 * (FEATURES + 1)) * h
    layer2 = 2 * (2 * b) * (2 * h) * e
    head = 2 * b * (4 * e) * h + 2 * b * h
    return 2.0 * layer1 + 3.0 * (layer2 + head)


def gather_bytes_per_step(spec: dict) -> float:
    """Bytes the step's gathers must read at the least. Per target edge:
    src, dst, label; for its 2 centres the CSR row bounds and a feature
    row; for 2·f1 first-hop and 2·f1·f2 second-hop slots a neighbour id
    and an RTT, a feature row, and (first hop) the row bounds for the
    next hop. Nothing flows back into the tables, so there is no
    backward gather."""
    b, (f1, f2) = spec["batch"], spec["model"]["fanouts"]
    feature_row = FEATURES * F32
    centres, hop1, hop2 = 2, 2 * f1, 2 * f1 * f2
    per_edge = (2 * I32 + F32
                + centres * (2 * I32 + feature_row)
                + hop1 * (I32 + F32 + feature_row + 2 * I32)
                + hop2 * (I32 + F32 + feature_row))
    return float(b * per_edge)
