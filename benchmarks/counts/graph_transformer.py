"""Work one optimizer step of the ``graph_transformer`` kind requires,
from the configuration's shapes alone (never from the HLO).

Rows N = hosts. A host's neighbour list holds itself and every host
it has probed or been probed by, at most ``neighbor_cap`` of them, so
all lists together hold at most ``min(N + 2·E, N·neighbor_cap)`` filled
slots for E probe records (benchmarks/traffic.py). That is what the
step has to attend over; slots that pad a short list are no work. (A
pair met twice and what the cap cuts from long lists are not taken off:
under 3% at the cells' fleets, counted on the side of more work.)
"""

from __future__ import annotations

from benchmarks import traffic

BF16 = 2


def shapes(spec: dict) -> dict:
    fleet, m = spec["fleet"], spec["model"]
    n = fleet["hosts"]
    records = int(traffic.degree_sequences(fleet)[0].sum())
    return {"rows": n,
            "list_slots": min(n + 2 * records, n * m["neighbor_cap"]),
            "batch": spec["batch"], "features": 8}


def flops_per_step(spec: dict) -> float:
    """Dense products and attention, forward and backward. A product is
    2·m·n·k forward, as much again for its weight's gradient, and as
    much again for its input's gradient where the input has one (the
    node features have none). Recomputation is not counted."""
    s, m = shapes(spec), spec["model"]
    n, slots, b = s["rows"], s["list_slots"], s["batch"]
    h, e = m["hidden"], m["embed"]
    per_block = (4 * 2 * n * h * h          # q, k, v, out projections
                 + 2 * 2 * n * h * 2 * h    # MLP up and down
                 + 2 * 2 * slots * h)       # scores, weighted sum
    with_input_grad = (m["layers"] * per_block
                       + 2 * n * h * e      # embedding projection
                       + 2 * b * 2 * e * e  # edge head hidden
                       + 2 * b * e)         # edge head out
    return 3.0 * with_input_grad + 2.0 * (2 * n * s["features"] * h)


def gather_bytes_per_step(spec: dict) -> float:
    """Bytes the step's row gathers must read at the least: per layer
    one row of the [k|v] table (2·hidden bfloat16) for every filled list
    slot forward, and one row of that gather's cotangent backward
    (whatever turns them into the table's gradient: scatter-add,
    inverse-index gather or a kernel); the edge head's 2·B embedding
    rows each way."""
    s, m = shapes(spec), spec["model"]
    kv_row = 2 * m["hidden"] * BF16
    attention = m["layers"] * 2 * s["list_slots"] * kv_row
    head = 2 * 2 * s["batch"] * m["embed"] * BF16
    return float(attention + head)
