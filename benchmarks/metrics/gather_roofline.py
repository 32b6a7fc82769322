"""Share of the memory roofline the step's row gathers reach: the bytes
they must read (``counts/<kind>.py``, from shapes: the same work
whatever implements it) over the chip's HBM bandwidth, divided by the
summed device time of the operations that implement them. A
memory-bound share, as ``step_mfu`` is the compute-side share of the
whole step. Layer: kernels. Moves ``train_samples_per_s``.

The operations are selected here, from the name the trace gives each
(on a TPU the whole HLO instruction), not by the program:

- an instruction whose opcode is ``gather`` or ``scatter``, or whose own
  name says so (``%gather_fusion.3``, ``%scatter-add.1``);
- a ``kind=kCustom`` fusion that takes an integer index vector: the form
  the v5e compiler gives a row gather and the inverse-index backward
  (``%fusion.12 = bf16[2550000,256] fusion(bf16[2550000,256] %copy,
  s32[2550784] %idx), kind=kCustom``);
- anything named ``table_gather*`` / ``table_scatter_add*``
  (``ops/table_gather.py``'s kernels, where a later PR turns them on).

Layout copies, slices and transposes around a gather are not selected:
they are what a better gather would not need. Device time that is not
selected is printed as ``other`` in ``breakdown``.
"""

import re

_OWN_NAME = re.compile(r"gather|scatter", re.I)
_OPCODE = re.compile(r"[\]\})]\s(gather|scatter)\(")
_INDEX_VECTOR = re.compile(r"\b[su]32\[\d")


def selects(text: str) -> bool:
    own, _, rest = text.partition(" = ")
    if _OWN_NAME.search(own) or _OPCODE.search(rest):
        return True
    _, fusion, operands = rest.partition(" fusion(")
    operands, custom, _ = operands.partition(", kind=kCustom")
    return bool(fusion and custom and _INDEX_VECTOR.search(operands))


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    seconds = trace.seconds_where(selects) * ctx["run"]["chips"]
    if seconds <= 0:
        return None
    bytes_moved = (ctx["counts"].gather_bytes_per_step(ctx["spec"])
                   * ctx["run"]["steps"])
    return 100.0 * (bytes_moved / ctx["peaks"]["hbm_bytes_per_s"]) / seconds
