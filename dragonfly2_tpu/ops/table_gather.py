"""Pallas TPU kernels for small-table row gather / scatter-add.

Motivation (config #3, `artifacts/gather_micro_r5.json`): XLA lowers a
row gather from a 10 MB table to one HBM DMA per row — 1.28 M DMAs
move 655 MB at ~8 GB/s, DMA-issue-rate bound, and the autodiff
transpose (duplicate-index scatter-add) is the same op run backwards.
But a degree-capped probe graph's K/V table fits in VMEM (128 MiB on
v5e): these kernels keep an f32 column chunk of the table (gather) or
of the gradient accumulator (scatter-add) resident in VMEM and stream
the big side ([M, D] rows) through blocked grid steps, so the per-row
operation is a VMEM dynamic slice — no HBM round trip per row.

Opt-in (`DF2_PALLAS_GATHER=1`) single-device TPU path for
``gather_graph_attention``; the XLA inverse-index formulation stays the
default until an on-chip A/B (ROADMAP A3) proves this faster.
Correctness is hermetic: ``interpret=True`` tests compare against
``table[idx]`` and autodiff end to end, and
``tests/test_chip_compile.py`` compiles both kernels for a described
v5e at the config #3 table.

Reference hook: SURVEY §2.6 (pallas ops mandate); the consumer is the
GraphTransformer gather mode (`models/graph_transformer.py`).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows per grid step of the streamed side. The [BLOCK] int32 index block
# lives in SMEM, whose 1-D tiling is 1024 words: Mosaic refuses any other
# block length for that operand (interpret mode takes any multiple of
# ROW_UNROLL). 1024 rows × 128 lanes × 4 B = 512 KB per streamed block.
BLOCK = 1024

# Rows handled per iteration of the in-kernel row loop (Mosaic's
# fori_loop takes unroll=1 or a full unroll only, so the partial unroll
# is spelled out in the loop body).
ROW_UNROLL = 8

# Budget for ONE resident [n_rows, cols] f32 block. The pipeline double-
# buffers it, and the streamed blocks ride beside it, so the kernels ask
# the compiler for `_vmem_limit` bytes — above the 16 MiB default scoped
# limit, inside v5e's 128 MiB of VMEM.
VMEM_TABLE_BUDGET = 12 * 1024 * 1024


def fits_vmem(n_rows: int, width: int, dtype) -> bool:
    """A whole [n_rows, width] block of ``dtype`` within the budget."""
    return n_rows * width * jnp.dtype(dtype).itemsize <= VMEM_TABLE_BUDGET


def _scatter_col_chunk(n_rows: int, d: int) -> int | None:
    """Widest column chunk (multiple of 128 dividing d) whose f32 block
    [n_rows, chunk] — what BOTH kernels keep resident, whatever the
    table's dtype — fits the VMEM budget; None if even 128 columns
    don't fit."""
    dc = (d // 128) * 128
    while dc >= 128:
        if d % dc == 0 and fits_vmem(n_rows, dc, jnp.float32):
            return dc
        dc -= 128
    return None


def pallas_path_feasible(n_rows: int, width: int, dtype) -> bool:
    """The gate ``gather_graph_attention`` routes by: lane-aligned rows,
    a dtype the resident f32 block holds exactly, and a column chunk of
    that block within the budget. The table's own bytes are not part of
    the rule: neither kernel holds the table in its dtype.
    ``tests/test_chip_compile.py`` compiles the largest tables this
    admits, bf16 and f32, for a v5e."""
    dtype = jnp.dtype(dtype)
    return (width % 128 == 0
            and jnp.issubdtype(dtype, jnp.floating) and dtype.itemsize <= 4
            and _scatter_col_chunk(n_rows, width) is not None)


def _vmem_limit(n_rows: int, dc: int, block: int) -> int:
    """Scoped VMEM the kernels request: the resident f32 block twice
    (pipeline double-buffering), the streamed side's two blocks plus the
    f32 staging block (bounded as four f32 blocks), and slack."""
    return 2 * n_rows * dc * 4 + 4 * block * dc * 4 + (2 << 20)


def _row_loop(n_rows: int, row_fn) -> None:
    """``row_fn(r)`` for r in [0, n_rows), ROW_UNROLL rows per trip."""
    def body(g, carry):
        for u in range(ROW_UNROLL):
            row_fn(g * ROW_UNROLL + u)
        return carry

    jax.lax.fori_loop(0, n_rows // ROW_UNROLL, body, 0)


def _gather_kernel(idx_ref, table_ref, out_ref, rows_ref):
    # Single-row dynamic slices need 32-bit rows (a packed bf16 row is
    # half a sublane word — Mosaic cannot address it), so the resident
    # table and the staging block are f32; the cast back to the table's
    # dtype is one whole-block store.
    def copy_row(r):
        rows_ref[pl.ds(r, 1), :] = table_ref[pl.ds(idx_ref[r], 1), :]

    _row_loop(rows_ref.shape[0], copy_row)
    out_ref[...] = rows_ref[...].astype(out_ref.dtype)


@partial(jax.jit, static_argnames=("interpret", "block"))
def table_gather(table, idx, *, interpret: bool = False,
                 block: int = BLOCK):
    """``table[idx]`` with the table resident in VMEM.

    table: [N, D] f32 or bf16 (D a multiple of 128, a 128-multiple
    column chunk of N f32 rows within the VMEM budget); idx: [M] int32
    in [0, N). Returns [M, D] in table's dtype. The table is held as
    f32 (exact for bf16) in column chunks, like the scatter-add's
    accumulator, so both directions share one feasibility rule.
    """
    n, d = table.shape
    (m,) = idx.shape
    assert d % 128 == 0, d
    assert block % ROW_UNROLL == 0, block
    dc = _scatter_col_chunk(n, d)
    assert dc is not None, (n, d)
    m_pad = pl.cdiv(m, block) * block
    idx_p = jnp.pad(idx.astype(jnp.int32), (0, m_pad - m))
    out = pl.pallas_call(
        _gather_kernel,
        grid=(d // dc, m_pad // block),
        in_specs=[
            pl.BlockSpec((block,), lambda c, i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((n, dc), lambda c, i: (0, c),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block, dc), lambda c, i: (i, c),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m_pad, d), table.dtype),
        scratch_shapes=[pltpu.VMEM((block, dc), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(n, dc, block)),
        interpret=interpret,
    )(idx_p, table.astype(jnp.float32))
    return out[:m]


def _scatter_add_kernel(idx_ref, ct_ref, out_ref, rows_ref):
    # Grid is (column_chunks, row_blocks): the accumulator chunk stays
    # resident across the inner row sweep; zero it on the sweep's
    # first step.
    @pl.when(pl.program_id(1) == 0)
    def _zero():
        out_ref[:, :] = jnp.zeros_like(out_ref)

    # Upcast the streamed block once, whole: rows are then 32-bit and
    # singly addressable (see _gather_kernel).
    rows_ref[...] = ct_ref[...].astype(jnp.float32)

    def add_row(r):
        out_ref[pl.ds(idx_ref[r], 1), :] += rows_ref[pl.ds(r, 1), :]

    _row_loop(rows_ref.shape[0], add_row)


@partial(jax.jit, static_argnames=("n_rows", "interpret", "block"))
def table_scatter_add(ct, idx, n_rows: int, *, interpret: bool = False,
                      block: int = BLOCK):
    """``zeros([n_rows, D]).at[idx].add(ct)`` (f32 accumulation) with
    the accumulator resident in VMEM while ct rows stream through the
    grid in their OWN dtype (upcast happens per row block inside the
    kernel — no padded f32 copy of the cotangent in HBM).

    When the full f32 accumulator would bust the VMEM budget, the grid
    gains an outer dimension over column chunks (each chunk's sweep
    revisits its own [n_rows, dc] window); duplicate indices accumulate
    exactly either way. Rows of zeros may be used as padding.
    """
    m, d = ct.shape
    assert d % 128 == 0, d
    assert block % ROW_UNROLL == 0, block
    dc = _scatter_col_chunk(n_rows, d)
    assert dc is not None, (n_rows, d)
    m_pad = pl.cdiv(m, block) * block
    idx_p = jnp.pad(idx.astype(jnp.int32), (0, m_pad - m))
    ct_p = jnp.pad(ct, ((0, m_pad - m), (0, 0)))
    out = pl.pallas_call(
        _scatter_add_kernel,
        grid=(d // dc, m_pad // block),
        in_specs=[
            pl.BlockSpec((block,), lambda c, i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((block, dc), lambda c, i: (i, c),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((n_rows, dc), lambda c, i: (0, c),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_rows, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block, dc), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(n_rows, dc, block)),
        interpret=interpret,
    )(idx_p, ct_p)
    return out.astype(ct.dtype)


def neighbor_gather_pallas(table, idx, *, interpret: bool = False,
                           block: int = BLOCK):
    """[N, K]-indexed row gather with BOTH directions as VMEM-resident
    pallas kernels: forward gathers rows of ``table`` [N, D]; the
    backward scatter-adds the cotangent into a VMEM accumulator — no
    inverse index needed. Numerically exact vs ``table[idx]`` +
    autodiff (pad rows must carry zero cotangent, which the attention
    mask guarantees — same contract as the inverse-index path)."""

    @jax.custom_vjp
    def gather(t, ix):
        n, k = ix.shape
        return table_gather(t, ix.reshape(-1), interpret=interpret,
                            block=block).reshape(n, k, -1)

    def fwd(t, ix):
        return gather(t, ix), (ix, t.shape[0])

    def bwd(res, ct):
        ix, n_rows = res
        n, k = ix.shape
        d_t = table_scatter_add(ct.reshape(n * k, -1), ix.reshape(-1),
                                n_rows, interpret=interpret, block=block)
        return d_t, np.zeros(ix.shape, dtype=jax.dtypes.float0)

    gather.defvjp(fwd, bwd)
    return gather(table, idx)
