"""batch_gather — the LIRS kernel: indexed gather of records from an
HBM-resident table into a contiguous batch buffer.

This is the TPU-native analogue of LIRS's random preads: the *random
assignment table* (scalar-prefetched indices) drives per-step DMA of one
record block HBM→VMEM.  ``rows_per_block`` is the device-side page-aware
knob: gathering R consecutive rows per indexed block amortizes DMA setup
exactly like page-granular reads amortize I/O — the paper's §4.1 argument
re-materialized at the memory-hierarchy level.

Grid: (batch, d_model/block_d).  The index map of the table operand reads
the scalar-prefetched index ref — Pallas's supported pattern for
data-dependent block addressing.

``batch_gather_dma`` is the coalesced variant: each grid step materializes
``rows_per_step`` indexed blocks with hand-rolled double-buffered async
DMA (HBM→VMEM), so DMA issue overlaps the copy-out of the previous block —
amortizing per-transfer setup across a step exactly like the host side
amortizes syscalls across a coalesced extent.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(idx_ref, table_ref, out_ref):
    # the whole block selected by the scalar-prefetched index is already in
    # VMEM; emit it
    out_ref[...] = table_ref[...]


@functools.partial(
    jax.jit, static_argnames=("block_d", "rows_per_block", "interpret")
)
def batch_gather(
    table: jax.Array,
    indices: jax.Array,
    *,
    block_d: int = 512,
    rows_per_block: int = 1,
    interpret: bool = False,
) -> jax.Array:
    """Gather ``rows_per_block`` consecutive rows starting at
    ``indices[i] * rows_per_block`` for each i.

    table:   (N, D)  — HBM-resident dataset shard
    indices: (B,) int32 — block ids (record ids when rows_per_block=1)
    returns: (B * rows_per_block, D)
    """
    n, d = table.shape
    b = indices.shape[0]
    r = rows_per_block
    assert n % r == 0, (n, r)
    bd = min(block_d, d)
    assert d % bd == 0, (d, bd)

    grid = (b, d // bd)
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((r, bd), lambda i, j, idx: (idx[i], j)),
            ],
            out_specs=pl.BlockSpec((r, bd), lambda i, j, idx: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((b * r, d), table.dtype),
        interpret=interpret,
    )(indices.astype(jnp.int32), table)
    return out


def _gather_dma_kernel(idx_ref, table_ref, out_ref, scratch, sems, *, m, r, bd):
    """One grid step gathers ``m`` indexed blocks with 2-deep DMA
    pipelining: while block k streams out of VMEM scratch, block k+1's
    HBM→VMEM copy is already in flight."""
    i = pl.program_id(0)
    j = pl.program_id(1)

    def dma(slot, k):
        row = idx_ref[i * m + k] * r
        return pltpu.make_async_copy(
            table_ref.at[pl.ds(row, r), pl.ds(j * bd, bd)],
            scratch.at[slot],
            sems.at[slot],
        )

    dma(0, 0).start()
    for k in range(m):  # static unroll: m is a compile-time constant
        slot = k % 2
        if k + 1 < m:
            dma(1 - slot, k + 1).start()
        dma(slot, k).wait()
        out_ref[k * r : (k + 1) * r, :] = scratch[slot]


@functools.partial(
    jax.jit,
    static_argnames=("block_d", "rows_per_block", "rows_per_step", "interpret"),
)
def batch_gather_dma(
    table: jax.Array,
    indices: jax.Array,
    *,
    block_d: int = 512,
    rows_per_block: int = 1,
    rows_per_step: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Multi-row, double-buffered ``batch_gather``.

    Semantics match :func:`batch_gather` bit-exactly; the difference is the
    execution shape: the grid shrinks by ``rows_per_step`` and each step
    issues its own async DMAs from the HBM-resident table, double-buffered
    through a 2-slot VMEM scratch ring.

    table:   (N, D)  — HBM-resident dataset shard
    indices: (B,) int32 — block ids (record ids when rows_per_block=1)
    returns: (B * rows_per_block, D)
    """
    n, d = table.shape
    b = indices.shape[0]
    r = rows_per_block
    m = min(rows_per_step, b)
    assert n % r == 0, (n, r)
    bd = min(block_d, d)
    assert d % bd == 0, (d, bd)

    b_pad = -(-b // m) * m
    if b_pad != b:
        # pad with index 0 — extra rows are computed then sliced away
        indices = jnp.concatenate(
            [indices, jnp.zeros(b_pad - b, indices.dtype)]
        )

    grid = (b_pad // m, d // bd)
    kernel = functools.partial(_gather_dma_kernel, m=m, r=r, bd=bd)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((m * r, bd), lambda i, j, idx: (i, j)),
            scratch_shapes=[
                pltpu.VMEM((2, r, bd), table.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b_pad * r, d), table.dtype),
        interpret=interpret,
    )(indices.astype(jnp.int32), table)
    return out[: b * r]
