"""Pallas TPU kernel: embedding-bag (multi-hot gather-reduce) for DLRM.

The FBGEMM-TBE access pattern adapted to TPU: per (field, batch-tile) grid
step, the kernel walks the tile's bag indices (scalar-prefetched) and issues
row loads from the field's table — HBM→VMEM DMAs of one row each (the
table lives in ANY/HBM memory space; each row is one ``make_async_copy``
into a VMEM row buffer), accumulated in a VMEM scratch tile and divided by
the bag size (mean pooling).  This is the DIP-LIST CSR query generalized from OR-mask
to weighted segment reduction (DESIGN.md §4).

Sizing: bag indices are (Bt, MH) int32 in SMEM; accumulation tile (Bt, D) f32
in VMEM — Bt=256, D≤128 ⇒ 128 KiB, trivially VMEM-resident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BT = 256


def _embedding_bag_kernel(idx_ref, table_ref, out_ref, acc_scr, row_scr, sem,
                          *, bt: int, mh: int):
    f = pl.program_id(0)  # field (tables are field-major in HBM)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def bag_body(b, carry):
        def hot_body(h, c):
            row = idx_ref[0, b, h]
            # an ANY/HBM ref is read only through a DMA: one (1, D) row
            copy = pltpu.make_async_copy(
                table_ref.at[f, pl.ds(row, 1), :], row_scr, sem)
            copy.start()
            copy.wait()
            acc_scr[pl.ds(b, 1), :] += row_scr[...].astype(jnp.float32)
            return c

        return jax.lax.fori_loop(0, mh, hot_body, carry)

    jax.lax.fori_loop(0, bt, bag_body, 0)
    out_ref[0] = (acc_scr[...] / mh).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def embedding_bag_pallas(tables: jax.Array, idx: jax.Array, *, bt: int = DEFAULT_BT,
                         interpret: bool = True) -> jax.Array:
    """tables: (F, V, D); idx: (B, F, MH) int32 → (B, F, D) mean-pooled bags.

    Compiled (``interpret=False``), D must be a multiple of 128: Mosaic
    slices the HBM table only in whole 128-lane tiles, so a narrower row
    cannot be DMA'd.  Padding the table instead would copy all of it on
    every call, so a narrower D is refused here with a ValueError."""
    B, F, MH = idx.shape
    _, V, D = tables.shape
    if not interpret and D % 128:
        raise ValueError(
            f"embedding_bag_pallas: compiled for TPU, the row width D={D} "
            "must be a multiple of 128 (use the 'take' path for narrower "
            "tables)")
    bt = min(bt, B)
    assert B % bt == 0, (B, bt)
    idx_t = idx.transpose(1, 0, 2)  # (F, B, MH) — field-major for the grid

    out = pl.pallas_call(
        functools.partial(_embedding_bag_kernel, bt=bt, mh=MH),
        grid=(F, B // bt),
        in_specs=[
            pl.BlockSpec((1, bt, MH), lambda f, b: (f, b, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),  # whole table stack in HBM
        ],
        out_specs=pl.BlockSpec((1, bt, D), lambda f, b: (f, b, 0)),
        out_shape=jax.ShapeDtypeStruct((F, B, D), tables.dtype),
        scratch_shapes=[pltpu.VMEM((bt, D), jnp.float32),
                        pltpu.VMEM((1, D), tables.dtype),
                        pltpu.SemaphoreType.DMA],
        interpret=interpret,
    )(idx_t, tables)
    return out.transpose(1, 0, 2)  # (B, F, D)
