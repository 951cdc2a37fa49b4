"""Pallas TPU kernels: DIP-ARR attribute query, byte (MXU) and packed (VPU).

The paper's DIP-ARR query scans the selected attribute rows of the (K, N)
byte bitmap and ORs them (§VI-C, O(N/P)).  On TPU the byte form is
reformulated for the systolic array:

    counts(1, Nt) = mask(1, K) @ bitmap(K, Nt);   out = counts > 0

Grid: 1-D over entity tiles (the paper's distribution dimension).  Each step
holds a (K, Nt) bitmap block and the full (1, K) query mask in VMEM.
VMEM budget: K ≤ 512 attributes × Nt = 2048 entities × 4 B (f32 on the MXU
path) ≈ 4 MiB — comfortably inside the ~16 MiB/core VMEM envelope; Nt is the
lane-aligned (×128) tunable.

The PACKED form works on the (K, W = ceil(N/32)) uint32 word plane instead.
There is no MXU trick for bitwise OR, but none is needed: the scan is
bandwidth-bound, and the packed plane moves 8× fewer bytes than int8 (32×
fewer than the f32 the MXU path casts to).  The kernel is a VPU loop over K
accumulating ``acc |= select[:, a] & plane[a]`` on (Q, Wt) uint32 lanes.
The query masks arrive as one (Q, K) f32 0/1 block; each step widens
column ``a`` to a (Q, 1) full-word 0x00000000/0xFFFFFFFF select in
registers.  uint32 is a 32-bit lane type ⇒ (8, 128) minimum tile;
Wt = 512 words (= 16 384 entities) keeps the (K, Wt) block at K=512 to
1 MiB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TILE_N = 2048
DEFAULT_TILE_W = 512  # packed words per grid step (×128 lane-aligned)


def _bitmap_query_kernel(mask_ref, bitmap_ref, out_ref):
    mask = mask_ref[...]          # (Q, K) f32 — Q=1 for the single-query form
    block = bitmap_ref[...]       # (K, Nt) int8
    counts = jnp.dot(mask, block.astype(jnp.float32),
                     preferred_element_type=jnp.float32)  # (Q, Nt) on the MXU
    out_ref[...] = (counts > 0.5)


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def bitmap_query_pallas(bitmap: jax.Array, attr_mask: jax.Array, *,
                        tile_n: int = DEFAULT_TILE_N, interpret: bool = True) -> jax.Array:
    """bitmap: (K, N) int8; attr_mask: (K,) bool → (N,) bool."""
    k, n = bitmap.shape
    tile_n = min(tile_n, n)
    pad = (-n) % tile_n
    if pad:
        bitmap = jnp.pad(bitmap, ((0, 0), (0, pad)))
    n_pad = n + pad
    maskf = attr_mask.astype(jnp.float32)[None, :]  # (1, K)

    out = pl.pallas_call(
        _bitmap_query_kernel,
        grid=(n_pad // tile_n,),
        in_specs=[
            pl.BlockSpec((1, k), lambda i: (0, 0)),        # query mask: replicated
            pl.BlockSpec((k, tile_n), lambda i: (0, i)),   # bitmap: entity tiles
        ],
        out_specs=pl.BlockSpec((1, tile_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.bool_),
        interpret=interpret,
    )(maskf, bitmap)
    return out[0, :n]


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def bitmap_query_batched_pallas(bitmap: jax.Array, attr_masks: jax.Array, *,
                                tile_n: int = DEFAULT_TILE_N,
                                interpret: bool = True) -> jax.Array:
    """Batched multi-mask form: ``bitmap (K, N) int8 × attr_masks (Q, K) bool
    → (Q, N) bool`` in ONE kernel launch.

    The planner fuses the label masks of every node slot of a pattern into
    this single launch: the (K, Nt) bitmap tile is read from HBM once and
    reused across all Q query rows on the MXU (``(Q, K) @ (K, Nt)``) instead
    of once per mask — same grid, Q× the arithmetic intensity.
    """
    k, n = bitmap.shape
    q = attr_masks.shape[0]
    tile_n = min(tile_n, n)
    pad = (-n) % tile_n
    if pad:
        bitmap = jnp.pad(bitmap, ((0, 0), (0, pad)))
    n_pad = n + pad
    maskf = attr_masks.astype(jnp.float32)  # (Q, K)

    out = pl.pallas_call(
        _bitmap_query_kernel,
        grid=(n_pad // tile_n,),
        in_specs=[
            pl.BlockSpec((q, k), lambda i: (0, 0)),        # all queries: replicated
            pl.BlockSpec((k, tile_n), lambda i: (0, i)),   # bitmap: entity tiles
        ],
        out_specs=pl.BlockSpec((q, tile_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((q, n_pad), jnp.bool_),
        interpret=interpret,
    )(maskf, bitmap)
    return out[:, :n]


def _bitmap_query_packed_kernel(select_ref, plane_ref, out_ref):
    select = select_ref[...]      # (Q, K) f32 — 1.0 where query row selects a
    q, k = select.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, k), 1)

    def body(a, acc):
        # Mosaic cannot slice a value at a traced lane index, so column a
        # of the selects is picked by a lane-masked max instead of select[:, a]
        pick = jnp.max(jnp.where(lane == a, select, 0.0), axis=1, keepdims=True)
        col = jnp.where(pick > 0.5, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
        return acc | (col & plane_ref[pl.ds(a, 1), :])   # (Q, 1) & (1, Wt)

    out_ref[...] = jax.lax.fori_loop(0, k, body, jnp.zeros(out_ref.shape, jnp.uint32))


@functools.partial(jax.jit, static_argnames=("tile_w", "interpret"))
def bitmap_query_batched_packed_pallas(
    plane: jax.Array, attr_masks: jax.Array, *,
    tile_w: int = DEFAULT_TILE_W, interpret: bool = True
) -> jax.Array:
    """Packed batched query: ``plane (K, W) uint32 × attr_masks (Q, K) bool
    → (Q, W) uint32`` word masks, one launch for all Q queries.

    The fori_loop over K keeps VMEM at (K, Wt) + (Q, Wt) — no (Q, K, Wt)
    intermediate — while each (K, Wt) plane tile streams from HBM exactly
    once for all Q query rows.  W need not divide by the tile: each output
    word reads only its own plane column, so the ragged last block's
    out-of-range lanes never reach a kept word, and the plane is not
    re-padded (copied) per call.
    """
    k, w = plane.shape
    q = attr_masks.shape[0]
    tile_w = min(tile_w, w)
    return pl.pallas_call(
        _bitmap_query_packed_kernel,
        grid=(pl.cdiv(w, tile_w),),
        in_specs=[
            pl.BlockSpec((q, k), lambda i: (0, 0)),        # selects: replicated
            pl.BlockSpec((k, tile_w), lambda i: (0, i)),   # plane: word tiles
        ],
        out_specs=pl.BlockSpec((q, tile_w), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((q, w), jnp.uint32),
        interpret=interpret,
    )(attr_masks.astype(jnp.float32), plane)


@functools.partial(jax.jit, static_argnames=("tile_w", "interpret"))
def bitmap_query_packed_pallas(plane: jax.Array, attr_mask: jax.Array, *,
                               tile_w: int = DEFAULT_TILE_W,
                               interpret: bool = True) -> jax.Array:
    """Packed single query: ``plane (K, W) uint32 × attr_mask (K,) bool →
    (W,) uint32`` word mask."""
    out = bitmap_query_batched_packed_pallas(
        plane, attr_mask[None, :], tile_w=tile_w, interpret=interpret)
    return out[0]
