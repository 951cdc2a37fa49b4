"""Batched property-filtered neighbor sampling — one launch per seed batch.

The serving-path sampler (docs/ARCHITECTURE.md §15): gather the SEG/DST
adjacency window of every seed in a batch, reject edges the packed edge
mask disallows by reading its uint32 words DIRECTLY (bit ``e & 31`` of
word ``e >> 5`` — the ``core.bitplane`` layout, no bool materialization),
draw one uniform priority per window lane, and keep the ``fanout``
smallest-priority allowed lanes per seed.  Order statistics of i.i.d.
uniforms make that a uniform without-replacement sample of the filtered
adjacency; degree-0 (or fully filtered) seeds come out fully masked, and
seeds with filtered degree ≤ fanout keep every allowed edge exactly once.

Shape discipline mirrors ``bitmap_query``: the jitted programs specialize
on (request count R, seed capacity S, window W, fanout), so all three are
bucketed — R through :func:`bucketed_requests` (the scheduler's coalesced
group), S through :func:`bucketed_seeds`, W through
:func:`bucketed_window` (graph max-degree, static per graph).  Compile
count across QPS traffic is therefore bounded by the bucket grids, which
:func:`sample_compile_count` (backed by the ``pg_sample_compiles``
process counter) makes assertable.

Lowering: the selection math is plain XLA (`lax.top_k` over negated
priorities — ties break to the lower lane) on every entry — one fused
program that composes with GSPMD-sharded ``seg``/``dst`` under a mesh,
where sampling stays owner-device local (each seed's window gather
touches only the shard holding its slice).

Randomness contract: callers pass explicit PRNG keys; every program
derives its uniforms ONLY from the per-request key (row r of a batched
launch uses key r and nothing else), so a request samples bitwise
identically whether it runs alone or coalesced into any batch — the
parity the service tests rely on.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitplane
from repro.obs.metrics import GLOBAL as _OBS
from repro.obs.metrics import enabled as _obs_enabled

__all__ = [
    "SEED_BUCKET_MIN",
    "WINDOW_BUCKET_MIN",
    "REQUEST_BUCKETS",
    "bucketed_requests",
    "bucketed_seeds",
    "bucketed_window",
    "neighbor_sample",
    "neighbor_sample_batched",
    "neighbor_sample_from_words",
    "sample_compile_count",
    "sample_embed",
]

SEED_BUCKET_MIN = 16  # smallest seed-capacity bucket (khop_csr's floor)
WINDOW_BUCKET_MIN = 8  # smallest adjacency-window bucket
REQUEST_BUCKETS = (1, 2, 4, 8, 16, 32)  # coalesced-group R buckets

_M_COMPILES = _OBS.counter(
    "pg_sample_compiles", "distinct neighbor_sample program specializations")
_M_LAUNCHES = _OBS.counter(
    "pg_sample_launches", "neighbor_sample device launches")
_SEEN_KEYS: set = set()


def _pow2_bucket(size: int, floor: int) -> int:
    cap = floor
    while cap < size:
        cap <<= 1
    return cap


def bucketed_seeds(s: int) -> int:
    """Seed-batch capacity bucket: next power of two ≥ s (min 16)."""
    return _pow2_bucket(max(int(s), 1), SEED_BUCKET_MIN)


def bucketed_window(w: int) -> int:
    """Adjacency-window bucket: next power of two ≥ w (min 8).  Static per
    graph — callers pass max(graph max-degree, fanout)."""
    return _pow2_bucket(max(int(w), 1), WINDOW_BUCKET_MIN)


def bucketed_requests(r: int) -> int:
    """Coalesced request-count bucket (``bucketed_q`` scheme: fixed grid,
    multiples of the top bucket beyond it)."""
    if r < 1:
        raise ValueError(f"r must be ≥ 1, got {r}")
    for b in REQUEST_BUCKETS:
        if r <= b:
            return b
    top = REQUEST_BUCKETS[-1]
    return -(-r // top) * top


def _note_launch(kind: str, shape_key: tuple) -> None:
    """Host-side compile/launch accounting: a (kind, static shapes) tuple
    not seen before in this process is a new XLA specialization."""
    if not _obs_enabled():
        return
    _M_LAUNCHES.inc()
    key = (kind,) + shape_key
    if key not in _SEEN_KEYS:
        _SEEN_KEYS.add(key)
        _M_COMPILES.inc()


def sample_compile_count() -> int:
    """Distinct sampler program specializations this process has seen."""
    return len(_SEEN_KEYS)


# --------------------------------------------------------------- core select
def _bit_at(words: jax.Array, idx: jax.Array) -> jax.Array:
    """Read bit ``idx`` of a packed uint32 word vector (bitplane layout)."""
    w = words[idx >> 5]
    return ((w >> (idx & 31).astype(jnp.uint32)) & jnp.uint32(1)).astype(bool)


def _window_select(seg, dst, m: int, n: int, seeds, valid, ew_words, u,
                   fanout: int):
    """The selection core (traceable): per seed, gather its SEG window,
    mask disallowed lanes to +inf priority, keep the ``fanout`` smallest.

    seeds (S,) int32 in [0, n) (pad rows arbitrary but ``valid`` False),
    u (S, W) f32 uniforms, ew_words packed (ceil(m/32),) uint32 or None.
    Returns (nbrs, eids, mask) each (S, fanout); -1 in masked slots.
    """
    W = u.shape[1]
    sidx = jnp.clip(seeds, 0, max(n - 1, 0))
    start = seg[sidx]
    deg = seg[sidx + 1] - start
    lane = jnp.arange(W, dtype=jnp.int32)
    eidx = start[:, None] + lane[None, :]
    in_win = (lane[None, :] < deg[:, None]) & valid[:, None]
    eidx_c = jnp.clip(eidx, 0, max(m - 1, 0))
    allowed = in_win if ew_words is None else in_win & _bit_at(ew_words, eidx_c)
    pri = jnp.where(allowed, u, jnp.float32(jnp.inf))
    neg, sel = jax.lax.top_k(-pri, fanout)  # ties → lower lane first
    ok = neg > jnp.float32(-jnp.inf)
    sel_e = jnp.take_along_axis(eidx_c, sel, axis=1)
    nbrs = jnp.where(ok, dst[sel_e], -1)
    eids = jnp.where(ok, sel_e, -1)
    return nbrs, eids, ok


@partial(jax.jit, static_argnames=("m", "n", "fanout", "window"))
def _sample_one(seg, dst, seeds, valid, ew_words, key, *, m: int, n: int,
                fanout: int, window: int):
    u = jax.random.uniform(key, (seeds.shape[0], window))
    return _window_select(seg, dst, m, n, seeds, valid, ew_words, u, fanout)


@partial(jax.jit, static_argnames=("m", "n", "fanout", "window"))
def _sample_many(seg, dst, seeds, valid, ew_words, keys, *, m: int, n: int,
                 fanout: int, window: int):
    """(R, S) stacked requests → (R, S, fanout) outputs, ONE launch.  Row r
    reads only its own key (and its own edge words when per-request
    filters differ), so each row is bitwise the row's solo launch."""

    def row(sd, vl, ew, k):
        u = jax.random.uniform(k, (sd.shape[0], window))
        return _window_select(seg, dst, m, n, sd, vl, ew, u, fanout)

    if ew_words is None:
        return jax.vmap(lambda sd, vl, k: row(sd, vl, None, k))(
            seeds, valid, keys)
    return jax.vmap(row)(seeds, valid, ew_words, keys)


@partial(jax.jit, static_argnames=("m", "n", "cap", "fanout", "window"))
def _sample_from_words(seg, dst, seed_words, ew_words, key, *, m: int, n: int,
                       cap: int, fanout: int, window: int):
    """Packed-seed entry: the uint32 seed bitmap feeds the window gather
    inside ONE program — bit-expansion and index extraction never leave
    the device (the §15 seed-bitmap handoff)."""
    bits = bitplane.unpack_mask(seed_words, n)
    idx = jnp.nonzero(bits, size=cap, fill_value=n)[0].astype(jnp.int32)
    valid = idx < n
    u = jax.random.uniform(key, (cap, window))
    nbrs, eids, ok = _window_select(
        seg, dst, m, n, idx, valid, ew_words, u, fanout)
    return idx, valid, nbrs, eids, ok


@partial(jax.jit, static_argnames=("m", "n", "fanout", "window"))
def _sample_embed_one(seg, dst, seeds, valid, ew_words, key, table, *,
                      m: int, n: int, fanout: int, window: int):
    """Fused sample+lookup: the sampled neighbor ids index an embedding
    table and mean-pool inside the SAME program — a recsys request is one
    device program instead of sample → host → embedding_bag."""
    u = jax.random.uniform(key, (seeds.shape[0], window))
    nbrs, eids, ok = _window_select(
        seg, dst, m, n, seeds, valid, ew_words, u, fanout)
    rows = table[jnp.clip(nbrs, 0, table.shape[0] - 1)]  # (S, fanout, D)
    w = ok[..., None].astype(table.dtype)
    cnt = jnp.maximum(ok.sum(axis=-1, keepdims=True), 1).astype(table.dtype)
    bags = jnp.sum(rows * w, axis=1) / cnt  # (S, D); all-masked seeds → 0
    return bags, nbrs, eids, ok


# ---------------------------------------------------------- public wrappers
def _pad_seeds(seeds, cap: int) -> Tuple[jax.Array, jax.Array]:
    seeds = jnp.asarray(seeds, jnp.int32).reshape(-1)
    s = int(seeds.shape[0])
    if s > cap:
        raise ValueError(f"{s} seeds exceed capacity {cap}")
    valid = jnp.arange(cap, dtype=jnp.int32) < s
    if s < cap:
        seeds = jnp.concatenate([seeds, jnp.zeros((cap - s,), jnp.int32)])
    return seeds, valid


def _window_for(max_deg: Optional[int], seg, fanout: int) -> int:
    if max_deg is None or max_deg < 0:
        max_deg = int(np.max(np.asarray(seg[1:]) - np.asarray(seg[:-1]),
                             initial=0))
    return bucketed_window(max(int(max_deg), int(fanout)))


def neighbor_sample(seg, dst, n: int, m: int, seeds, key, *, fanout: int,
                    edge_words=None, max_deg: Optional[int] = None):
    """Sample ≤ ``fanout`` filtered out-neighbors per seed, one launch.

    ``edge_words``: packed (ceil(m/32),) uint32 edge-allowed bitmap (None
    = every edge).  Returns (nbrs, eids, mask) shaped (S_cap, fanout) with
    S_cap = ``bucketed_seeds(len(seeds))``; rows past the real seed count
    are fully masked."""
    cap = bucketed_seeds(np.asarray(seeds).size)
    window = _window_for(max_deg, seg, fanout)
    sd, valid = _pad_seeds(seeds, cap)
    _note_launch("one", (cap, window, int(fanout), edge_words is not None,
                         n, m))
    return _sample_one(
        seg, dst, sd, valid,
        None if edge_words is None else jnp.asarray(edge_words),
        key, m=m, n=n, fanout=int(fanout), window=window)


def neighbor_sample_batched(seg, dst, n: int, m: int, seeds, valid, keys, *,
                            fanout: int, edge_words=None,
                            max_deg: Optional[int] = None):
    """Coalesced entry: R stacked requests → ONE launch (module docstring).

    ``seeds``/``valid``: (R, S_cap) padded id rows; ``keys``: (R, 2)
    uint32 per-request PRNG keys; ``edge_words``: (R, W_m) per-request
    packed edge filters or None.  R must already be padded to
    ``bucketed_requests`` (pad rows: valid all-False, any key).  Returns
    (nbrs, eids, mask) shaped (R, S_cap, fanout)."""
    seeds = jnp.asarray(seeds, jnp.int32)
    R, S = int(seeds.shape[0]), int(seeds.shape[1])
    window = _window_for(max_deg, seg, fanout)
    _note_launch("many", (R, S, window, int(fanout), edge_words is not None,
                          n, m))
    return _sample_many(
        seg, dst, seeds, jnp.asarray(valid),
        None if edge_words is None else jnp.asarray(edge_words),
        jnp.asarray(keys), m=m, n=n, fanout=int(fanout), window=window)


def neighbor_sample_from_words(seg, dst, n: int, m: int, seed_words,
                               seed_count: int, key, *, fanout: int,
                               edge_words=None,
                               max_deg: Optional[int] = None):
    """Packed-seed entry: seeds arrive as a uint32 bitmap (the ``match()``
    combine's output words); ``seed_count`` (its popcount, the one scalar
    the host reads) picks the capacity bucket.  Returns (seeds, valid,
    nbrs, eids, mask) with S_cap = ``bucketed_seeds(seed_count)``."""
    cap = bucketed_seeds(seed_count)
    window = _window_for(max_deg, seg, fanout)
    _note_launch("words", (cap, window, int(fanout), edge_words is not None,
                           n, m))
    return _sample_from_words(
        seg, dst, jnp.asarray(seed_words),
        None if edge_words is None else jnp.asarray(edge_words),
        key, m=m, n=n, cap=cap, fanout=int(fanout), window=window)


def sample_embed(seg, dst, n: int, m: int, seeds, key, table, *, fanout: int,
                 edge_words=None, max_deg: Optional[int] = None):
    """Fused ``sample+lookup`` verb: sample filtered neighbors AND
    mean-pool their embedding rows in one program.  ``table``: (V, D)
    with V ≥ n.  Returns (bags (S_cap, D), nbrs, eids, mask); bags of
    fully-masked seeds are zero."""
    cap = bucketed_seeds(np.asarray(seeds).size)
    window = _window_for(max_deg, seg, fanout)
    sd, valid = _pad_seeds(seeds, cap)
    _note_launch("embed", (cap, window, int(fanout), edge_words is not None,
                           n, m, int(table.shape[-1])))
    return _sample_embed_one(
        seg, dst, sd, valid,
        None if edge_words is None else jnp.asarray(edge_words),
        key, jnp.asarray(table), m=m, n=n, fanout=int(fanout), window=window)
