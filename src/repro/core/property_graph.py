"""PropGraph — the user-facing property-graph API (mirrors Arachne's Python surface).

Workflow (§V of the paper):

    pg = PropGraph(backend="arr")                      # ar.PropGraph()
    pg.add_edges_from(src, dst)                        # bulk DI build
    pg.add_node_labels(nodes, labels)                  # strings ok
    pg.add_edge_relationships(esrc, edst, rels)
    pg.add_node_properties("age", nodes, ages)         # typed columns
    vmask = pg.query_labels(["person", "place"])       # OR semantics
    emask = pg.query_relationships(["follows"])
    sub, kept = pg.subgraph(labels=[...], relationships=[...])

Ingestion follows the paper's three steps: (1) attribute values remapped to
dense int ids (`AttributeMap`), (2) internal vertex/edge indices generated
(vertex normalization + `edge_lookup` binary search), (3) bulk insert into the
chosen DIP backend.  Backends: ``arr`` (DIP-ARR bitmap), ``list`` (DIP-LIST
CSR), ``listd`` (DIP-LISTD linked chains + inverted CSR).

Distribution (docs/ARCHITECTURE.md §7): ``PropGraph(backend=..., mesh=...)``
opts into multi-device execution via ``core.dip_shard`` and the
``launch.sharding.pg_specs`` family.  The DIP stores — the heavy query-side
data — are padded to the shard count and always entity-sharded, and every
query runs under ``shard_map`` so each device scans only its N/P entity
slice.  DI arrays and typed property columns keep their exact logical sizes:
they shard when their length divides the device count and replicate
otherwise (explicit placements require even shards).  Results are
bitwise-identical to the default single-device path.
"""
from __future__ import annotations

import functools
import operator
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitplane, dip_arr, dip_list, dip_listd, dip_shard
from repro.core.attr_map import AttributeMap
from repro.core.di import DIGraph, build_di, edge_lookup
from repro.core.queries import extract_subgraph, filtered_bfs, induce_edge_mask
from repro.obs.metrics import GLOBAL as _OBS
from repro.obs.metrics import SIZE_BUCKETS as _SIZE_BUCKETS
from repro.obs.metrics import enabled as _obs_enabled
from repro.overlay.delta import AttrDelta, EdgeDelta, MutationEvent, pair_keys


def _obs_traverse(op: str, rounds: Optional[int], seeds: Optional[int]) -> None:
    """Frontier/semiring engine accounting (docs/ARCHITECTURE.md §13):
    per-op run counts plus the host-known shape of the work — relax-round
    budgets and seed-set sizes.  The exact converged round count lives
    inside a jitted ``while_loop``; reading it back would force a device
    sync per call, so the budget (``k``/``max_iters``, the loop's bound)
    is what's recorded.  Host-side only, never a device sync."""
    if not _obs_enabled():
        return
    _OBS.counter("pg_traverse_runs", "frontier/semiring engine runs",
                 op=op).inc()
    if rounds is not None:
        _OBS.histogram("pg_traverse_relax_rounds",
                       "relax-round budget per run (loop bound)",
                       buckets=_SIZE_BUCKETS, op=op).observe(rounds)
    if seeds is not None:
        _OBS.histogram("pg_traverse_seed_size",
                       "seed/frontier-origin set size per run",
                       buckets=_SIZE_BUCKETS, op=op).observe(seeds)

__all__ = ["PropGraph", "BACKENDS"]

BACKENDS = ("arr", "list", "listd")


def _write_locked(fn):
    """Serialize a mutator (or ``compact``) on the per-graph write lock.

    Writes and compaction are mutually exclusive: ``compact_propgraph``
    gathers the overlay, rebuilds, then swaps the stores — a mutation
    landing inside that window would be silently discarded by the swap, so
    every path that changes graph state takes the same re-entrant lock
    (re-entrant because ``insert_edges`` falls back to ``add_edges_from``
    and ``compact`` runs nested helpers).  Readers stay lock-free: the
    service layer re-checks ``version`` around execution and retries torn
    views, and ``snapshot()`` clones under the lock for a consistent pin."""

    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        with self._write_lock:
            return fn(self, *args, **kwargs)

    return wrapper


class _AttrStore:
    """One DIP instance over ``n_entities`` (vertices or edges).

    With ``mesh`` set, ``finalize_sharded()`` additionally maintains a padded,
    device-placed copy of the store (``core.dip_shard``) and the query paths
    run under ``shard_map``; both caches invalidate together on ``insert``.

    LSM write path (docs/ARCHITECTURE.md §11): the first query *seals* the
    base (dense device store or sharded placement, built at ``_k_base``
    attribute rows).  Later inserts land in ``_delta`` — a small append-only
    host buffer — in O(batch) instead of invalidating and rebuilding the
    O(N·K) dense form.  Queries answer ``base_mask | delta_mask``, exact
    stats come from ``attr_counts`` (base counts + delta counts deduped
    against ``base_keys``), and the overlay compactor folds the delta back
    into the pair lists before a fresh seal.  ``out_n`` is the query result
    length: it tracks the EFFECTIVE entity universe (base + delta edges for
    the edge store) while ``n`` stays the sealed base's row count.
    """

    def __init__(self, backend: str, n_entities: int, mesh=None):
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
        self.backend = backend
        self.n = n_entities
        self.out_n = n_entities
        self.mesh = mesh
        self.amap = AttributeMap()
        self._pairs_e: List[np.ndarray] = []  # entity ids, insertion order
        self._pairs_a: List[np.ndarray] = []  # attribute ids
        self._store = None
        self._sharded = None
        self._host = None  # host-built dense form awaiting upload/placement
        self._counts: Optional[np.ndarray] = None
        self._dirty = True
        self._delta = AttrDelta()  # pairs landed after the base was sealed
        self._k_base: Optional[int] = None  # attribute rows in the sealed base
        self._base_keys: Optional[np.ndarray] = None  # sorted base pair keys

    @property
    def sealed(self) -> bool:
        """A device/sharded base exists — inserts must not invalidate it."""
        return self._store is not None or self._sharded is not None

    @property
    def packed(self) -> bool:
        """True when this store's base holds (or will hold) the bit-packed
        uint32 word plane (arr only).  Captured at build time — a built
        store answers from its own layout even if the process-wide flag
        flips afterwards."""
        if self.backend != "arr":
            return False
        for built in (self._store, self._sharded, self._host):
            if built is not None:
                return bool(built.packed)
        return bitplane.packed_default()

    def insert(self, entity_ids: np.ndarray, values: Sequence[str]) -> None:
        attr_ids = self.amap.encode(values)
        attr_ids = np.broadcast_to(np.atleast_1d(attr_ids), np.shape(entity_ids)).ravel()
        entity_ids = np.asarray(entity_ids, np.int32).ravel()
        ok = entity_ids >= 0  # unmatched edge rows (edge_lookup -1) are dropped
        ent, att = entity_ids[ok], attr_ids[ok].astype(np.int32)
        if self.sealed:
            # LSM path: the sealed base is immutable — O(batch) delta append,
            # no store invalidation, no rebuild
            self._delta.append(ent, att)
            return
        # pre-seal: entities beyond the base universe (delta edges) can never
        # enter the n-row dense build — they live in the delta regardless
        hi = ent >= self.n
        if hi.any():
            self._delta.append(ent[hi], att[hi])
            ent, att = ent[~hi], att[~hi]
        self._pairs_e.append(ent)
        self._pairs_a.append(att)
        self._counts = None
        self._host = None
        self._dirty = True
        self._base_keys = None

    @property
    def k(self) -> int:
        return max(len(self.amap), 1)

    def _build_host(self):
        """Dense store with HOST (numpy) arrays, built from the raw pairs.

        Also derives the per-attribute selectivity stats (``attr_counts``)
        while the dense form is in hand — bitmap row sums / CSR segment
        lengths, computed host-side so the stats never require a device
        store.  The build is stashed in ``_host`` so a stats read followed
        by a query builds once, not twice; ``finalize`` /
        ``finalize_sharded`` consume the stash — after placement the dense
        copy is RELEASED in mesh mode (per-device memory stays O(NK/P),
        docs/ARCHITECTURE.md §7)."""
        if self._host is not None:
            return self._host
        ent = np.concatenate(self._pairs_e) if self._pairs_e else np.zeros(0, np.int32)
        att = np.concatenate(self._pairs_a) if self._pairs_a else np.zeros(0, np.int32)
        if self.backend == "arr":
            host = dip_arr.build_dip_arr_host(ent, att, k=self.k, n=self.n)
            if host.packed:
                # popcount of the word plane rows ≡ the byte row sums
                self._counts = np.bitwise_count(host.bitmap).sum(
                    axis=1, dtype=np.int64)
            else:
                self._counts = host.bitmap.sum(axis=1, dtype=np.int64)
        elif self.backend == "list":
            host = dip_list.build_dip_list_host(ent, att, k=self.k, n=self.n)
            self._counts = np.bincount(np.asarray(host.val), minlength=self.k)
        else:
            host = dip_listd.build_dip_listd_host(ent, att, k=self.k, n=self.n)
            self._counts = np.asarray(host.a_off[1:] - host.a_off[:-1])
        self._host = host
        self._k_base = self.k  # the row count this base answers queries at
        return host

    def finalize(self):
        if not self._dirty and self._store is not None:
            return self._store
        self._store = jax.tree_util.tree_map(jnp.asarray, self._build_host())
        self._host = None  # consumed; the device copy is the cache now
        self._dirty = False
        return self._store

    def finalize_sharded(self):
        """Padded, mesh-placed copy of the store (mesh mode only).

        Builds the dense form host-side, places the padded shards, and
        releases the dense copy — no device (and no cache slot) holds a
        full replica; the selectivity stats survive in ``_counts``."""
        if self._sharded is None:
            self._sharded = dip_shard.place_store(
                self.backend, self._build_host(), self.mesh
            )
            self._host = None  # dense copy released after placement
        return self._sharded

    def known_ids(self, values: Sequence[str]) -> np.ndarray:
        """Interned attribute ids for ``values`` (unknown values dropped)."""
        ids = np.atleast_1d(self.amap.lookup(list(values)))
        return ids[ids >= 0].astype(np.int32)

    def base_keys(self) -> np.ndarray:
        """Sorted unique packed (entity, attribute) keys of the BASE pairs —
        the dedup reference ``attr_counts`` uses so re-inserting a pair that
        already sits in the sealed base never double-counts."""
        if self._base_keys is None:
            ent = np.concatenate(self._pairs_e) if self._pairs_e else np.zeros(0, np.int32)
            att = np.concatenate(self._pairs_a) if self._pairs_a else np.zeros(0, np.int32)
            self._base_keys = np.unique(pair_keys(ent, att))
        return self._base_keys

    def all_pairs(self) -> Tuple[np.ndarray, np.ndarray]:
        """Full (entity, attribute) pair history, base ++ delta, insertion
        order preserved — what the compactor folds into a fresh base."""
        de, da = self._delta.cat()
        ent = self._pairs_e + ([de] if de.size else [])
        att = self._pairs_a + ([da] if da.size else [])
        if not ent:
            return np.zeros(0, np.int32), np.zeros(0, np.int32)
        return np.concatenate(ent), np.concatenate(att)

    def attr_counts(self, *, dead_ids: Optional[np.ndarray] = None) -> np.ndarray:
        """(k,) per-attribute entity counts — the DIP selectivity statistics
        the planner orders joins with (bitmap row sums / CSR segment
        lengths; each store carries them for free).  Derived host-side
        during ``_build_host`` — reading them never uploads a store — and
        invalidated with the store (``insert`` clears them).  With a live
        delta, the sealed base's counts are padded to the current attribute
        set and the delta's (base-deduped) counts add in — still exact, so
        the planner never orders joins with stale or estimated stats.

        ``dead_ids`` (sorted or not) subtracts the contributions of
        tombstoned entities, so counts agree with what ``query_any`` masked
        by the alive masks actually returns — ``PropGraph.label_counts`` /
        ``relationship_counts`` and the planner pass the tombstone set."""
        if self._counts is None:
            self._build_host()  # sets _counts; build stays stashed for the
            # next finalize, so stats-then-query builds once
        counts = self._counts
        k = self.k
        if len(counts) < k:
            counts = np.concatenate(
                [counts, np.zeros(k - len(counts), counts.dtype)])
        if self._delta.size:
            counts = counts + self._delta.counts(k, self.base_keys())
        if dead_ids is not None and np.asarray(dead_ids).size:
            counts = counts - self._dead_attr_counts(np.asarray(dead_ids))
        return counts

    def _dead_attr_counts(self, dead_ids: np.ndarray) -> np.ndarray:
        """(k,) per-attribute pair counts held by tombstoned entities.

        Mirrors ``attr_counts``'s accounting exactly — base pairs counted
        the way the backend stores them (``listd`` keeps duplicate pairs,
        ``arr``/``list`` dedupe) plus the delta's base-deduped unique pairs
        — so subtracting it yields the alive-only statistic."""
        k = self.k
        out = np.zeros(k, np.int64)
        ent = np.concatenate(self._pairs_e) if self._pairs_e else np.zeros(0, np.int32)
        att = np.concatenate(self._pairs_a) if self._pairs_a else np.zeros(0, np.int32)
        if ent.size:
            if self.backend != "listd":
                keys = np.unique(pair_keys(ent, att))
                ent = (keys >> 31).astype(np.int64)
                att = (keys & 0x7FFFFFFF).astype(np.int64)
            sel = np.isin(ent, dead_ids)
            if sel.any():
                out += np.bincount(att[sel], minlength=k)[:k]
        if self._delta.size:
            de, da = self._delta.cat()
            keys = np.unique(pair_keys(de, da))
            bk = self.base_keys()
            if bk.size:
                pos = np.clip(np.searchsorted(bk, keys), 0, bk.size - 1)
                keys = keys[bk[pos] != keys]
            sel = np.isin((keys >> 31).astype(np.int64), dead_ids)
            if sel.any():
                out += np.bincount(
                    (keys[sel] & 0x7FFFFFFF).astype(np.int64), minlength=k)[:k]
        return out

    @property
    def nnz(self) -> int:
        """Stored (entity, attribute) pair count (post-dedupe where the
        backend dedupes) — Σ attr_counts, so reading it needs no store."""
        return int(np.sum(self.attr_counts()))

    def _pad_to_out(self, mask: jax.Array) -> jax.Array:
        """Extend a (n,)-row base result to the effective universe: entities
        past the sealed base (delta edges) hold no base attributes."""
        if self.out_n > int(mask.shape[0]):
            mask = jnp.concatenate(
                [mask, jnp.zeros((self.out_n - int(mask.shape[0]),), mask.dtype)])
        return mask

    def _query_base(self, values: Sequence[str], *, impl: Optional[str] = None) -> jax.Array:
        """(n,) bool over the sealed base only.  The query mask is built at
        ``_k_base`` — values interned after the seal are invisible here (the
        delta union answers them)."""
        if self.mesh is not None:
            sharded = self.finalize_sharded()
            mask = jnp.asarray(self.amap.mask(values, self._k_base))
            return dip_shard.query_any_sharded(
                self.backend, sharded, mask, impl=impl
            )
        store = self.finalize()
        mask = jnp.asarray(self.amap.mask(values, self._k_base))
        if self.backend == "arr":
            return dip_arr.query_any(store, mask, impl=impl or "matvec")
        if self.backend == "list":
            return dip_list.query_any(store, mask)
        if impl == "budget":
            ids = self.known_ids(values)
            ids = ids[ids < self._k_base]  # delta-only values have no chain
            if ids.size == 0:
                return jnp.zeros((self.n,), jnp.bool_)
            a_off = np.asarray(store.a_off)
            budget = int((a_off[ids + 1] - a_off[ids]).sum())
            budget = max(-(-budget // 128) * 128, 128)  # lane-aligned, ≥1 tile
            return dip_listd.query_any_budget(store, jnp.asarray(ids), budget=budget)
        return dip_listd.query_any(store, mask, impl=impl or "inverted")

    def query_any(self, values: Sequence[str], *, impl: Optional[str] = None) -> jax.Array:
        ids = self.known_ids(values) if len(values) else np.zeros(0, np.int32)
        if ids.size == 0:
            # degenerate query (empty list / all-unknown values): the answer
            # is definitionally empty — skip the store entirely
            return jnp.zeros((self.out_n,), jnp.bool_)
        out = self._pad_to_out(self._query_base(values, impl=impl))
        if self._delta.size:
            # LSM read union, composed BEFORE any propagation consumes it
            dmask = self._delta.mask(ids, self.out_n)
            if dmask.any():
                out = out | jnp.asarray(dmask)
        return out

    def query_any_batched(
        self, values_list: Sequence[Sequence[str]], *, impl: Optional[str] = None
    ) -> jax.Array:
        """(Q, out_n) bool — Q OR-queries in one shot.  On the ``arr`` backend
        all Q masks go through ONE matvec / Pallas-kernel launch (the
        planner's fusion path) and any delta rows OR in as a second stacked
        host mask; other backends fall back to a per-query loop."""
        if self.backend == "arr":
            if self.mesh is not None:
                sharded = self.finalize_sharded()
                masks = jnp.asarray(
                    np.stack([self.amap.mask(v, self._k_base) for v in values_list])
                )
                rows = dip_shard.query_any_batched_sharded(sharded, masks, impl=impl)
            else:
                store = self.finalize()
                masks = jnp.asarray(
                    np.stack([self.amap.mask(v, self._k_base) for v in values_list])
                )
                rows = dip_arr.query_any_batched(store, masks, impl=impl or "matvec")
            if self.out_n > int(rows.shape[1]):
                rows = jnp.concatenate(
                    [rows, jnp.zeros((rows.shape[0], self.out_n - int(rows.shape[1])),
                                     rows.dtype)], axis=1)
            if self._delta.size:
                drows = np.stack(
                    [self._delta.mask(self.known_ids(v), self.out_n)
                     for v in values_list])
                if drows.any():
                    rows = rows | jnp.asarray(drows)
            return rows
        return jnp.stack([self.query_any(v, impl=impl) for v in values_list])

    def _pad_words_to_out(self, words: jax.Array) -> jax.Array:
        """Word-space analog of ``_pad_to_out``: base tail bits past ``n``
        are zero by the build invariant, so extending to the effective
        universe is a zero-word concat — no bit surgery."""
        w_out = bitplane.n_words(self.out_n)
        if w_out > int(words.shape[-1]):
            pad_shape = words.shape[:-1] + (w_out - int(words.shape[-1]),)
            words = jnp.concatenate(
                [words, jnp.zeros(pad_shape, jnp.uint32)], axis=-1)
        return words[..., :w_out]

    def query_any_words(self, values: Sequence[str], *,
                        impl: Optional[str] = None) -> jax.Array:
        """Packed query: (ceil(out_n/32),) uint32 word mask — the executor's
        fused path keeps this packed through mask combination and unpacks
        once at the propagation boundary.  arr + packed base only."""
        assert self.packed, "query_any_words requires a packed arr store"
        ids = self.known_ids(values) if len(values) else np.zeros(0, np.int32)
        w_out = bitplane.n_words(self.out_n)
        if ids.size == 0:
            return jnp.zeros((w_out,), jnp.uint32)
        if self.mesh is not None:
            sharded = self.finalize_sharded()
            mask = jnp.asarray(self.amap.mask(values, self._k_base))
            out = dip_shard.query_any_words_sharded(sharded, mask, impl=impl)
        else:
            store = self.finalize()
            mask = jnp.asarray(self.amap.mask(values, self._k_base))
            if impl == "kernel":
                from repro.kernels.bitmap_query import ops as _ops

                out = _ops.bitmap_query_packed(store.bitmap, mask)
            else:
                out = dip_arr.query_any_words(store, mask)
        out = self._pad_words_to_out(out)
        if self._delta.size:
            dwords = self._delta.mask_words(ids, self.out_n)
            if dwords.any():
                out = out | jnp.asarray(dwords)
        return out

    def query_any_batched_words(
        self, values_list: Sequence[Sequence[str]], *,
        impl: Optional[str] = None
    ) -> jax.Array:
        """(Q, ceil(out_n/32)) uint32 — Q packed OR-queries, one launch."""
        assert self.packed, "query_any_batched_words requires a packed arr store"
        if self.mesh is not None:
            sharded = self.finalize_sharded()
            masks = jnp.asarray(
                np.stack([self.amap.mask(v, self._k_base) for v in values_list])
            )
            rows = dip_shard.query_any_batched_words_sharded(
                sharded, masks, impl=impl)
        else:
            store = self.finalize()
            masks = jnp.asarray(
                np.stack([self.amap.mask(v, self._k_base) for v in values_list])
            )
            if impl == "kernel":
                from repro.kernels.bitmap_query import ops as _ops

                rows = _ops.bitmap_query_batched_packed(store.bitmap, masks)
            else:
                rows = dip_arr.query_any_batched_words(store, masks)
        rows = self._pad_words_to_out(rows)
        if self._delta.size:
            drows = np.stack(
                [self._delta.mask_words(self.known_ids(v), self.out_n)
                 for v in values_list])
            if drows.any():
                rows = rows | jnp.asarray(drows)
        return rows

    def clone(self) -> "_AttrStore":
        """Structurally-shared copy for snapshots/views: the sealed base,
        stash, stats and pair CHUNKS are shared (all append-only or
        immutable); the chunk lists, delta chain and attribute map are
        private so parent and clone diverge without copying the base."""
        c = _AttrStore.__new__(_AttrStore)
        c.backend = self.backend
        c.n = self.n
        c.out_n = self.out_n
        c.mesh = self.mesh
        c.amap = AttributeMap(self.amap.values)
        c._pairs_e = list(self._pairs_e)
        c._pairs_a = list(self._pairs_a)
        c._store = self._store
        c._sharded = self._sharded
        c._host = self._host
        c._counts = self._counts
        c._dirty = self._dirty
        c._delta = self._delta.frozen_copy()
        c._k_base = self._k_base
        c._base_keys = self._base_keys
        return c


class PropGraph:
    """A static, directed, labeled property multigraph over the DI structure.

    ``mesh=None`` (default) runs single-device, exactly as before.  Passing a
    device mesh (e.g. ``launch.mesh.make_entity_mesh()``) distributes the
    entity axis of the DIP stores over its devices (DI arrays and property
    columns shard when divisible, replicate otherwise) — queries return the
    same masks, computed shard-locally (docs/ARCHITECTURE.md §7).
    """

    def __init__(self, backend: str = "arr", mesh=None):
        self.backend = backend
        self.mesh = mesh
        self.graph: Optional[DIGraph] = None
        self._vstore: Optional[_AttrStore] = None
        self._estore: Optional[_AttrStore] = None
        # typed property columns: name -> (values (x,), valid mask (x,))
        self.vertex_props: Dict[str, Tuple[jax.Array, jax.Array]] = {}
        self.edge_props: Dict[str, Tuple[jax.Array, jax.Array]] = {}
        # monotone mutation counter + observers — the service layer's cache
        # invalidation contract.  ``last_mutation`` carries the matching
        # MutationEvent so observers can invalidate by OVERLAP (a cached
        # result survives writes that cannot touch its masks) instead of
        # purging everything on every version bump (docs/ARCHITECTURE.md §11).
        self.version: int = 0
        self.last_mutation: Optional[MutationEvent] = None
        self._mutation_hooks: List = []
        # ---- overlay state (docs/ARCHITECTURE.md §11) -------------------
        self._delta_edges: Optional[EdgeDelta] = None  # structural inserts
        self._dead_v: Optional[np.ndarray] = None  # (n,) bool tombstones
        self._dead_e: Optional[np.ndarray] = None  # sorted global edge ids
        self._eff_cache: Optional[Tuple[int, DIGraph]] = None
        self._frozen = False  # snapshots refuse mutation
        # serializes mutators + compact() (see _write_locked); re-entrant,
        # never taken by the read paths
        self._write_lock = threading.RLock()

    # ----------------------------------------------------------- mutation API
    def on_mutation(self, hook) -> "PropGraph":
        """Register ``hook(pg)`` to run after every mutating call (structure
        or attributes).  Hooks fire AFTER ``version`` is bumped, so a hook
        reading ``pg.version`` sees the post-mutation value."""
        self._mutation_hooks.append(hook)
        return self

    def _bump_version(self) -> None:
        self.version += 1
        for hook in list(self._mutation_hooks):
            hook(self)

    def _check_writable(self) -> None:
        if self._frozen:
            raise RuntimeError(
                "this PropGraph is a frozen snapshot; fork() it for a "
                "writable view")

    # ------------------------------------------------------------- structure
    @_write_locked
    def add_edges_from(self, src, dst) -> "PropGraph":
        """Bulk edge ingestion → DI build (sort + normalize + SEG).

        Rebuilding the structure drops all previously attached attributes
        (fresh stores) AND the whole overlay — and, like every mutator,
        bumps ``version``.  For incremental structural growth that keeps
        attributes and costs O(batch), use ``insert_edges``."""
        self._check_writable()
        src = np.asarray(src)
        if src.size == 0 and self.graph is not None:
            return self  # no-op: nothing to rebuild from, keep caches live
        self.graph = build_di(src, np.asarray(dst))
        if self.mesh is not None:
            self.graph = dip_shard.place_graph(self.graph, self.mesh)
        self._vstore = _AttrStore(self.backend, self.graph.n, mesh=self.mesh)
        self._estore = _AttrStore(self.backend, max(self.graph.m, 1), mesh=self.mesh)
        self._delta_edges = None
        self._dead_v = None
        self._dead_e = None
        self._eff_cache = None
        self.last_mutation = MutationEvent.structural_event("add_edges_from")
        self._bump_version()
        return self

    @_write_locked
    def insert_edges(self, src, dst) -> "PropGraph":
        """O(batch) structural ingestion: append (src, dst) pairs to the edge
        delta instead of re-sorting the whole DI structure.  Endpoints must
        already exist in the vertex universe (growing it means a new
        normalization — that is ``add_edges_from``'s bulk path).  Delta
        edges get global ids ``m_base + i``; queries and analytics see them
        through the combined edge view until ``compact()`` folds them in.
        Pairs already present ALIVE (base or delta) are dropped, matching
        the DI one-structural-edge-per-(u,v) invariant.

        Tombstones behave exactly as they do after ``compact()`` made them
        physical (compaction stays transparent): a pair whose only
        occurrence is tombstoned (``delete_edges``) is re-inserted as a
        fresh BARE delta edge — the dead edge's relationships and property
        values do not carry over, just as a post-compaction re-insert
        starts clean; an endpoint tombstoned by ``delete_vertices`` raises
        ``ValueError``, just as the vertex is unknown post-compaction."""
        self._check_writable()
        if self.graph is None:
            return self.add_edges_from(src, dst)
        src = np.asarray(src).ravel()
        dst = np.asarray(dst).ravel()
        if src.size == 0:
            return self  # no-op
        u = self._vertex_internal(src)
        v = self._vertex_internal(dst)
        if (u < 0).any() or (v < 0).any():
            unknown = np.unique(np.concatenate([src[u < 0], dst[v < 0]]))
            raise ValueError(
                f"insert_edges endpoints must already exist; unknown vertices "
                f"{unknown[:10].tolist()} — use add_edges_from (bulk rebuild) "
                f"to grow the vertex universe")
        if self._dead_v is not None:
            du, dv = self._dead_v[u], self._dead_v[v]
            if du.any() or dv.any():
                gone = np.unique(np.concatenate([src[du], dst[dv]]))
                raise ValueError(
                    f"insert_edges endpoints {gone[:10].tolist()} are "
                    f"tombstoned (delete_vertices) — a deleted vertex is "
                    f"gone before and after compaction; re-add it via "
                    f"add_edges_from (bulk rebuild)")
        if self._delta_edges is None:
            self._delta_edges = EdgeDelta(self.graph.m)
        base_idx = np.asarray(edge_lookup(self.graph, jnp.asarray(u), jnp.asarray(v)))
        alive_in_base = base_idx >= 0
        if self._dead_e is not None and self._dead_e.size:
            # a tombstoned base pair no longer exists — it is insertable
            alive_in_base &= ~np.isin(base_idx, self._dead_e)
        fresh = ~alive_in_base
        added = (self._delta_edges.append(u[fresh], v[fresh], dead=self._dead_e)
                 if fresh.any() else 0)
        if added == 0:
            return self  # every pair already present: caches stay live
        self._estore.out_n = max(self.graph.m + self._delta_edges.size, 1)
        self._eff_cache = None
        self.last_mutation = MutationEvent.structural_event("insert_edges")
        self._bump_version()
        return self

    @_write_locked
    def delete_vertices(self, nodes) -> "PropGraph":
        """Tombstone vertices (and implicitly every incident edge) in the
        overlay — the base structure is untouched, so snapshots taken before
        the delete still see the vertices.  ``compact()`` makes it physical."""
        self._check_writable()
        self._require_graph()
        idx = self._vertex_internal(np.asarray(nodes).ravel())
        idx = idx[idx >= 0]
        if idx.size == 0:
            return self  # no-op
        dead = (np.zeros(self.graph.n, bool) if self._dead_v is None
                else self._dead_v.copy())  # copy-on-write: snapshots share ours
        before = int(dead.sum())
        dead[idx] = True
        if int(dead.sum()) == before:
            return self  # all already dead
        self._dead_v = dead
        self._eff_cache = None
        self.last_mutation = MutationEvent.structural_event("delete_vertices")
        self._bump_version()
        return self

    @_write_locked
    def delete_edges(self, src, dst) -> "PropGraph":
        """Tombstone individual edges (base or delta) by endpoint pair."""
        self._check_writable()
        self._require_graph()
        idx = self._edge_internal(src, dst)
        idx = idx[idx >= 0].astype(np.int32)
        if idx.size == 0:
            return self  # no-op
        cur = self._dead_e if self._dead_e is not None else np.zeros(0, np.int32)
        merged = np.unique(np.concatenate([cur, idx]))
        if merged.size == cur.size:
            return self  # all already dead
        self._dead_e = merged
        self._eff_cache = None
        self.last_mutation = MutationEvent.structural_event("delete_edges")
        self._bump_version()
        return self

    def _effective_graph(self) -> DIGraph:
        """Base DI structure ++ delta edges, as one edge-centric view.

        The combined graph keeps the base's SEG (valid for the sorted base
        prefix only) and is flagged ``unsorted`` so SEG-dependent fast paths
        route around it; everything the executor and frontier engine run is
        edge-centric and consumes it unchanged.  Cached per delta size —
        repeated queries between writes pay the concat once."""
        base = self.graph
        de = self._delta_edges
        if de is None or de.size == 0:
            return base
        if self._eff_cache is not None and self._eff_cache[0] == de.size:
            return self._eff_cache[1]
        ds, dd = de.cat()
        g = DIGraph(
            src=jnp.concatenate([base.src, jnp.asarray(ds)]),
            dst=jnp.concatenate([base.dst, jnp.asarray(dd)]),
            seg=base.seg, node_map=base.node_map,
            n=base.n, m=base.m + de.size, max_deg=-1, unsorted=True)
        self._eff_cache = (de.size, g)
        return g

    def _require_graph(self) -> DIGraph:
        if self.graph is None:
            raise RuntimeError("call add_edges_from(...) first")
        return self._effective_graph()

    def _vertex_internal(self, nodes) -> np.ndarray:
        """Original vertex ids → internal [0, n) ids (−1 if absent)."""
        g = self._require_graph()
        nm = np.asarray(g.node_map)
        nodes = np.asarray(nodes).ravel()
        pos = np.searchsorted(nm, nodes)
        pos = np.clip(pos, 0, len(nm) - 1)
        ok = nm[pos] == nodes
        return np.where(ok, pos, -1).astype(np.int32)

    def _edge_internal(self, src, dst) -> np.ndarray:
        self._require_graph()
        g = self.graph  # edge_lookup needs the SORTED base (SEG windows)
        u = self._vertex_internal(src)
        v = self._vertex_internal(dst)
        u_c = jnp.asarray(np.maximum(u, 0))
        v_c = jnp.asarray(np.maximum(v, 0))
        idx = np.asarray(edge_lookup(g, u_c, v_c))
        idx = np.where((u >= 0) & (v >= 0), idx, -1).astype(np.int32)
        if self._delta_edges is not None and self._delta_edges.size:
            miss = idx < 0
            if miss.any():
                # base misses may still be delta edges (global ids ≥ m_base)
                didx = self._delta_edges.lookup(u[miss], v[miss])
                idx[miss] = np.where((u[miss] >= 0) & (v[miss] >= 0), didx, -1)
        if self._dead_e is not None and self._dead_e.size:
            # a tombstoned edge no longer exists at (u, v): resolve to the
            # revived delta edge (insert_edges after delete_edges) if one
            # exists, else -1 — so attribute/property writes and deletes
            # address exactly what a post-compaction graph would hold
            dead_hit = np.isin(idx, self._dead_e)
            if dead_hit.any():
                if self._delta_edges is not None and self._delta_edges.size:
                    rep = self._delta_edges.lookup(u[dead_hit], v[dead_hit])
                    rep = np.where(np.isin(rep, self._dead_e), -1, rep)
                else:
                    rep = np.full(int(dead_hit.sum()), -1, np.int32)
                idx[dead_hit] = rep
        return idx

    # ------------------------------------------------------------ attributes
    @_write_locked
    def add_node_labels(self, nodes, labels) -> "PropGraph":
        self._check_writable()
        self._require_graph()
        if np.asarray(nodes).size == 0:
            return self  # no-op: nothing changes, caches stay live
        self._vstore.insert(self._vertex_internal(nodes), labels)
        self.last_mutation = MutationEvent.labels_event(labels)
        self._bump_version()
        return self

    @_write_locked
    def add_edge_relationships(self, src, dst, relationships) -> "PropGraph":
        self._check_writable()
        self._require_graph()
        if np.asarray(src).size == 0:
            return self  # no-op
        self._estore.insert(self._edge_internal(src, dst), relationships)
        self.last_mutation = MutationEvent.rels_event(relationships)
        self._bump_version()
        return self

    @_write_locked
    def add_node_properties(self, name: str, nodes, values, fill=0) -> "PropGraph":
        self._check_writable()
        g = self._require_graph()
        if np.asarray(nodes).size == 0:
            return self  # no-op
        idx = self._vertex_internal(nodes)
        vals = np.asarray(values)
        col = np.full((g.n,), fill, dtype=vals.dtype)
        valid = np.zeros((g.n,), dtype=bool)
        ok = idx >= 0
        col[idx[ok]] = vals[ok]
        valid[idx[ok]] = True
        self.vertex_props[name] = self._place_column(col, valid)
        self.last_mutation = MutationEvent.props_event(name)
        self._bump_version()
        return self

    @_write_locked
    def add_edge_properties(self, name: str, src, dst, values, fill=0) -> "PropGraph":
        self._check_writable()
        g = self._require_graph()
        if np.asarray(src).size == 0:
            return self  # no-op
        idx = self._edge_internal(src, dst)
        vals = np.asarray(values)
        col = np.full((g.m,), fill, dtype=vals.dtype)
        valid = np.zeros((g.m,), dtype=bool)
        ok = idx >= 0
        col[idx[ok]] = vals[ok]
        valid[idx[ok]] = True
        self.edge_props[name] = self._place_column(col, valid)
        self.last_mutation = MutationEvent.props_event(name)
        self._bump_version()
        return self

    @_write_locked
    def update_node_properties(self, name: str, nodes, values) -> "PropGraph":
        """Point-update an EXISTING typed column: functional scatter onto a
        fresh array, so snapshots holding the previous column are untouched.
        Unknown vertices are dropped; an unknown property is an error
        (``add_node_properties`` defines columns)."""
        self._check_writable()
        self._require_graph()
        if name not in self.vertex_props:
            raise KeyError(
                f"unknown vertex property {name!r}; add_node_properties first")
        idx = self._vertex_internal(np.asarray(nodes).ravel())
        vals = np.asarray(values).ravel()
        ok = idx >= 0
        if not ok.any():
            return self  # no-op
        col, valid = self.vertex_props[name]
        at = jnp.asarray(idx[ok])
        self.vertex_props[name] = (
            col.at[at].set(jnp.asarray(vals[ok]).astype(col.dtype)),
            valid.at[at].set(True))
        self.last_mutation = MutationEvent.props_event(name)
        self._bump_version()
        return self

    @_write_locked
    def update_edge_properties(self, name: str, src, dst, values) -> "PropGraph":
        """Point-update an existing edge column; delta edges are addressable
        too (the column pads to the effective edge count on first touch)."""
        self._check_writable()
        g = self._require_graph()
        if name not in self.edge_props:
            raise KeyError(
                f"unknown edge property {name!r}; add_edge_properties first")
        idx = self._edge_internal(src, dst)
        vals = np.asarray(values).ravel()
        ok = idx >= 0
        if not ok.any():
            return self  # no-op
        col, valid = self.edge_props[name]
        if int(col.shape[0]) < g.m:
            pad = g.m - int(col.shape[0])
            col = jnp.concatenate([col, jnp.zeros((pad,), col.dtype)])
            valid = jnp.concatenate([valid, jnp.zeros((pad,), jnp.bool_)])
        at = jnp.asarray(idx[ok])
        self.edge_props[name] = (
            col.at[at].set(jnp.asarray(vals[ok]).astype(col.dtype)),
            valid.at[at].set(True))
        self.last_mutation = MutationEvent.props_event(name)
        self._bump_version()
        return self

    def _place_column(self, col, valid) -> Tuple[jax.Array, jax.Array]:
        col, valid = jnp.asarray(col), jnp.asarray(valid)
        if self.mesh is not None:
            col = dip_shard.place_column(col, self.mesh)
            valid = dip_shard.place_column(valid, self.mesh)
        return col, valid

    # ---------------------------------------------------------- alive masks
    def _alive_vertex_mask(self) -> Optional[jax.Array]:
        """(n,) bool (False = tombstoned) or None when nothing is deleted."""
        if self._dead_v is None:
            return None
        return jnp.asarray(~self._dead_v)

    def _alive_edge_mask(self) -> Optional[jax.Array]:
        """(m_eff,) bool or None — False on tombstoned edges and on edges
        with a deleted endpoint (deleting a vertex detaches it)."""
        if self._dead_e is None and self._dead_v is None:
            return None
        g = self._require_graph()
        alive = np.ones(g.m, dtype=bool)
        if self._dead_e is not None and self._dead_e.size:
            alive[self._dead_e] = False
        mask = jnp.asarray(alive)
        av = self._alive_vertex_mask()
        if av is not None:
            mask = mask & av[g.src] & av[g.dst]
        return mask

    def _dead_vertex_ids(self) -> Optional[np.ndarray]:
        """Tombstoned internal vertex ids, or None when nothing is dead —
        the subtraction set for tombstone-exact attribute stats."""
        if self._dead_v is None:
            return None
        ids = np.flatnonzero(self._dead_v)
        return ids if ids.size else None

    def _dead_edge_ids(self) -> Optional[np.ndarray]:
        """Global ids of edges the alive mask excludes (tombstoned edges
        plus edges detached by a dead endpoint) — same universe as
        ``_alive_edge_mask``, as ids instead of a mask."""
        ae = self._alive_edge_mask()
        if ae is None:
            return None
        ids = np.flatnonzero(~np.asarray(ae))
        return ids if ids.size else None

    # --------------------------------------------------------------- queries
    def query_labels(self, labels, *, impl: Optional[str] = None) -> jax.Array:
        """(n,) bool — vertices holding ANY of ``labels`` (§VI OR semantics).
        Overlay-aware: delta-held labels OR in, tombstoned vertices AND out."""
        self._require_graph()
        out = self._vstore.query_any(labels, impl=impl)
        av = self._alive_vertex_mask()
        return out if av is None else out & av

    def query_relationships(self, relationships, *, impl: Optional[str] = None) -> jax.Array:
        """(m,) bool — edges holding ANY of ``relationships`` (effective
        edge universe: base ++ delta, minus tombstones)."""
        self._require_graph()
        out = self._estore.query_any(relationships, impl=impl)
        ae = self._alive_edge_mask()
        if ae is not None and int(ae.shape[0]) == int(out.shape[0]):
            out = out & ae
        return out

    # ------------------------------------------------- typed property masks
    _PRED_OPS = {
        "==": operator.eq,
        "!=": operator.ne,
        "<": operator.lt,
        "<=": operator.le,
        ">": operator.gt,
        ">=": operator.ge,
    }

    def _predicate_mask(
        self, cols: Dict[str, Tuple[jax.Array, jax.Array]], kind: str,
        name: str, op: str, value,
    ) -> jax.Array:
        if name not in cols:
            raise KeyError(
                f"unknown {kind} property {name!r}; known: {sorted(cols)}"
            )
        if op not in self._PRED_OPS:
            raise ValueError(f"unknown predicate op {op!r}; known: {sorted(self._PRED_OPS)}")
        if isinstance(value, str):
            # property columns are numeric typed columns; a str here would
            # silently broadcast to a scalar True/False under ==/!= instead
            # of comparing — string-valued attributes belong in labels/
            # relationships (the DIP stores), not predicates
            raise TypeError(
                f"{kind} predicate {name!r} {op} {value!r}: string comparisons "
                "are not supported on typed property columns — model "
                "string-valued attributes as labels/relationships instead"
            )
        col, valid = cols[name]
        return valid & self._PRED_OPS[op](col, value)

    def _predicate_parts(
        self, kind: str, name: str, op: str, value
    ) -> Tuple[jax.Array, jax.Array]:
        """Host-side half of a predicate: validate (same KeyError /
        ValueError / TypeError contracts as ``_predicate_mask``) and return
        the raw ``(col, valid)`` column pair — the executor's fused packed
        combine evaluates ``valid & op(col, value)`` INSIDE its single
        jitted launch instead of through a separate mask op.  Edge columns
        shorter than the effective universe are handled by the combine
        (missing rows are invalid ⇒ False), not padded here."""
        cols = self.vertex_props if kind == "node" else self.edge_props
        ckind = "vertex" if kind == "node" else "edge"
        if name not in cols:
            raise KeyError(
                f"unknown {ckind} property {name!r}; known: {sorted(cols)}"
            )
        if op not in self._PRED_OPS:
            raise ValueError(f"unknown predicate op {op!r}; known: {sorted(self._PRED_OPS)}")
        if isinstance(value, str):
            raise TypeError(
                f"{ckind} predicate {name!r} {op} {value!r}: string comparisons "
                "are not supported on typed property columns — model "
                "string-valued attributes as labels/relationships instead"
            )
        return cols[name]

    def vertex_predicate_mask(self, name: str, op: str, value) -> jax.Array:
        """(n,) bool — vertices whose typed property ``name`` compares true
        (entities without the property never match: the valid mask ANDs in;
        tombstoned vertices never match either)."""
        self._require_graph()
        out = self._predicate_mask(self.vertex_props, "vertex", name, op, value)
        av = self._alive_vertex_mask()
        return out if av is None else out & av

    def edge_predicate_mask(self, name: str, op: str, value) -> jax.Array:
        """(m_eff,) bool — edges whose typed property ``name`` compares true.
        Columns predating the current delta edges pad with False (a delta
        edge has no value until ``update_edge_properties`` touches it)."""
        g = self._require_graph()
        out = self._predicate_mask(self.edge_props, "edge", name, op, value)
        if int(out.shape[0]) < g.m:
            out = jnp.concatenate(
                [out, jnp.zeros((g.m - int(out.shape[0]),), jnp.bool_)])
        ae = self._alive_edge_mask()
        if ae is not None and int(ae.shape[0]) == int(out.shape[0]):
            out = out & ae
        return out

    # ------------------------------------------------------ pattern matching
    def match(self, pattern, *, impl: Optional[str] = None,
              profile: bool = False):
        """Declarative pattern query: ``pg.match("(a:person {age > 30})-[:follows]->(b:person)")``.

        Parses ``pattern`` (str or a pre-built ``repro.query.Pattern``),
        plans it against this graph's DIP statistics and executes the fused
        mask pipeline.  Returns a ``repro.query.MatchResult`` whose
        ``vertex_mask``/``edge_mask`` cover exactly the entities in at least
        one full match.  ``impl`` force-overrides the planner's per-mask
        implementation choice.

        ``profile=True`` returns ``(MatchResult, ProfileReport)`` instead —
        the EXPLAIN ANALYZE path (docs/ARCHITECTURE.md §13): per-stage wall
        times with the JAX compile-vs-execute split measured by a steady-
        state re-run, so it costs roughly one extra warm execution.
        """
        if profile:
            from repro.obs.profile import profile_match

            return profile_match(self, pattern, impl=impl)
        from repro.query import execute_plan, parse, plan_pattern

        pat = parse(pattern) if isinstance(pattern, str) else pattern
        return execute_plan(self, plan_pattern(self, pat, impl=impl))

    def explain(self, pattern, *, impl: Optional[str] = None) -> str:
        """The plan ``match`` would run, as a human-readable string — which
        DIP impl each mask uses, selectivity estimates, chain orientation,
        and kernel-fusion decisions."""
        from repro.query import parse, plan_pattern

        pat = parse(pattern) if isinstance(pattern, str) else pattern
        return plan_pattern(self, pat, impl=impl).describe()

    def explain_analyze(self, pattern, *, impl: Optional[str] = None):
        """EXPLAIN ANALYZE: run ``pattern`` and return a ``ProfileReport``
        — the executed plan annotated with measured per-stage times
        (parse / plan / mask materialization / propagation) and the
        first-call XLA compilation separated from device execution
        (``report.compile_ms`` / ``report.cold``).  ``report.describe()``
        renders the plan with the timing table appended."""
        from repro.obs.profile import profile_match

        return profile_match(self, pattern, impl=impl)[1]

    def subgraph(
        self,
        labels: Optional[Sequence[str]] = None,
        relationships: Optional[Sequence[str]] = None,
        *,
        impl: Optional[str] = None,
    ) -> Tuple[DIGraph, np.ndarray]:
        """Intersect label/relationship query masks into an induced subgraph."""
        g = self._require_graph()
        vmask = (
            self.query_labels(labels, impl=impl)
            if labels is not None
            else jnp.ones((g.n,), jnp.bool_)
        )
        emask = (
            self.query_relationships(relationships, impl=impl)
            if relationships is not None
            else jnp.ones((g.m,), jnp.bool_)
        )
        av = self._alive_vertex_mask()
        if av is not None:
            vmask = vmask & av
        ae = self._alive_edge_mask()
        if ae is not None and int(ae.shape[0]) == int(emask.shape[0]):
            emask = emask & ae
        return extract_subgraph(g, induce_edge_mask(g, vmask, emask))

    def bfs(
        self,
        sources,
        labels: Optional[Sequence[str]] = None,
        relationships: Optional[Sequence[str]] = None,
        max_iters: int = 64,
    ) -> jax.Array:
        """Property-filtered BFS from original-id sources; (n,) depths."""
        g = self._require_graph()
        v_ok = self.query_labels(labels) if labels is not None else None
        e_ok = self.query_relationships(relationships) if relationships is not None else None
        av = self._alive_vertex_mask()
        if av is not None:
            v_ok = av if v_ok is None else v_ok & av
        ae = self._alive_edge_mask()
        if ae is not None:
            e_ok = ae if e_ok is None else e_ok & ae
        srcs = jnp.asarray(np.maximum(self._vertex_internal(sources), 0))
        return filtered_bfs(g, srcs, edge_allowed=e_ok, vertex_allowed=v_ok, max_iters=max_iters)

    # -------------------------------------------------- frontier analytics
    def khop(
        self,
        seeds,
        k: int,
        *,
        pattern=None,
        undirected: bool = False,
        impl: Optional[str] = None,
    ) -> jax.Array:
        """Vertices within ≤``k`` hops of ``seeds`` (original ids), following
        only edges the filter ``pattern`` allows — (n,) bool, seeds included.

        ``pattern`` is a node-only or single-hop filter (the same §VI masks
        ``match`` composes): for ``"(a:host)-[:flows {bytes > 0}]->(b)"``
        an edge is traversable iff it holds ``flows``, satisfies the
        predicate, its tail matches ``a`` and its head matches ``b``;
        ``<-[...]-`` walks edges in reverse; a node-only pattern confines
        the traversal to matching vertices.  ``None`` allows everything.

        ``impl``: ``None``/``"frontier"`` = the edge-centric bitmap step
        (one jitted ``while_loop``; the shard_map all-reduce path under a
        mesh); ``"csr"`` = the small-frontier CSR gather fast path —
        O(|frontier|·max_deg) per step instead of O(m) (single-device,
        forward, directed only; degrades to ``frontier`` otherwise, like
        the listd ``budget`` impl under a mesh).  All paths are
        bitwise-identical.
        """
        from repro import traverse

        g = self._require_graph()
        if impl not in (None, "frontier", "csr"):
            raise ValueError(f"unknown impl {impl!r}")
        _obs_traverse("khop", int(k), int(np.asarray(seeds).size))
        v_tail, v_head, e_mask, direction = traverse.single_hop_filters(
            self, pattern)
        e_ok = jnp.ones((g.m,), jnp.bool_) if e_mask is None else e_mask
        tail, head = (g.src, g.dst) if direction == 1 else (g.dst, g.src)
        if v_tail is not None:
            e_ok = e_ok & v_tail[tail]
        if v_head is not None:
            e_ok = e_ok & v_head[head]
        ae = self._alive_edge_mask()
        if ae is not None:
            e_ok = e_ok & ae  # overlay tombstones compose pre-propagation
        ids = self._vertex_internal(seeds)
        ids = ids[ids >= 0]
        if self._dead_v is not None and ids.size:
            ids = ids[~self._dead_v[ids]]  # dead seeds don't traverse
        if (impl == "csr" and self.mesh is None and direction == 1
                and not undirected and not g.unsorted):
            # the CSR gather fast path needs valid SEG windows — a combined
            # base++delta view has none, so it degrades to the frontier step
            return traverse.khop_csr(g, ids, e_ok, k=k)
        seed_mask = jnp.zeros((g.n,), jnp.bool_).at[jnp.asarray(ids)].set(True)
        if self.mesh is not None:
            return traverse.khop_mask_sharded(
                g, seed_mask, e_ok, k=k, mesh=self.mesh,
                direction=direction, undirected=undirected)
        return traverse.khop_mask(g, seed_mask, e_ok, k=k,
                                  direction=direction, undirected=undirected)

    # -------------------------------------------------- fused sampling (§15)
    def _sampling_view(self):
        """(seg, dst, max_deg, perm) windows for the CURRENT effective
        graph.  A sorted base graph is its own view (perm None); an overlay
        combined view (``unsorted``) has no valid SEG, so the host lexsorts
        the combined endpoints ONCE per version into a sampleable CSR —
        ``perm[j]`` is the global edge id at sorted position j, the gather
        that routes per-edge filters into window space.  Cached per
        version: QPS traffic between writes pays the sort once."""
        g = self._require_graph()
        if not g.unsorted:
            return g.seg, g.dst, int(g.max_deg), None
        cache = getattr(self, "_sample_view_cache", None)
        if cache is not None and cache[0] == self.version:
            return cache[1]
        src_np = np.asarray(g.src)
        order = np.argsort(src_np, kind="stable").astype(np.int32)
        seg = np.searchsorted(src_np[order], np.arange(g.n + 1)).astype(np.int32)
        md = int((seg[1:] - seg[:-1]).max(initial=0))
        view = (jnp.asarray(seg), jnp.asarray(np.asarray(g.dst)[order]), md,
                jnp.asarray(order))
        self._sample_view_cache = (self.version, view)
        return view

    def _sample_edge_words(self, pattern, perm) -> Optional[jax.Array]:
        """Packed (uint32-word) edge-allowed bitmap for sampling under the
        khop-style single-hop filter ``pattern``: an edge is sampleable iff
        it holds the relationship, satisfies the predicates, its tail
        matches the ``a`` constraint, its head matches ``b``, AND it is
        alive in the overlay (tombstoned edges and edges of deleted
        vertices never appear).  ``perm`` routes the mask into an overlay
        view's window order.  None = every live edge.  Cached per
        (version, canonical pattern) so a served pattern packs once."""
        from repro import traverse

        key = (self.version, None if pattern is None else str(pattern),
               perm is not None)
        cache = getattr(self, "_sample_filter_cache", None)
        if cache is not None and cache[0] == key:
            return cache[1]
        g = self._require_graph()
        v_tail, v_head, e_mask, direction = traverse.single_hop_filters(
            self, pattern)
        if direction != 1:
            raise ValueError(
                "sampling follows out-edges; reverse-direction filter "
                "patterns (<-[...]-) are not supported")
        e_ok = e_mask
        if v_tail is not None or v_head is not None:
            e_ok = jnp.ones((g.m,), jnp.bool_) if e_ok is None else e_ok
            if v_tail is not None:
                e_ok = e_ok & v_tail[g.src]
            if v_head is not None:
                e_ok = e_ok & v_head[g.dst]
        ae = self._alive_edge_mask()
        if ae is not None:
            e_ok = ae if e_ok is None else e_ok & ae
        if e_ok is None:
            words = None
        else:
            if perm is not None:
                e_ok = jnp.take(e_ok, perm)
            words = bitplane.pack_mask(e_ok)
        self._sample_filter_cache = (key, words)
        return words

    def _sample_rest(self, frontier, nbrs0, mask0, fanouts, key_or_seed,
                     seg, dstv, max_deg, ew_words):
        """Layers 1..L of the layered loop + block assembly, shared by the
        in-process path and the service's coalesced layer-0 launch (which
        must finish each request identically to a solo run).  Layer l keys
        are ``fold_in(base, l)`` — independent per layer; ``key_or_seed``
        may be the base key array or the plain int seed (then the key is
        derived in one jitted dispatch, bitwise the eager form)."""
        from repro.graph.sampler import layer_key, local_block
        from repro.kernels.neighbor_sample import neighbor_sample

        g = self._require_graph()
        layer_frontiers = [frontier]
        layer_samples = [(frontier, nbrs0, mask0)]
        nxt = np.unique(np.concatenate([frontier, nbrs0[mask0]])).astype(
            np.int32)
        layer_frontiers.append(nxt)
        for li in range(1, len(fanouts)):
            cur = layer_frontiers[-1]
            kl = (layer_key(key_or_seed, li)
                  if isinstance(key_or_seed, (int, np.integer))
                  else jax.random.fold_in(key_or_seed, li))
            nb, _ei, mk = neighbor_sample(
                seg, dstv, g.n, g.m, cur, kl, fanout=fanouts[li],
                edge_words=ew_words, max_deg=max_deg)
            nb = np.asarray(nb)[:len(cur)]
            mk = np.asarray(mk)[:len(cur)]
            layer_samples.append((cur, nb, mk))
            layer_frontiers.append(
                np.unique(np.concatenate([cur, nb[mk]])).astype(np.int32))
        blocks = []
        for li in range(len(fanouts) - 1, -1, -1):
            dst_nodes, nb, mk = layer_samples[li]
            blocks.append(
                local_block(dst_nodes, layer_frontiers[li + 1], nb, mk))
        return blocks

    def sample(self, seeds_or_pattern, fanouts, *, key=None, seed: int = 0,
               pattern=None):
        """Fused property-filtered neighborhood sampling — the one-launch
        pattern→sample path (docs/ARCHITECTURE.md §15).

        ``seeds_or_pattern``: original vertex ids, or a Cypher-lite pattern
        string — then the seeds are the vertices the pattern's FIRST node
        variable binds, and the packed ``match`` combine's uint32 bitmap
        feeds the window gather directly (no host unpack; the host reads
        one popcount scalar to pick the capacity bucket).  ``fanouts``:
        per-layer caps, innermost first (GraphSAGE order).  ``pattern``:
        an optional khop-style single-hop filter constraining which edges
        may be sampled at EVERY layer (relationship, predicates, endpoint
        labels); overlay tombstones are always excluded.  ``key``/``seed``:
        the base PRNG key — results are bitwise-reproducible given it
        (layer l draws from ``fold_in(key, l)`` only).

        Returns ``SampledBlock``s innermost-first (``blocks[-1].dst_nodes``
        = the seed batch); node ids are INTERNAL [0, n) ids — index device
        property columns/embedding tables directly, or map back through
        ``graph.node_map``.  Selection is uniform without replacement over
        each seed's filtered adjacency: degree-0 seeds emit fully-masked
        slots, filtered degree ≤ fanout keeps every allowed edge once.
        Unknown and tombstoned seed ids drop out (the ``khop`` rule).
        """
        from repro.kernels.neighbor_sample import (
            neighbor_sample,
            neighbor_sample_from_words,
        )

        g = self._require_graph()
        fanouts = [int(f) for f in fanouts]
        if not fanouts or min(fanouts) < 1:
            raise ValueError(f"fanouts must be ≥1 per layer, got {fanouts}")
        from repro.graph.sampler import layer_key

        seg, dstv, max_deg, perm = self._sampling_view()
        ew_words = self._sample_edge_words(pattern, perm)
        key_or_seed = int(seed) if key is None else key
        k0 = (layer_key(key_or_seed, 0) if key is None
              else jax.random.fold_in(key, 0))
        if isinstance(seeds_or_pattern, str) or hasattr(seeds_or_pattern,
                                                        "nodes"):
            res = self.match(seeds_or_pattern)
            seed_mask = (res.node_masks[0] if res.node_masks
                         else res.vertex_mask)
            words = bitplane.pack_mask(seed_mask)
            count = int(jnp.sum(seed_mask))  # the one host scalar read
            idx, valid, nb, _ei, mk = neighbor_sample_from_words(
                seg, dstv, g.n, g.m, words, count, k0,
                fanout=fanouts[0], edge_words=ew_words, max_deg=max_deg)
            keep = np.asarray(valid)
            frontier = np.asarray(idx)[keep].astype(np.int32)
            nbrs0, mask0 = np.asarray(nb)[keep], np.asarray(mk)[keep]
        else:
            ids = self._vertex_internal(seeds_or_pattern)
            ids = ids[ids >= 0]
            if self._dead_v is not None and ids.size:
                ids = ids[~self._dead_v[ids]]
            nb, _ei, mk = neighbor_sample(
                seg, dstv, g.n, g.m, ids, k0, fanout=fanouts[0],
                edge_words=ew_words, max_deg=max_deg)
            frontier = ids.astype(np.int32)
            nbrs0 = np.asarray(nb)[:len(ids)]
            mask0 = np.asarray(mk)[:len(ids)]
        return self._sample_rest(frontier, nbrs0, mask0, fanouts, key_or_seed,
                                 seg, dstv, max_deg, ew_words)

    def components(self, pattern=None, *, max_iters: int = 128) -> jax.Array:
        """Connected components of the subgraph the filter ``pattern``
        allows — (n,) int32 labels (component id = smallest member vertex
        id, internal numbering), -1 for vertices outside the filter.

        Edges count as undirected; an edge participates iff it satisfies
        the pattern's relationship/predicate masks AND both endpoints
        match their node constraints (``pg.components(
        "(a:person)-[:follows]->(b:person)")`` = components of the
        follows-subgraph between persons).  Vertices matching either
        endpoint constraint participate (isolated ones form singletons).
        ``None`` = plain structural components.
        """
        from repro import traverse

        g = self._require_graph()
        _obs_traverse("components", int(max_iters), None)
        v_tail, v_head, e_mask, direction = traverse.single_hop_filters(
            self, pattern)
        tail, head = (g.src, g.dst) if direction == 1 else (g.dst, g.src)
        e_ok = jnp.ones((g.m,), jnp.bool_) if e_mask is None else e_mask
        v_ok = None
        if v_tail is not None or v_head is not None:
            vt = jnp.ones((g.n,), jnp.bool_) if v_tail is None else v_tail
            vh = jnp.ones((g.n,), jnp.bool_) if v_head is None else v_head
            e_ok = e_ok & vt[tail] & vh[head]
            v_ok = vt | vh
        ae = self._alive_edge_mask()
        if ae is not None:
            e_ok = e_ok & ae
        av = self._alive_vertex_mask()
        if av is not None:
            v_ok = av if v_ok is None else v_ok & av
        return traverse.components_masked(g, v_ok, e_ok, max_iters=max_iters)

    def _weighted_edge_filter(self, e_ok, weight: Optional[str]):
        """Fold a numeric edge-property column into a traversal: returns
        (f32 weights or None, edge filter with the column's validity mask
        ANDed in).  An edge without the property is NOT traversable under
        a weighted semiring — there is no sound default weight."""
        if weight is None:
            return None, e_ok
        from repro.query.weights import edge_weight_values

        w, wvalid = edge_weight_values(self, weight)
        return w, (wvalid if e_ok is None else e_ok & wvalid)

    def shortest_paths(
        self,
        seeds,
        *,
        weight: Optional[str] = None,
        pattern=None,
        undirected: bool = False,
        max_iters: Optional[int] = None,
    ) -> jax.Array:
        """Multi-source shortest-path distances from ``seeds`` (original
        ids) over the (min, +) tropical semiring — (n,) f32, 0.0 at the
        seeds, +inf where unreachable (docs/ARCHITECTURE.md §12).

        ``weight`` names a numeric edge property; edges without the
        property do not participate (``None`` = unit weights, hop
        counts).  ``pattern`` is the same node-only or single-hop filter
        ``khop`` takes — the ``shortestPath()``-style hook: the pattern
        constrains each STEP of the walk (relationship, predicates,
        endpoint labels, ``<-[...]-`` direction), the fixed point
        supplies the path structure.  Overlay tombstones and delta edges
        compose exactly as in ``khop``; under a mesh the per-round relax
        all-reduces partial distances with ``pmin`` (bitwise-identical
        to the single-device path)."""
        from repro import traverse

        g = self._require_graph()
        _obs_traverse("shortest_paths",
                      None if max_iters is None else int(max_iters),
                      int(np.asarray(seeds).size))
        v_tail, v_head, e_mask, direction = traverse.single_hop_filters(
            self, pattern)
        e_ok = jnp.ones((g.m,), jnp.bool_) if e_mask is None else e_mask
        tail, head = (g.src, g.dst) if direction == 1 else (g.dst, g.src)
        if v_tail is not None:
            e_ok = e_ok & v_tail[tail]
        if v_head is not None:
            e_ok = e_ok & v_head[head]
        ae = self._alive_edge_mask()
        if ae is not None:
            e_ok = e_ok & ae
        w, e_ok = self._weighted_edge_filter(e_ok, weight)
        ids = self._vertex_internal(seeds)
        ids = ids[ids >= 0]
        if self._dead_v is not None and ids.size:
            ids = ids[~self._dead_v[ids]]  # dead seeds don't traverse
        seed_mask = jnp.zeros((g.n,), jnp.bool_).at[jnp.asarray(ids)].set(True)
        if self.mesh is not None:
            return traverse.shortest_paths_sharded(
                g, seed_mask, w, e_ok, mesh=self.mesh, direction=direction,
                undirected=undirected, max_iters=max_iters)
        return traverse.shortest_paths_masked(
            g, seed_mask, w, e_ok, direction=direction,
            undirected=undirected, max_iters=max_iters)

    def _subgraph_filters(self, pattern):
        """Whole-subgraph mask composition shared by ``components``-shaped
        analytics (pagerank/communities): pattern endpoint masks gate
        edges AND define vertex membership (either endpoint constraint
        admits a vertex), overlay tombstones AND out of both."""
        from repro import traverse

        g = self._require_graph()
        v_tail, v_head, e_mask, direction = traverse.single_hop_filters(
            self, pattern)
        tail, head = (g.src, g.dst) if direction == 1 else (g.dst, g.src)
        e_ok = e_mask
        v_ok = None
        if v_tail is not None or v_head is not None:
            vt = jnp.ones((g.n,), jnp.bool_) if v_tail is None else v_tail
            vh = jnp.ones((g.n,), jnp.bool_) if v_head is None else v_head
            em = jnp.ones((g.m,), jnp.bool_) if e_ok is None else e_ok
            e_ok = em & vt[tail] & vh[head]
            v_ok = vt | vh
        ae = self._alive_edge_mask()
        if ae is not None:
            e_ok = ae if e_ok is None else e_ok & ae
        av = self._alive_vertex_mask()
        if av is not None:
            v_ok = av if v_ok is None else v_ok & av
        return g, v_ok, e_ok

    def pagerank(
        self,
        *,
        pattern=None,
        weight: Optional[str] = None,
        damping: float = 0.85,
        iters: int = 20,
    ) -> jax.Array:
        """PageRank on the subgraph the filter ``pattern`` allows — (n,)
        f32 ranks, 0.0 for vertices outside the filter (§12).

        The (+, ×) semiring instance: per-iteration contributions
        ``rank/out_degree`` flow along allowed edges (``weight`` scales
        them per-edge; edges without the property drop out), teleport and
        dangling mass redistribute over the allowed vertex count.  With
        no filter this is the classic §I kernel (``repro.graph.pagerank``
        delegates here).  Under a mesh the per-step aggregation
        all-reduces partial sums with ``psum`` — equal to the
        single-device ranks within float tolerance.

        Rank always flows along each edge's stored direction: the
        pattern only selects edges, so ``(b)<-[:r]-(a)`` selects the same
        a→b edges as ``(a)-[:r]->(b)`` and ranks them alike."""
        from repro import traverse

        _obs_traverse("pagerank", int(iters), None)
        g, v_ok, e_ok = self._subgraph_filters(pattern)
        w, e_ok = self._weighted_edge_filter(e_ok, weight)
        if self.mesh is not None:
            return traverse.pagerank_sharded(
                g, v_ok, e_ok, w, mesh=self.mesh, damping=damping,
                iters=iters)
        return traverse.pagerank_masked(
            g, v_ok, e_ok, w, damping=damping, iters=iters)

    def communities(self, pattern=None, *, max_iters: int = 64) -> jax.Array:
        """Community labels by synchronous label propagation on the
        subgraph the filter ``pattern`` allows — (n,) int32 (label =
        a member vertex id, internal numbering), -1 outside the filter
        (§12).

        Mode relax under a fixed deterministic tie-break (most frequent
        neighbor label, smallest wins ties); edges count as undirected,
        exactly ``components``' participation rule.  Every op is integer,
        so results are exact and identical under a mesh (the sort-based
        mode has no elementwise ⊕ to all-reduce; GSPMD runs the same
        program over the placed arrays)."""
        from repro import traverse

        _obs_traverse("communities", int(max_iters), None)
        g, v_ok, e_ok = self._subgraph_filters(pattern)
        return traverse.label_propagation_masked(
            g, v_ok, e_ok, max_iters=max_iters)

    # ------------------------------------------- snapshots / views / overlay
    def snapshot(self) -> "PropGraph":
        """Immutable view pinned at (base store @ version, frozen delta
        chain).  Zero-copy: the sealed device stores, DI arrays and typed
        columns are SHARED with the parent — only the small delta chunk
        lists are shallow-copied.  Writes keep landing on the parent (its
        delta chain grows past the snapshot's frozen prefix, its columns
        are replaced functionally), so a long-running ``components()`` or
        ``match()`` on the snapshot reads a consistent view throughout.
        Mutators on a snapshot raise; ``fork()`` one to branch."""
        from repro.overlay.views import clone_propgraph

        return clone_propgraph(self, frozen=True)

    def fork(self) -> "PropGraph":
        """Writable copy-on-write view: (base graph @ snapshot, private
        overlay).  Shares the base's device shards with the parent; each
        side's subsequent writes land in its own delta/tombstones — the
        what-if primitive (\"delete this hub, what breaks\") and the
        per-tenant branch the service's ``fork_view`` verb exposes."""
        from repro.overlay.views import clone_propgraph

        return clone_propgraph(self, frozen=False)

    @_write_locked
    def compact(self) -> "PropGraph":
        """Fold the whole overlay (delta edges, delta attribute pairs,
        tombstones) into fresh sealed base stores — the LSM merge step.
        Equivalent to rebuilding from scratch with the surviving data;
        structural for cache purposes (every cached result dies).  No-op
        when there is no overlay."""
        self._check_writable()
        if not self.has_overlay():
            return self
        from repro.overlay.compactor import compact_propgraph

        compact_propgraph(self)
        self.last_mutation = MutationEvent.structural_event("compact")
        self._bump_version()
        return self

    def has_overlay(self) -> bool:
        """Any uncompacted overlay state (delta pairs/edges or tombstones)?"""
        return self.overlay_size() > 0

    def overlay_size(self) -> int:
        """Total overlay entries — the compaction-policy signal the
        background ``Compactor`` thresholds on."""
        size = 0
        if self._delta_edges is not None:
            size += self._delta_edges.size
        if self._vstore is not None:
            size += self._vstore._delta.size
        if self._estore is not None:
            size += self._estore._delta.size
        if self._dead_v is not None:
            size += int(self._dead_v.sum())
        if self._dead_e is not None:
            size += int(self._dead_e.size)
        return size

    def delta_stats(self) -> Dict[str, int]:
        """Per-component overlay sizes (observability; pgserve surfaces it)."""
        return {
            "delta_edges": self._delta_edges.size if self._delta_edges else 0,
            "delta_vertex_pairs": self._vstore._delta.size if self._vstore else 0,
            "delta_edge_pairs": self._estore._delta.size if self._estore else 0,
            "dead_vertices": int(self._dead_v.sum()) if self._dead_v is not None else 0,
            "dead_edges": int(self._dead_e.size) if self._dead_e is not None else 0,
        }

    @property
    def frozen(self) -> bool:
        return self._frozen

    # ------------------------------------------------------------------ info
    @property
    def n_vertices(self) -> int:
        return self._require_graph().n

    @property
    def n_edges(self) -> int:
        return self._require_graph().m

    def label_set(self) -> List[str]:
        return self._vstore.amap.values if self._vstore else []

    def relationship_set(self) -> List[str]:
        return self._estore.amap.values if self._estore else []

    def label_counts(self) -> Dict[str, int]:
        """Per-label vertex counts, read off the cached ``attr_counts()``
        stats (host-derived; never a per-value ``query_any`` scan and never
        a device store upload).  Tombstoned vertices are subtracted, so the
        counts agree with ``query_labels`` (which masks them out)."""
        if self._vstore is None:
            return {}
        counts = self._vstore.attr_counts(dead_ids=self._dead_vertex_ids())
        return {v: int(counts[i]) for i, v in enumerate(self._vstore.amap.values)}

    def relationship_counts(self) -> Dict[str, int]:
        """Per-relationship edge counts, read off the cached
        ``attr_counts()`` stats (same contract as ``label_counts`` —
        tombstoned/detached edges subtracted)."""
        if self._estore is None:
            return {}
        counts = self._estore.attr_counts(dead_ids=self._dead_edge_ids())
        return {v: int(counts[i]) for i, v in enumerate(self._estore.amap.values)}
