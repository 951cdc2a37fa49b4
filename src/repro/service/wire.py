"""Wire codec for the pgd network front-end — length-prefixed JSON + binary.

Arachne rides Arkouda's request/reply server: a thin Python client sends
small messages naming server-held objects, the server answers with small
metadata plus (when needed) bulk array payloads (paper §III,
docs/ARCHITECTURE.md §9).  This module is that message format for the
analytics service — one codec shared by ``server.py`` and ``client.py`` so
the two can never disagree about framing.

Frame layout (the frame's integers big-endian)::

    MAGIC (4 bytes, b"PGW1")
    payload_len   uint32        # bytes after this field
    header_len    uint32        # JSON part of the payload
    header        UTF-8 JSON    # op/id/fields + "arrays": [spec, ...]
    blob          bytes         # the arrays' buffers, concatenated

The header is small and human-debuggable JSON; bulk data (masks, id
arrays, property columns) travels as raw buffers described by per-array
specs ``{"dtype", "shape"}`` appended by the codec.  A bool array travels
one of two ways, and comes back as the same plain bool array either way —
mask round-trips are bitwise, which the cross-process equivalence gate
relies on (``pgserve --net --smoke``):

* ``{"dtype": "bool", "shape": [n], "enc": "ids", "count": k}`` — a (n,)
  mask with few set bits (``32·k < n``, where 4k bytes undercut the n/8 of
  a bitmap) ships as its k set positions, ascending little-endian int32.
  A typed pattern's reply masks usually are: a hop over one relationship
  of many touches thousands of entities out of millions.  The receiver
  validates them (in range, strictly ascending, the count matching the
  blob) and sets those bits in ``np.zeros(n, bool)``.
* otherwise ``np.packbits(bitorder="little")``-packed (8× smaller than
  bytes).  Little-endian bit order makes the wire bytes IDENTICAL to the
  ``core.bitplane`` word plane's byte view, so a mask the server holds
  packed ships verbatim (:class:`PackedMask`).

The choice follows the mask's popcount alone.  ``result_to_wire`` takes it
on the device: one launch per mask packs it, counts its bits and sorts its
first 2¹⁰ nonzero words to the front (``bitplane.census``); a sparse mask
with more set bits than that takes a second launch
(``bitplane.nonzero_words``) whose buffer is the smallest of a few
per-length capacities that holds the count.  So the device-to-host copy is
on the order of the set bits, not of n, and the host expands the nonzero
words to positions.  The counter
``pg_wire_masks{enc="ids"|"bitmap"}`` counts each mask at encode.

``recv_msg`` raises ``ConnectionError`` on a clean EOF at a frame
boundary (peer closed) and ``ProtocolError`` on everything else —
truncated frames, bad magic, oversized payloads, bad array specs or ids,
bytes past the last blob.
"""
from __future__ import annotations

import dataclasses
import json
import socket
import struct
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "MAGIC",
    "MAX_PAYLOAD",
    "ProtocolError",
    "RemoteError",
    "PackedMask",
    "SparseMask",
    "encode_msg",
    "send_msg",
    "recv_msg",
    "result_to_wire",
    "wire_to_result",
    "WireMatchResult",
    "WireSampledBlock",
    "blocks_to_wire",
    "wire_to_blocks",
    "exc_to_wire",
    "wire_to_exc",
]

MAGIC = b"PGW1"
MAX_PAYLOAD = 1 << 30  # 1 GiB — fail fast on garbage length prefixes
_LEN = struct.Struct("!I")

# process-global frame/byte accounting (docs/ARCHITECTURE.md §13); the
# counters are resolved once at import so the per-frame cost with metrics
# ON is two lock+add pairs, and with metrics OFF a single flag check
from repro.obs.metrics import enabled as _obs_enabled  # noqa: E402


def _wire_counters(direction: str):
    from repro.obs.metrics import GLOBAL

    return (GLOBAL.counter("pg_wire_frames", "wire frames", dir=direction),
            GLOBAL.counter("pg_wire_bytes", "wire bytes", dir=direction))


_SENT = _wire_counters("sent")
_RECEIVED = _wire_counters("received")


def _mask_counter(enc: str):
    from repro.obs.metrics import GLOBAL

    return GLOBAL.counter("pg_wire_masks", "bool masks encoded", enc=enc)


_MASKS_IDS = _mask_counter("ids")
_MASKS_BITMAP = _mask_counter("bitmap")


class ProtocolError(RuntimeError):
    """Malformed frame: bad magic, truncated payload, oversized length."""


class RemoteError(RuntimeError):
    """A server-side exception type we cannot reconstruct locally."""

    def __init__(self, type_name: str, message: str):
        super().__init__(f"{type_name}: {message}")
        self.type_name = type_name
        self.message = message


# ------------------------------------------------------------------ arrays
@dataclasses.dataclass(frozen=True)
class PackedMask:
    """A (n,) bool mask already bit-packed in ``core.bitplane`` layout.

    The codec ships its little-endian byte view verbatim (tail bits are
    zero by the bitplane invariant, exactly what ``np.packbits`` would
    emit) and the receiver sees a plain bool array — senders holding
    packed words skip the unpack→repack round-trip entirely."""

    words: np.ndarray  # (ceil(n/32),) uint32, little-endian bit order
    n: int


@dataclasses.dataclass(frozen=True)
class SparseMask:
    """A (n,) bool mask held as its set positions: the ``ids`` encoding.

    The codec ships ``ids`` as they are and the receiver sees a plain bool
    array, like :class:`PackedMask`'s."""

    ids: np.ndarray  # (k,) int32, strictly ascending, each in [0, n)
    n: int


def _ids_cheaper(count: int, n: int) -> bool:
    """True when k int32 positions take fewer bytes than the n-bit bitmap."""
    return 32 * count < n


def _ids_blob(ids, n: int) -> Tuple[dict, bytes]:
    ids = np.ascontiguousarray(ids, dtype="<i4")
    _MASKS_IDS.inc()
    return ({"dtype": "bool", "shape": [int(n)], "enc": "ids",
             "count": int(ids.shape[0])}, ids.tobytes())


def _pack_array(a) -> Tuple[dict, bytes]:
    if isinstance(a, SparseMask):
        return _ids_blob(a.ids, a.n)
    if isinstance(a, PackedMask):
        _MASKS_BITMAP.inc()
        spec = {"dtype": "bool", "shape": [int(a.n)]}
        nbytes = (int(a.n) + 7) // 8
        words = np.ascontiguousarray(np.asarray(a.words, dtype="<u4"))
        return spec, words.view(np.uint8)[:nbytes].tobytes()
    a = np.ascontiguousarray(a)
    spec = {"dtype": str(a.dtype), "shape": list(a.shape)}
    if a.dtype == np.bool_:
        if a.ndim == 1 and _ids_cheaper(np.count_nonzero(a), a.shape[0]):
            return _ids_blob(np.flatnonzero(a), a.shape[0])
        _MASKS_BITMAP.inc()
        return spec, np.packbits(a.reshape(-1), bitorder="little").tobytes()
    return spec, a.tobytes()


def _parse_spec(spec) -> Tuple[np.dtype, Tuple[int, ...], int]:
    """Validate an untrusted array spec → (dtype, shape, element count);
    anything off is ``ProtocolError`` (a corrupt frame must never surface
    as a raw numpy error — the server session and client loops only handle
    protocol exceptions).  The count is computed with Python ints, so an
    absurd shape cannot overflow into a plausible-looking size."""
    try:
        dtype = np.dtype(spec["dtype"])
        shape = tuple(spec["shape"])
    except (KeyError, TypeError, ValueError) as e:
        raise ProtocolError(f"bad array spec {spec!r}: {e}") from None
    if dtype.hasobject or not all(
            isinstance(d, int) and 0 <= d <= MAX_PAYLOAD for d in shape):
        raise ProtocolError(f"bad array spec {spec!r}")
    count = 1
    for d in shape:
        count *= d
    if count * max(dtype.itemsize, 1) > MAX_PAYLOAD:
        raise ProtocolError(f"bad array spec {spec!r}: too large")
    return dtype, shape, count


def _parse_ids_spec(spec: dict) -> Tuple[int, int]:
    """Validate an untrusted ``ids`` spec → (n, count)."""
    shape, count = spec.get("shape"), spec.get("count")
    if (spec.get("enc") != "ids" or spec.get("dtype") != "bool"
            or not isinstance(shape, list) or len(shape) != 1
            or type(shape[0]) is not int or type(count) is not int
            or not 0 <= count <= shape[0] <= MAX_PAYLOAD):
        raise ProtocolError(f"bad array spec {spec!r}")
    return shape[0], count


def _blob_nbytes(dtype: np.dtype, count: int) -> int:
    if dtype == np.bool_:
        return (count + 7) // 8
    return count * dtype.itemsize


def _unpack_ids(n: int, count: int, buf: memoryview) -> np.ndarray:
    ids = np.frombuffer(buf, dtype="<i4", count=count)
    if count and (ids[0] < 0 or ids[-1] >= n
                  or not (ids[1:] > ids[:-1]).all()):
        raise ProtocolError(
            f"ids of a ({n},) mask are not strictly ascending in range")
    mask = np.zeros(n, np.bool_)
    mask[ids] = True
    return mask


def _unpack_array(dtype: np.dtype, shape: Tuple[int, ...], count: int,
                  buf: memoryview) -> np.ndarray:
    if dtype == np.bool_:
        bits = np.unpackbits(np.frombuffer(buf, np.uint8), count=count,
                             bitorder="little")
        return bits.astype(np.bool_).reshape(shape)
    return np.frombuffer(buf, dtype=dtype, count=count).reshape(shape)


# ------------------------------------------------------------------ framing
def encode_msg(header: Dict, arrays: Sequence[np.ndarray] = ()) -> bytes:
    """One complete frame.  ``header`` must be JSON-serializable; the codec
    owns the ``"arrays"`` key."""
    specs, blobs = [], []
    for a in arrays:
        spec, blob = _pack_array(a if isinstance(a, (PackedMask, SparseMask))
                                 else np.asarray(a))
        specs.append(spec)
        blobs.append(blob)
    hdr = dict(header)
    hdr["arrays"] = specs
    hbytes = json.dumps(hdr, sort_keys=True).encode("utf-8")
    payload_len = _LEN.size + len(hbytes) + sum(len(b) for b in blobs)
    if payload_len > MAX_PAYLOAD:
        raise ProtocolError(f"frame too large: {payload_len} bytes")
    parts = [MAGIC, _LEN.pack(payload_len), _LEN.pack(len(hbytes)), hbytes]
    parts.extend(blobs)
    return b"".join(parts)


def send_msg(sock: socket.socket, header: Dict,
             arrays: Sequence[np.ndarray] = ()) -> None:
    buf = encode_msg(header, arrays)
    if _obs_enabled():
        frames, nbytes = _SENT
        frames.inc()
        nbytes.inc(len(buf))
    sock.sendall(buf)


def _recv_exact(sock: socket.socket, n: int, *, at_boundary: bool) -> bytes:
    chunks, got = [], 0
    while got < n:
        chunk = sock.recv(min(n - got, 1 << 20))
        if not chunk:
            if at_boundary and got == 0:
                raise ConnectionError("peer closed the connection")
            raise ProtocolError(f"truncated frame: wanted {n} bytes, got {got}")
        chunks.append(chunk)
        got += len(chunk)
    return b"".join(chunks)


def recv_msg(sock: socket.socket) -> Tuple[Dict, List[np.ndarray]]:
    """Read one frame → ``(header, arrays)``; blocks until complete."""
    head = _recv_exact(sock, len(MAGIC) + _LEN.size, at_boundary=True)
    if _obs_enabled():
        frames, nbytes = _RECEIVED
        frames.inc()
        nbytes.inc(len(head))
    if head[: len(MAGIC)] != MAGIC:
        raise ProtocolError(f"bad magic {head[:len(MAGIC)]!r}")
    (payload_len,) = _LEN.unpack(head[len(MAGIC):])
    if payload_len > MAX_PAYLOAD or payload_len < _LEN.size:
        raise ProtocolError(f"bad payload length {payload_len}")
    payload = memoryview(_recv_exact(sock, payload_len, at_boundary=False))
    if _obs_enabled():
        _RECEIVED[1].inc(payload_len)
    (header_len,) = _LEN.unpack(payload[: _LEN.size])
    if _LEN.size + header_len > payload_len:
        raise ProtocolError(f"bad header length {header_len}")
    try:
        header = json.loads(bytes(payload[_LEN.size:_LEN.size + header_len]))
    except ValueError as e:
        raise ProtocolError(f"bad header JSON: {e}") from None
    specs = header.pop("arrays", []) if isinstance(header, dict) else None
    if not isinstance(specs, list):
        raise ProtocolError("header is not an object with an array list")
    arrays: List[np.ndarray] = []
    off = _LEN.size + header_len
    for spec in specs:
        if isinstance(spec, dict) and "enc" in spec:
            n, count = _parse_ids_spec(spec)
            nbytes = 4 * count
            if off + nbytes > payload_len:
                raise ProtocolError("array blobs exceed payload")
            arrays.append(_unpack_ids(n, count, payload[off:off + nbytes]))
        else:
            dtype, shape, count = _parse_spec(spec)
            nbytes = _blob_nbytes(dtype, count)
            if off + nbytes > payload_len:
                raise ProtocolError("array blobs exceed payload")
            arrays.append(_unpack_array(dtype, shape, count,
                                        payload[off:off + nbytes]))
        off += nbytes
    if off != payload_len:
        raise ProtocolError(f"{payload_len - off} bytes past the array blobs")
    return header, arrays


# ------------------------------------------------------------ MatchResult
@dataclasses.dataclass(frozen=True)
class WireMatchResult:
    """Client-side view of a ``query.executor.MatchResult``.

    Carries the participation masks and name-keyed bindings (computed
    server-side — the ``Plan`` object itself never crosses the wire); the
    mask payloads are bitwise-identical to the in-process result's.
    """

    vertex_mask: np.ndarray  # (n,) bool
    edge_mask: np.ndarray  # (m,) bool
    _bindings: Dict[str, np.ndarray]

    def bindings(self) -> Dict[str, np.ndarray]:
        return dict(self._bindings)

    def n_vertices(self) -> int:
        return int(self.vertex_mask.sum())

    def n_edges(self) -> int:
        return int(self.edge_mask.sum())


# A device mask's census brings back its first _HEAD nonzero words with its
# popcount, so a mask with no more set bits than that (8 KB) takes one
# launch.  A sparse mask with more takes a second launch, whose buffer is the
# smallest of _WORD_CAPS, or of the largest count the ids encoding takes,
# that holds the count: the copy is at most 4x the 4k bytes shipped (a mask
# has no more nonzero words than set bits), from at most four programs a
# mask length.
_HEAD = 1 << 10
_WORD_CAPS = (1 << 12, 1 << 14, 1 << 16)
_warmed: set = set()
_warm_lock = threading.Lock()


def _word_caps(n: int) -> List[int]:
    """The second-launch buffers a (n,) mask may use, smallest first; none
    where the head holds every count the ids encoding takes."""
    top = (n - 1) // 32  # the largest count with _ids_cheaper(count, n)
    if top <= _HEAD:
        return []
    return [c for c in _WORD_CAPS if c < top] + [top]


def _warm_nonzero_words(bitplane, words, n: int) -> None:
    """Compile every buffer of this mask length and placement the first time
    one is seen, so that a count which lands in a new bucket later compiles
    nothing: a served cell's warm-up covers them all."""
    key = (n, words.sharding)
    with _warm_lock:
        if key in _warmed:
            return
        _warmed.add(key)
    for cap in _word_caps(n):
        bitplane.nonzero_words(words, cap=cap)


def _positions(pairs: np.ndarray) -> np.ndarray:
    """``bitplane.nonzero_words`` output → the mask's set positions,
    ascending (a padding slot's word is 0 and yields none)."""
    idx, words = np.split(pairs, 2)
    bits = np.unpackbits(np.ascontiguousarray(words, "<u4")
                         .view(np.uint8).reshape(-1, 4), axis=1,
                         bitorder="little")
    row, bit = np.nonzero(bits)
    return (idx[row].astype(np.int64) * 32 + bit).astype(np.int32)


def _mask_payloads(masks) -> list:
    """Reply masks as the codec ships them.  A (n,) bool device mask is
    packed and counted on the device, its first nonzero words gathered in
    the same launch (``bitplane.census``), and the rest of a sparse mask's
    in a second (``bitplane.nonzero_words``), so only ~8 bytes a set bit
    cross to the host, where they expand to the :class:`SparseMask`'s
    positions; a dense one ships as the :class:`PackedMask` of its words.
    Anything else (host arrays, non-bool) goes through the codec's generic
    path."""
    try:
        import jax

        from repro.core import bitplane
    except ImportError:  # jax-free client process
        return [np.asarray(m) for m in masks]
    out = list(masks)
    dev = {}
    for i, m in enumerate(masks):
        if (isinstance(m, jax.Array) and m.dtype == bool and m.ndim == 1
                and m.shape[0]):
            dev[i] = bitplane.census(m, cap=_HEAD)
            for a in dev[i][1:]:
                a.copy_to_host_async()
        else:
            out[i] = np.asarray(m)
    staged = {}
    for i, (words, count, head) in dev.items():
        n, k = int(masks[i].shape[0]), int(count)
        _warm_nonzero_words(bitplane, words, n)
        if not _ids_cheaper(k, n):
            staged[i] = (words, False, n)
        elif k <= _HEAD:
            out[i] = SparseMask(ids=_positions(np.asarray(head)), n=n)
        else:
            cap = next(c for c in _word_caps(n) if c >= k)
            staged[i] = (bitplane.nonzero_words(words, cap=cap), True, n)
    for a, _, _ in staged.values():
        a.copy_to_host_async()
    for i, (a, sparse, n) in staged.items():
        out[i] = (SparseMask(ids=_positions(np.asarray(a)), n=n) if sparse
                  else PackedMask(words=np.asarray(a), n=n))
    return out


def result_to_wire(res) -> Tuple[Dict, List[np.ndarray]]:
    """``MatchResult`` → (meta, arrays): masks first, bindings after in
    ``meta["vars"]`` order, each as ids or a bitmap by its popcount."""
    bindings = res.bindings()
    names = sorted(bindings)
    arrays = _mask_payloads([res.vertex_mask, res.edge_mask]
                            + [bindings[k] for k in names])
    return {"vars": names}, arrays


def _as_bool_mask(a) -> np.ndarray:
    """Normalize a result payload to a (n,) bool array.  The codec already
    delivers bool (``recv_msg``); in-process callers that short-circuit the
    transport may hand back the ``PackedMask`` or ``SparseMask`` from
    ``result_to_wire`` (possibly wrapped in a 0-d object array by
    ``np.asarray``) — expand it host-side (numpy only, so jax-free clients
    stay jax-free)."""
    if isinstance(a, np.ndarray) and a.dtype == object and a.ndim == 0:
        a = a.item()
    if isinstance(a, SparseMask):
        mask = np.zeros(a.n, np.bool_)
        mask[np.asarray(a.ids)] = True
        return mask
    if isinstance(a, PackedMask):
        words = np.ascontiguousarray(np.asarray(a.words, dtype="<u4"))
        bits = np.unpackbits(words.view(np.uint8), bitorder="little")
        return bits[:a.n].astype(bool)
    return np.asarray(a)


def wire_to_result(meta: Dict, arrays: Sequence[np.ndarray]) -> WireMatchResult:
    names = meta["vars"]
    if len(arrays) != 2 + len(names):
        raise ProtocolError(
            f"result carries {len(arrays)} arrays for {len(names)} vars")
    arrays = [_as_bool_mask(a) for a in arrays]
    return WireMatchResult(
        vertex_mask=arrays[0], edge_mask=arrays[1],
        _bindings=dict(zip(names, arrays[2:])),
    )


# ------------------------------------------------------------ SampledBlock
@dataclasses.dataclass(frozen=True)
class WireSampledBlock:
    """Client-side view of one ``graph.sampler.SampledBlock`` layer.

    Same field contract (ids are the server graph's INTERNAL ids, edge_*
    are local indices into src_nodes/dst_nodes, edge_mask False = padded
    slot) but plain numpy — the client stays jax-free.  Payloads are
    bitwise the in-process blocks': the deterministic-mode wire-parity
    gate in ``pgserve --net --smoke`` depends on that.
    """

    src_nodes: np.ndarray  # (n_src,) int32
    dst_nodes: np.ndarray  # (n_dst,) int32
    edge_src: np.ndarray  # (n_edges,) int32 local
    edge_dst: np.ndarray  # (n_edges,) int32 local
    edge_mask: np.ndarray  # (n_edges,) bool

    @property
    def n_src(self) -> int:
        return int(self.src_nodes.shape[0])

    @property
    def n_dst(self) -> int:
        return int(self.dst_nodes.shape[0])

    @property
    def n_edges(self) -> int:
        return int(self.edge_src.shape[0])


def blocks_to_wire(blocks) -> Tuple[Dict, List[np.ndarray]]:
    """SampledBlock list → (meta, arrays): five arrays per layer in block
    order (src_nodes, dst_nodes, edge_src, edge_dst, edge_mask) — the id/
    index arrays as int32 blobs, the mask as ids or a bitmap by the same
    popcount rule as a match's (§15's "blocks ship as packed masks + index
    arrays"; a padded block's mask is mostly set, so a bitmap)."""
    arrays: List[np.ndarray] = []
    masks = _mask_payloads([b.edge_mask for b in blocks])
    for b, mask in zip(blocks, masks):
        arrays.append(np.asarray(b.src_nodes, np.int32))
        arrays.append(np.asarray(b.dst_nodes, np.int32))
        arrays.append(np.asarray(b.edge_src, np.int32))
        arrays.append(np.asarray(b.edge_dst, np.int32))
        arrays.append(mask)
    return {"layers": len(blocks)}, arrays


def wire_to_blocks(meta: Dict, arrays: Sequence[np.ndarray]
                   ) -> List[WireSampledBlock]:
    layers = int(meta["layers"])
    if len(arrays) != 5 * layers:
        raise ProtocolError(
            f"sample result carries {len(arrays)} arrays for {layers} layers")
    blocks = []
    for li in range(layers):
        s, d, es, ed, em = arrays[5 * li:5 * li + 5]
        blocks.append(WireSampledBlock(
            src_nodes=np.asarray(s, np.int32),
            dst_nodes=np.asarray(d, np.int32),
            edge_src=np.asarray(es, np.int32),
            edge_dst=np.asarray(ed, np.int32),
            edge_mask=_as_bool_mask(em),
        ))
    return blocks


# -------------------------------------------------------------- exceptions
def exc_to_wire(e: BaseException) -> Dict[str, str]:
    return {"type": type(e).__name__, "message": str(e)}


def wire_to_exc(d: Dict[str, str]) -> BaseException:
    """Rebuild a builtin exception when possible (so ``pytest.raises
    (KeyError)`` works across the wire), ``RemoteError`` otherwise."""
    cls = getattr(__builtins__, d["type"], None) if not isinstance(
        __builtins__, dict) else __builtins__.get(d["type"])
    if isinstance(cls, type) and issubclass(cls, Exception):
        try:
            return cls(d["message"])
        except Exception:  # noqa: BLE001 — odd constructor signature
            pass
    return RemoteError(d["type"], d["message"])
