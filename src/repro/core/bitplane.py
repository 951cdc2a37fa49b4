"""Bit-packed mask plane: uint32 words, little-endian bit order.

The DIP-arr byte Boolean array (paper §V) spends one int8 per
(entity, attribute); every mask that crosses a layer boundary — store →
kernel → shard all-reduce → wire — inherits that byte.  This module is
the single source of truth for the packed alternative: entity ``e``
lives in bit ``e % 32`` of word ``e // 32``, the exact layout of
``np.packbits(bitorder='little')`` viewed as ``<u4``, so a packed plane's
byte view IS the wire format and host/device packing agree bit-for-bit.

Invariant enforced everywhere: tail padding bits (entities ≥ n inside the
last word) are ZERO.  Builders scatter only in-range entities, ``pack_mask``
pads with False, and word-level AND/OR preserve zeros — so word-space
algebra (``base | delta & ~tomb``) never needs a masking epilogue.

The byte path stays available for one release behind
``REPRO_PG_BYTE_MASKS=1`` (env) or the ``byte_masks()`` context manager
(tests/smokes use the latter to run both paths in one process).
"""
from __future__ import annotations

import contextlib
import functools
import os
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

WORD = 32  # bits per packed word

__all__ = [
    "WORD", "n_words", "packed_default", "byte_masks",
    "pack_bits_host", "unpack_bits_host",
    "pack_mask", "unpack_mask", "census", "nonzero_words",
    "or_reduce", "or_allreduce",
]

# None → consult the env var; True/False → explicit override (context manager).
_FORCE_BYTE: Optional[bool] = None


def packed_default() -> bool:
    """True when new stores should pack masks (the default this release)."""
    if _FORCE_BYTE is not None:
        return not _FORCE_BYTE
    return os.environ.get("REPRO_PG_BYTE_MASKS", "0") not in ("1", "true", "yes")


@contextlib.contextmanager
def byte_masks(enabled: bool = True) -> Iterator[None]:
    """Force the byte fallback path (or un-force it) for the enclosed block.

    Process-local and not thread-scoped: flip it only at test/smoke setup,
    before graphs are built — stores capture the flag at build time.
    """
    global _FORCE_BYTE
    prev = _FORCE_BYTE
    _FORCE_BYTE = bool(enabled)
    try:
        yield
    finally:
        _FORCE_BYTE = prev


def n_words(n: int) -> int:
    """Words needed for n entities (ceil(n / 32); 0 entities → 0 words)."""
    return (int(n) + WORD - 1) // WORD


# ---------------------------------------------------------------------------
# Host (numpy) pack / unpack
# ---------------------------------------------------------------------------

def pack_bits_host(bits: np.ndarray) -> np.ndarray:
    """Pack a host bool/int array along its LAST axis into uint32 words.

    ``(..., n)`` → ``(..., ceil(n/32))`` with bit ``e & 31`` of word
    ``e >> 5`` = ``bits[..., e]``; tail bits zero.  Matches
    ``np.packbits(bitorder='little')`` then ``.view('<u4')``.
    """
    bits = np.asarray(bits)
    n = bits.shape[-1]
    w = n_words(n)
    packed8 = np.packbits(bits.astype(bool), axis=-1, bitorder="little")
    # packbits yields ceil(n/8) bytes; pad the byte axis to a 4-byte multiple
    # so the <u4 view lines up.  Pad bytes are zero → tail bits zero.
    pad = 4 * w - packed8.shape[-1]
    if pad:
        packed8 = np.concatenate(
            [packed8, np.zeros(bits.shape[:-1] + (pad,), np.uint8)], axis=-1)
    return np.ascontiguousarray(packed8).view("<u4").astype(np.uint32, copy=False)


def unpack_bits_host(words: np.ndarray, n: int) -> np.ndarray:
    """Inverse of :func:`pack_bits_host`: ``(..., W)`` uint32 → ``(..., n)`` bool."""
    words = np.ascontiguousarray(np.asarray(words, dtype="<u4"))
    bits = np.unpackbits(words.view(np.uint8), axis=-1, bitorder="little")
    return bits[..., :n].astype(bool)


# ---------------------------------------------------------------------------
# Device (jax) pack / unpack — identical layout
# ---------------------------------------------------------------------------

def pack_mask(mask: jax.Array) -> jax.Array:
    """Pack a device bool array along its last axis into uint32 words.

    jit-safe; pads the tail with False so padding bits are zero.
    """
    n = mask.shape[-1]
    w = n_words(n)
    pad = w * WORD - n
    if pad:
        cfg = [(0, 0)] * (mask.ndim - 1) + [(0, pad)]
        mask = jnp.pad(mask, cfg, constant_values=False)
    lanes = mask.reshape(mask.shape[:-1] + (w, WORD)).astype(jnp.uint32)
    shifts = jnp.arange(WORD, dtype=jnp.uint32)  # bit j ↔ entity w*32+j
    return jnp.sum(lanes << shifts, axis=-1, dtype=jnp.uint32)


def unpack_mask(words: jax.Array, n: int) -> jax.Array:
    """Inverse of :func:`pack_mask`: ``(..., W)`` uint32 → ``(..., n)`` bool."""
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    flat = bits.reshape(words.shape[:-1] + (words.shape[-1] * WORD,))
    return flat[..., :n].astype(bool)


def _nonzero_words(words: jax.Array, cap: int) -> jax.Array:
    w = words.shape[0]
    keys = jnp.where(words != 0, jnp.arange(w, dtype=jnp.int32), w)
    keys, words = jax.lax.sort((keys, words), num_keys=1)
    return jnp.concatenate([keys[:cap].astype(jnp.uint32), words[:cap]])


@functools.partial(jax.jit, static_argnames="cap")
def nonzero_words(words: jax.Array, cap: int) -> jax.Array:
    """The first ``cap`` nonzero words of a packed mask with their indices:
    ``(2·min(cap, W),)`` uint32, the indices (ascending) then the words.

    One key-value sort of the W words, nonzero ones keyed by their index
    and the rest by W, so it gathers nothing: on a TPU a sort of 10⁵ keys
    costs far less than cap-wide gathers.  Slots past the nonzero words
    hold index W and word 0, and expand to no positions.
    """
    return _nonzero_words(words, cap)


@functools.partial(jax.jit, static_argnames="cap")
def census(mask: jax.Array, cap: int):
    """A (n,) device bool mask → ``(words, count, head)`` in one launch: its
    packed words, its popcount (int32), and ``nonzero_words(words, cap)``.
    A caller reads ``count`` back to choose how to ship the mask; when the
    mask has no more than ``cap`` set bits, ``head`` already holds them."""
    words = pack_mask(mask)
    count = jnp.sum(jax.lax.population_count(words), dtype=jnp.int32)
    return words, count, _nonzero_words(words, cap)


def or_reduce(words: jax.Array, axis: int = 0) -> jax.Array:
    """Bitwise-OR reduction of uint32 words over ``axis``.

    ``jnp`` has no ``bitwise_or.reduce``; ``lax.reduce`` with a bitwise-or
    computation lowers to a log-depth tree on TPU/CPU alike.
    """
    return jax.lax.reduce(words, jnp.uint32(0),
                          jax.lax.bitwise_or, (axis,))


def or_allreduce(words: jax.Array, axis_name: str, num_devices: int) -> jax.Array:
    """Bitwise-OR all-reduce of packed words across a mesh axis.

    ``lax.pmax`` on packed words is NOT an OR (max(0b01, 0b10) = 0b10), so
    the frontier/scatter paths need a real OR collective.  For power-of-two
    device counts this is a recursive-doubling butterfly over ``ppermute``
    (log₂P rounds, each moving W words = n/8 bytes — the §7 "1 bit per
    entity" claim made literal); otherwise fall back to all_gather + a
    local OR fold.
    """
    p = int(num_devices)
    if p <= 1:
        return words
    if isinstance(axis_name, (tuple, list)) and len(axis_name) == 1:
        axis_name = axis_name[0]
    if isinstance(axis_name, str) and p & (p - 1) == 0:
        d = 1
        while d < p:
            perm = [(i, i ^ d) for i in range(p)]
            words = words | jax.lax.ppermute(words, axis_name, perm)
            d <<= 1
        return words
    gathered = jax.lax.all_gather(words, axis_name)  # (P, ...)
    return or_reduce(gathered, axis=0)
