"""Packed-bitset algebra in JAX.

Subgraph states in the Nuri engine are fixed-width bitsets packed into
``uint32`` words (``W = ceil(N / 32)`` words for an N-vertex graph).  All
operations are elementwise / reduction ops that map directly onto the TPU
VPU; the hot combination (AND + population count) is also provided as a
Pallas kernel in :mod:`repro.kernels.frontier_expand`.

States are routinely stored bit-cast to ``int32`` (the engine's generic
state dtype); use :func:`to_i32` / :func:`to_u32` at the boundary.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

WORD_BITS = 32


def num_words(n_bits: int) -> int:
    """Number of uint32 words needed for ``n_bits`` bits."""
    return (int(n_bits) + WORD_BITS - 1) // WORD_BITS


def to_i32(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def to_u32(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


def zeros(shape_prefix, n_bits: int) -> jnp.ndarray:
    return jnp.zeros(tuple(shape_prefix) + (num_words(n_bits),), jnp.uint32)


def from_indices(indices, n_bits: int) -> np.ndarray:
    """Host-side: build a packed bitset (numpy) from an index list."""
    w = num_words(n_bits)
    out = np.zeros((w,), np.uint32)
    idx = np.asarray(indices, np.int64)
    if idx.size:
        np.bitwise_or.at(out, idx // WORD_BITS,
                         (np.uint32(1) << (idx % WORD_BITS).astype(np.uint32)))
    return out


def from_bool(mask: np.ndarray) -> np.ndarray:
    """Host-side: pack a boolean vector [..., N] into [..., W] uint32."""
    mask = np.asarray(mask, bool)
    n = mask.shape[-1]
    w = num_words(n)
    pad = w * WORD_BITS - n
    if pad:
        mask = np.concatenate(
            [mask, np.zeros(mask.shape[:-1] + (pad,), bool)], axis=-1)
    bits = mask.reshape(mask.shape[:-1] + (w, WORD_BITS)).astype(np.uint32)
    shifts = (np.uint32(1) << np.arange(WORD_BITS, dtype=np.uint32))
    return (bits * shifts).sum(axis=-1).astype(np.uint32)


def to_bool(bitset: jnp.ndarray, n_bits: int) -> jnp.ndarray:
    """Unpack [..., W] uint32 into a boolean [..., n_bits] array."""
    w = bitset.shape[-1]
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    bits = (bitset[..., :, None] >> shifts) & jnp.uint32(1)
    flat = bits.reshape(bitset.shape[:-1] + (w * WORD_BITS,))
    return flat[..., :n_bits].astype(bool)


def popcount(bitset: jnp.ndarray, axis=-1) -> jnp.ndarray:
    """Total number of set bits along ``axis`` (int32)."""
    return jnp.sum(jax.lax.population_count(bitset).astype(jnp.int32),
                   axis=axis)


def get_bit(bitset: jnp.ndarray, idx) -> jnp.ndarray:
    """Test bit ``idx`` (int array broadcastable to batch) -> bool."""
    idx = jnp.asarray(idx)
    word = jnp.take_along_axis(
        bitset, (idx // WORD_BITS)[..., None].astype(jnp.int32), axis=-1
    )[..., 0]
    return ((word >> (idx % WORD_BITS).astype(jnp.uint32)) & 1).astype(bool)


def set_bit(bitset: jnp.ndarray, idx) -> jnp.ndarray:
    """Return a copy of ``bitset`` with bit ``idx`` set (batched)."""
    idx = jnp.asarray(idx)
    word_idx = (idx // WORD_BITS).astype(jnp.int32)
    bit = (jnp.uint32(1) << (idx % WORD_BITS).astype(jnp.uint32))
    w = bitset.shape[-1]
    onehot = (jnp.arange(w, dtype=jnp.int32) == word_idx[..., None])
    return bitset | jnp.where(onehot, bit[..., None], jnp.uint32(0))


def lt_mask_table(n: int) -> np.ndarray:
    """Host-side table ``gt[v]`` = bitset of {u : u > v}, shape [n, W].

    Used for canonical (duplicate-free) clique expansion: the candidate set
    of ``s ∪ {v}`` is ``P_s ∩ N(v) ∩ gt[v]``.
    """
    w = num_words(n)
    u = np.arange(w * WORD_BITS)[None, :]
    v = np.arange(n)[:, None]
    mask = (u > v) & (u < n)
    return from_bool(mask)


def eye_table(n: int) -> np.ndarray:
    """Host-side identity table ``eye[v]`` = bitset containing only ``v``,
    shape [n, W].

    Used as the column operand of the masked-intersection kernel to turn
    popcounts into membership probes: ``popcount(m & eye[v])`` is bit ``v``
    of ``m`` (docs/KERNELS.md).
    """
    w = num_words(n)
    out = np.zeros((n, w), np.uint32)
    v = np.arange(n)
    out[v, v // WORD_BITS] = np.uint32(1) << (v % WORD_BITS).astype(np.uint32)
    return out


def eye(n: int) -> jnp.ndarray:
    """:func:`eye_table` computed on the device: inside a jitted program
    it is built from an iota, so the ``[n, W]`` table is never compiled in
    as a constant."""
    return set_bit(zeros((n,), n), jnp.arange(n, dtype=jnp.int32))


def first_set_bit(bitset: jnp.ndarray) -> jnp.ndarray:
    """Index of the lowest set bit, or -1 if empty.  Batched over leading dims."""
    w = bitset.shape[-1]
    # lowest set bit per word
    low = bitset & (~bitset + jnp.uint32(1))
    # log2 of an exact power of two via popcount(x - 1)
    bit_in_word = jax.lax.population_count(low - jnp.uint32(1)).astype(jnp.int32)
    has = (bitset != 0)
    word_idx = jnp.argmax(has, axis=-1).astype(jnp.int32)
    any_set = jnp.any(has, axis=-1)
    sel = jnp.take_along_axis(bit_in_word, word_idx[..., None], axis=-1)[..., 0]
    return jnp.where(any_set, word_idx * WORD_BITS + sel, -1)
