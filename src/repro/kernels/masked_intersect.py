"""Pallas TPU kernel: batched masked popcount-intersection over packed
bitsets — the one kernel behind every discovery workload's hot set check
(docs/KERNELS.md, DESIGN.md §10).

Contract (``W`` = uint32 words per bitset, ``a/mask [B, W]``, ``b [N, W]``)::

    counts[r, c] = popcount(a[r] & mask[r] & b[c])        # int32 [B, N]

``mask`` is the per-row constraint bitset (``None`` = all-ones).  The same
product serves every workload's call shape (the full operand table lives
in docs/KERNELS.md — label-constrained variants reuse these shapes with
predicate bitsets folded into the operands, DESIGN.md §12):

* **cross counts** (clique): ``a = P`` candidate bitsets, ``b = ext`` masks,
  no row mask — ``counts`` is the |P| of every child clique
  (:func:`frontier_expand` is exactly this specialization);
* **membership / candidate-set materialization** (iso): ``a`` = label
  bitset of the next query vertex, ``mask`` = the state's
  adjacency/complement constraint product, ``b = bitset.eye_table(n)``
  (one-hot rows) — ``counts[r, v] ∈ {0, 1}`` materializes the candidate
  grid for a whole dequeued batch in one call;
* **pair probes** (pattern mining): ``a = adj[u]``, ``mask = eye[v]``,
  ``b = ones [1, W]`` — ``counts[e, 0]`` is the edge-existence bit for
  every embedding in the batch.

TPU mapping: bitwise-AND/popcount "matmul" over the word axis — pure VPU
work.  The grid tiles (B, N); each step holds a ``[bB, W]`` row tile
(plus its mask tile) and a ``[bN, W]`` column tile in VMEM and
materializes only the ``[bB, bN, W]`` intersection tile, vs. the full
``[B, N, W]`` the jnp reference allocates — the VMEM working-set win that
makes expansion HBM-bandwidth bound instead of capacity bound.

Ragged shapes are handled by zero-padding B and N up to the block grid
(zero rows/columns contribute zero counts and are sliced off), so any
(B, N, W) — including W=1 and non-multiple-of-block sizes — is legal.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .runtime import resolve_interpret

DEFAULT_BLOCK_B = 8
DEFAULT_BLOCK_N = 128


def _kernel(a_ref, b_ref, out_ref):
    a = a_ref[...]                       # [bB, W] uint32
    b = b_ref[...]                       # [bN, W] uint32
    inter = a[:, None, :] & b[None, :, :]
    out_ref[...] = jnp.sum(
        jax.lax.population_count(inter).astype(jnp.int32), axis=-1)


def _kernel_masked(a_ref, mask_ref, b_ref, out_ref):
    a = a_ref[...] & mask_ref[...]       # [bB, W] uint32
    b = b_ref[...]                       # [bN, W] uint32
    inter = a[:, None, :] & b[None, :, :]
    out_ref[...] = jnp.sum(
        jax.lax.population_count(inter).astype(jnp.int32), axis=-1)


@functools.partial(jax.jit, static_argnames=("block_b", "block_n",
                                             "interpret"))
def _masked_intersect(a_bits, b_bits, mask_bits,
                      block_b: int, block_n: int, interpret: bool):
    b, w = a_bits.shape
    n, w2 = b_bits.shape
    assert w == w2, f"word-width mismatch: rows W={w}, columns W={w2}"
    bb = min(block_b, b)
    bn = min(block_n, n)
    pad_b = (-b) % bb
    pad_n = (-n) % bn
    if pad_b:
        a_bits = jnp.pad(a_bits, ((0, pad_b), (0, 0)))
        if mask_bits is not None:
            mask_bits = jnp.pad(mask_bits, ((0, pad_b), (0, 0)))
    if pad_n:
        b_bits = jnp.pad(b_bits, ((0, pad_n), (0, 0)))
    bp, np_ = b + pad_b, n + pad_n

    row_spec = pl.BlockSpec((bb, w), lambda i, j: (i, 0))
    col_spec = pl.BlockSpec((bn, w), lambda i, j: (j, 0))
    if mask_bits is None:
        kernel, in_specs, operands = \
            _kernel, [row_spec, col_spec], (a_bits, b_bits)
    else:
        kernel, in_specs, operands = (_kernel_masked,
                                      [row_spec, row_spec, col_spec],
                                      (a_bits, mask_bits, b_bits))
    out = pl.pallas_call(
        kernel,
        grid=(bp // bb, np_ // bn),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bb, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, np_), jnp.int32),
        interpret=interpret,
        name="masked_intersect",
    )(*operands)
    return out[:b, :n]


def masked_intersect(a_bits: jnp.ndarray, b_bits: jnp.ndarray,
                     mask_bits: Optional[jnp.ndarray] = None,
                     block_b: int = DEFAULT_BLOCK_B,
                     block_n: int = DEFAULT_BLOCK_N,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """``counts[r, c] = popcount(a[r] & mask[r] & b[c])``; int32 [B, N].

    ``mask_bits=None`` means no row mask; ``interpret=None`` auto-detects
    the backend (:func:`repro.kernels.runtime.default_interpret`).
    """
    if mask_bits is not None:
        assert mask_bits.shape == a_bits.shape, \
            f"mask shape {mask_bits.shape} != rows shape {a_bits.shape}"
    return _masked_intersect(a_bits, b_bits, mask_bits,
                             block_b=block_b, block_n=block_n,
                             interpret=resolve_interpret(interpret))
