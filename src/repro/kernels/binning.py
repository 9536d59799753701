"""Shared in-kernel histogram epilogue for the similarity kernels.

TPUs have no scatter-add, so binning a block of scores means comparing every
element against bin ids.  A naive epilogue does O(n_bins) VPU compares per
element.  Here the bin index is split as ``idx = hi * lane + lo`` and the two
halves are one-hot encoded separately::

    counts[hi, lo] = sum_e 1[hi_e == hi] * 1[lo_e == lo]
                   = (OH_hi @ OH_lo^T)[hi, lo]

so each element pays O(n_bins/lane + lane) compares on the VPU (32 + 128
instead of 4096 at the default bin count) and the combine runs as a matmul
on the MXU.  Counts stay exact: the one-hots are 0/1 (exact in bf16), the
f32 accumulator represents integers up to 2**24, and one block contributes
at most bm*bn <= 2**18 per bin.

Layout: counts live as an ``(n_hi, lane)`` tile (:func:`bin_layout`), which
satisfies the TPU's (8, 128) block rule for any bin count; bin counts that
are not a multiple of the lane width leave the tail of the last row unused,
and callers slice ``reshape(-1)[:n_bins]`` outside the kernel.  The block
is swept one row at a time from a VMEM copy of the bin ids — a dynamic row
load, which Mosaic supports where a flattening reshape of the block is not.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128  # TPU lane width: the `lo` radix of the two-level split
_LANE_BITS = LANE.bit_length() - 1


def bin_layout(n_bins: int) -> tuple[int, int]:
    """``(n_hi, lane)`` shape of one block's count tile."""
    lane = min(n_bins, LANE)
    return -(-n_bins // lane), lane


def bin_counts(idx, n_bins: int, idx_buf):
    """(bm, bn) int32 bin ids -> (n_hi, lane) int32 counts of the block.

    ``idx_buf`` is a (bm, bn) int32 VMEM scratch ref the ids are parked in
    so the row loop can load one row per step."""
    n_hi, lane = bin_layout(n_bins)
    bm, bn = idx.shape
    idx_buf[...] = idx
    iota_hi = jax.lax.broadcasted_iota(jnp.int32, (n_hi, bn), 0)
    iota_lo = jax.lax.broadcasted_iota(jnp.int32, (lane, bn), 0)

    def body(r, acc):
        row = idx_buf[pl.ds(r, 1), :]                  # (1, bn)
        if n_hi == 1:
            oh_hi = jnp.ones((1, bn), jnp.bfloat16)
            lo = row
        else:  # lane == LANE, a power of two
            oh_hi = (jax.lax.shift_right_logical(row, _LANE_BITS) == iota_hi)
            oh_hi = oh_hi.astype(jnp.bfloat16)
            lo = row & (LANE - 1)
        oh_lo = (lo == iota_lo).astype(jnp.bfloat16)   # (lane, bn)
        return acc + jax.lax.dot_general(
            oh_hi, oh_lo, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    acc = jax.lax.fori_loop(0, bm, body, jnp.zeros((n_hi, lane), jnp.float32))
    return acc.astype(jnp.int32)


def flat_counts(tiles, n_bins: int):
    """(..., n_hi, lane) count tiles -> (..., n_bins), outside the kernel."""
    flat = tiles.reshape(*tiles.shape[:-2], -1)
    return flat[..., :n_bins]
