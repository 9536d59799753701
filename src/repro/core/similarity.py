"""Embedding similarity and sampling weights (paper §2, §5.1).

All similarity math is JAX (jit-compiled, shardable); the outputs the
statistical layer needs (weight vectors, sums) are returned as float64 numpy
for numerically robust aggregation.

Weight convention: embeddings are unit-normalised, so ``E1 @ E2.T`` is the
cosine similarity.  The paper treats similarity as an (approximate) match
probability, so we map it to a strictly positive weight::

    w = max(clip(cos, 0, 1), floor) ** exponent

The floor keeps every tuple reachable (a zero sampling probability would break
unbiasedness for false negatives — the exact failure mode of blocking the
paper is fixing); the exponent reproduces the Fig. 13b sensitivity knob.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def normalize(emb: np.ndarray) -> np.ndarray:
    emb = np.asarray(emb, dtype=np.float32)
    norm = np.linalg.norm(emb, axis=-1, keepdims=True)
    return emb / np.maximum(norm, 1e-12)


@functools.partial(jax.jit, static_argnames=("exponent", "floor"))
def _pair_weights_jax(e1, e2, exponent: float, floor: float):
    # HIGHEST: a TPU runs a default-precision f32 dot as one bf16 pass,
    # which would move weights across histogram bins; the CPU is exact
    sim = jnp.dot(e1, e2.T, preferred_element_type=jnp.float32,
                  precision=jax.lax.Precision.HIGHEST)
    sim = jnp.clip(sim, 0.0, 1.0)
    w = jnp.maximum(sim, floor)
    if exponent != 1.0:
        w = w**exponent
    return w


def pair_weights(
    e1: np.ndarray,
    e2: np.ndarray,
    exponent: float = 1.0,
    floor: float = 1e-3,
    block: int = 8192,
) -> np.ndarray:
    """(N1, N2) sampling weights.  Blocked to bound peak memory."""
    e1 = np.asarray(e1, np.float32)
    e2 = np.asarray(e2, np.float32)
    n1 = e1.shape[0]
    if n1 <= block:
        return np.asarray(_pair_weights_jax(e1, e2, exponent, floor), np.float64)
    out = np.empty((n1, e2.shape[0]), np.float64)
    for s in range(0, n1, block):
        out[s : s + block] = np.asarray(
            _pair_weights_jax(e1[s : s + block], e2, exponent, floor), np.float64
        )
    return out


def chain_weights(
    embeddings: list[np.ndarray],
    exponent: float = 1.0,
    floor: float = 1e-3,
) -> np.ndarray:
    """Flattened (N1*...*Nk,) weights: product of consecutive pair weights.

    Paper Alg. 2 line 4: W(t) = prod_j sim(E(t_j), E(t_{j+1})).  Dense path —
    only used when the cross product fits in memory; the streaming/NN path in
    ``stratify.py`` covers the rest.
    """
    sizes = [e.shape[0] for e in embeddings]
    w = np.ones((1,), np.float64)
    # w has shape (prod(sizes[:i+1]),) after step i
    for i in range(len(embeddings) - 1):
        pw = pair_weights(embeddings[i], embeddings[i + 1], exponent, floor)
        if i == 0:
            w = pw.reshape(-1)
        else:
            # w: (prod(sizes[:i+1]),) indexed by (..., t_i); extend with t_{i+1}
            w = (w.reshape(-1, sizes[i])[:, :, None] * pw[None, :, :]).reshape(-1)
    return w


def quantize_rows_int8(emb: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Per-row symmetric int8 quantisation: ``emb ~= q * row_scale``.

    Used by the ``sim_sweep`` int8 fast path — scores reconstruct as
    ``(q1 @ q2^T) * rs1_i * rs2_j`` with exact int32 MXU accumulation, so
    the only error is the per-element rounding of the embeddings themselves
    (<= 0.5 * row_scale, i.e. ~0.4% of the row absmax).  All-zero rows
    (e.g. block padding) quantise to zeros with scale 0.
    """
    emb = np.asarray(emb, np.float32)
    absmax = np.abs(emb).max(axis=1, keepdims=True)
    row_scale = absmax / 127.0
    q = np.where(
        absmax > 0, np.rint(emb / np.maximum(row_scale, 1e-30)), 0.0
    ).astype(np.int8)
    return q, row_scale.astype(np.float32)


def dequantize_rows_int8(q: np.ndarray, row_scale: np.ndarray) -> np.ndarray:
    """Inverse of :func:`quantize_rows_int8` (up to rounding)."""
    return q.astype(np.float32) * np.asarray(row_scale, np.float32).reshape(-1, 1)


def weight_of_score(
    s: np.ndarray, exponent: float = 1.0, floor: float = 1e-3
) -> np.ndarray:
    """The score -> sampling-weight transform (single source of truth —
    stratification thresholds and sampling probabilities must agree)."""
    w = np.clip(s, 0.0, 1.0)
    w = np.maximum(w, floor)
    return w**exponent if exponent != 1.0 else w


def aligned_pair_weights(
    e1: np.ndarray,
    e2: np.ndarray,
    i: np.ndarray,
    j: np.ndarray,
    exponent: float = 1.0,
    floor: float = 1e-3,
) -> np.ndarray:
    """Elementwise weights for aligned index vectors (no cross block)."""
    sims = np.einsum("nd,nd->n", e1[i].astype(np.float64), e2[j].astype(np.float64))
    return weight_of_score(sims, exponent, floor)


def chain_tuple_weights(
    embeddings: list,
    idx: np.ndarray,
    exponent: float = 1.0,
    floor: float = 1e-3,
) -> np.ndarray:
    """Chain weights W(t) = prod_j w_j(t_j, t_{j+1}) for explicit (n, k)
    tuples — O(n * k * d), never touches the cross product."""
    idx = np.asarray(idx)
    w = np.ones(idx.shape[0], np.float64)
    for j in range(len(embeddings) - 1):
        w *= aligned_pair_weights(
            embeddings[j], embeddings[j + 1], idx[:, j], idx[:, j + 1],
            exponent, floor,
        )
    return w


# Pass accounting for the standalone walk-statistic recomputations below.
# The fused sweep (repro.core.stratify.sweep_pass*) emits row sums and the
# chain total in the same blocked pass as the histogram, so a streaming
# query that goes through the sweep — or hydrates a warm IndexArtifact —
# should never land here; tests assert these counters stay flat on those
# paths (see tests/test_chain_stats.py).
PASS_COUNTS: dict[str, int] = {"edge_row_sums": 0, "chain_total_weight": 0}


def edge_row_sums_raw(
    embeddings: list,
    exponent: float = 1.0,
    floor: float = 1e-3,
    block: int = 4096,
) -> list:
    """:func:`edge_row_sums` without the pass accounting — for internal
    callers (the fused sweep) that only touch cheap prefix edges."""
    out = []
    for j in range(len(embeddings) - 1):
        e1, e2 = embeddings[j], embeddings[j + 1]
        r = np.zeros(e1.shape[0], np.float64)
        for s in range(0, e1.shape[0], block):
            r[s : s + block] = pair_weights(
                e1[s : s + block], e2, exponent, floor
            ).sum(axis=1)
        out.append(r)
    return out


def edge_row_sums(
    embeddings: list,
    exponent: float = 1.0,
    floor: float = 1e-3,
    block: int = 4096,
) -> list:
    """Per-edge row sums r_j[i] = sum_t w_j(i, t), streamed in O(block * N)
    memory.  These normalise the WWJ walk distribution p(t) =
    (1/N1) * prod_j w_j(t_j, t_{j+1}) / r_j(t_j)."""
    PASS_COUNTS["edge_row_sums"] += 1
    return edge_row_sums_raw(embeddings, exponent, floor, block)


def chain_total_weight(
    embeddings: list,
    exponent: float = 1.0,
    floor: float = 1e-3,
    block: int = 4096,
) -> float:
    """sum over the full cross product of prod_j w_j — via the backward
    matrix-vector chain v_j = W_j v_{j+1}, streamed (O(max N) memory)."""
    PASS_COUNTS["chain_total_weight"] += 1
    v = np.ones(embeddings[-1].shape[0], np.float64)
    for j in range(len(embeddings) - 2, -1, -1):
        e1, e2 = embeddings[j], embeddings[j + 1]
        nxt = np.zeros(e1.shape[0], np.float64)
        for s in range(0, e1.shape[0], block):
            nxt[s : s + block] = pair_weights(
                e1[s : s + block], e2, exponent, floor
            ) @ v
        v = nxt
    return float(v.sum())


def flat_to_tuples(flat_idx: np.ndarray, sizes: tuple) -> np.ndarray:
    """(n,) flat cross-product indices -> (n, k) per-table indices."""
    return np.stack(np.unravel_index(np.asarray(flat_idx), sizes), axis=1).astype(
        np.int64
    )


def tuples_to_flat(idx: np.ndarray, sizes: tuple) -> np.ndarray:
    return np.ravel_multi_index(tuple(idx[:, j] for j in range(idx.shape[1])), sizes)
