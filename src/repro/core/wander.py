"""Weighted Wander Join (paper §5.1, Alg. 3).

WWJ = Wander Join with an *approximate* index: every random-walk step samples
the next record with probability proportional to embedding similarity, and a
Horvitz-Thompson correction (importance sampling over the cross product)
keeps the estimator unbiased.

Two samplers:

* :func:`walk_sample` — the faithful per-step random walk for k tables.  Cost
  O(n * sum_i N_i), never touches the cross product (paper's complexity
  argument, §5.1).
* :func:`flat_sample` — categorical over an explicit weight vector; used for
  within-stratum sampling in BAS (Alg. 4 ``WeightedSample(D_i, n_i, W)``) on
  the dense path.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.spans import span

from .similarity import _pair_weights_jax, weight_of_score
from .types import ConfidenceInterval


BLOCK = 128  # columns per block of a walk step's two-level draw
DRAW_ROWS = 32  # walks per host draw: bounds its f64 copies of the blocks


@dataclasses.dataclass
class WalkSample:
    idx: np.ndarray    # (n, k) tuple indices
    prob: np.ndarray   # (n,) sampling probability of each tuple (exact)


@functools.partial(jax.jit, static_argnames=("exponent", "floor"))
def _block_sums(cur, table, exponent: float, floor: float):
    """(rows, ceil(N / BLOCK)) f32 sums of each row's weights against
    ``table`` over blocks of ``BLOCK`` columns; the padding weighs 0."""
    w = _pair_weights_jax(cur, table, exponent, floor)
    w = jnp.pad(w, ((0, 0), (0, -table.shape[0] % BLOCK)))
    return w.reshape(w.shape[0], -1, BLOCK).sum(axis=2)


def _draw(u: np.ndarray, w: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Per row of ``w`` (f64, >= 0), the position whose cumulative weight
    first exceeds ``u`` times the row's total, and its share of that total.
    A position of weight 0 is never drawn."""
    cdf = np.cumsum(w, axis=1)
    tot = cdf[:, -1]
    pos = (cdf <= (u * tot)[:, None]).sum(axis=1)
    last = w.shape[1] - 1 - np.argmax(w[:, ::-1] > 0, axis=1)
    pos = np.minimum(pos, last)   # u * tot rounded up to tot
    return pos, np.take_along_axis(w, pos[:, None], axis=1)[:, 0] / tot


def _draw_in_blocks(sums: np.ndarray, cur: np.ndarray, table: np.ndarray,
                    u: np.ndarray, exponent: float,
                    floor: float) -> tuple[np.ndarray, np.ndarray]:
    """One walk step on the host: per walk (a row of ``cur``), a block b in
    proportion to its f32 sum S_b (``sums``), then a record of it in
    proportion to its weight recomputed in f64.  Returns (records, the
    probability (S_b / sum S) (w_j / sum_b w) of each draw); ``u`` holds
    the two uniforms of each walk."""
    n2 = table.shape[0]
    out = np.empty(len(cur), np.int64)
    prob = np.empty(len(cur), np.float64)
    for s in range(0, len(cur), DRAW_ROWS):
        sl = slice(s, s + DRAW_ROWS)
        b, p_block = _draw(u[sl, 0], sums[sl].astype(np.float64))
        cols = b[:, None] * BLOCK + np.arange(BLOCK)
        real = cols < n2
        cols = np.minimum(cols, n2 - 1)
        sims = np.einsum("nkd,nd->nk", table[cols].astype(np.float64),
                         cur[sl].astype(np.float64))
        w = np.where(real, weight_of_score(sims, exponent, floor), 0.0)
        j, p_rec = _draw(u[sl, 1], w)
        out[sl] = cols[np.arange(len(j)), j]
        prob[sl] = p_block * p_rec
    return out, prob


def walk_sample(
    embeddings: list[np.ndarray],
    n: int,
    rng: np.random.Generator,
    exponent: float = 1.0,
    floor: float = 1e-3,
    chunk: int = 512,
    timings: Optional[dict] = None,
) -> WalkSample:
    """n independent WWJ random walks over a k-table chain.

    Each step draws the next record of a walk in proportion to its weight,
    in two levels.  The device computes the walks' weights against the next
    table (uploaded once per edge) in launches of ``chunk`` rows, one
    program per table shape, and returns only their f32 sums over blocks of
    ``BLOCK`` columns; the host then draws a block and a record of it
    (:func:`_draw_in_blocks`).  Every real record weighs at least ``floor``
    in both levels, so every tuple stays reachable.  Two uniforms per walk
    and step are drawn up front, so the draws do not depend on ``chunk``.

    With ``timings``, the spans ``walk.blocks`` (upload, device steps and
    fetches) and ``walk.draw`` (host draws) add their seconds to
    ``walk_blocks_s`` and ``walk_draw_s``; ``walk_launches`` and
    ``walk_fetch_bytes`` count the device steps and the bytes fetched."""
    k = len(embeddings)
    n1 = embeddings[0].shape[0]
    idx = np.empty((n, k), np.int64)
    prob = np.full((n,), 1.0 / n1, np.float64)
    idx[:, 0] = rng.integers(0, n1, size=n)
    launches = fetched = 0
    for step in range(k - 1):
        prev = np.asarray(embeddings[step], np.float32)
        nxt = np.asarray(embeddings[step + 1], np.float32)
        u = rng.random((n, 2))
        # every launch of the edge is queued before the first fetch
        pending = []
        with span("walk.blocks", timings, "walk_blocks_s"):
            table = jax.device_put(nxt)
            for s in range(0, n, chunk):
                rows = min(chunk, n - s)
                cur = np.zeros((chunk, nxt.shape[1]), np.float32)
                cur[:rows] = prev[idx[s : s + rows, step]]
                pending.append((s, rows, cur,
                                _block_sums(cur, table, exponent, floor)))
        for s, rows, cur, out in pending:
            with span("walk.blocks", timings, "walk_blocks_s"):
                sums = np.asarray(out)
            launches += 1
            fetched += sums.nbytes
            with span("walk.draw", timings, "walk_draw_s"):
                j, p = _draw_in_blocks(sums[:rows], cur[:rows], nxt,
                                       u[s : s + rows], exponent, floor)
            idx[s : s + rows, step + 1] = j
            prob[s : s + rows] *= p
    if timings is not None:
        timings["walk_launches"] = timings.get("walk_launches", 0) + launches
        timings["walk_fetch_bytes"] = (timings.get("walk_fetch_bytes", 0)
                                       + fetched)
    return WalkSample(idx=idx, prob=prob)


def flat_sample(
    weights: np.ndarray, n: int, rng: np.random.Generator,
    defensive_mix: float = 0.0,
) -> tuple[np.ndarray, np.ndarray]:
    """Sample n positions from ``weights`` (with replacement) with probability
    proportional to weight.  Returns (positions, normalised probabilities).

    ``defensive_mix`` in (0, 1) mixes a uniform component over the *support*
    (weight > 0) into the proposal — defensive importance sampling: the HT
    weight is then bounded by |support| / mix, trading a little efficiency on
    clean weights for bounded variance when the weights are misleading."""
    w = np.asarray(weights, np.float64)
    total = w.sum()
    if total <= 0 or len(w) == 0:
        raise ValueError("cannot sample from empty/zero weights")
    p = w / total
    if defensive_mix > 0.0:
        support = (w > 0).astype(np.float64)
        p = (1.0 - defensive_mix) * p + defensive_mix * support / support.sum()
    cdf = np.cumsum(p)
    cdf[-1] = 1.0
    pos = np.searchsorted(cdf, rng.random(n), side="right")
    pos = np.minimum(pos, len(w) - 1)
    return pos.astype(np.int64), p[pos]


# ----------------------------------------------------------------------------
# Standalone WWJ estimator (Alg. 3): the paper's sampling-only method.
# ----------------------------------------------------------------------------

def ht_terms(values: np.ndarray, probs: np.ndarray) -> np.ndarray:
    """Horvitz-Thompson terms x_i = v_i / p_i; mean over them is unbiased for
    the population total when p is the exact sampling distribution."""
    return np.asarray(values, np.float64) / np.asarray(probs, np.float64)


def clt_ci(x: np.ndarray, p: float) -> tuple[float, ConfidenceInterval]:
    """Normal-approximation CI on the mean of HT terms (Alg. 3 lines 9-10)."""
    from scipy import stats

    x = np.asarray(x, np.float64)
    mu = float(x.mean())
    if len(x) < 2:
        return mu, ConfidenceInterval(-np.inf, np.inf, p)
    se = float(x.std(ddof=1) / np.sqrt(len(x)))
    z = float(stats.norm.ppf(0.5 + p / 2.0))
    return mu, ConfidenceInterval(mu - z * se, mu + z * se, p)
