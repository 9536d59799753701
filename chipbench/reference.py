"""Plain references of what the timed path computes, and their controls.

Nothing here imports the program.  The references follow the published
semantics: the sweep's weight transform max(clip(e1 . e2, 0, 1), floor) **
exponent, binned, ranked and summed in float64 on the host; the sampling
weight of each drawn pair under the same transform; COUNT's
Horvitz-Thompson estimate and its bootstrap-t CI in float64; and the
oracle's forward in float32 at the highest matmul precision, by the module
of its family under ``chipbench/oracles/``.  Each takes a ``control`` flag
(or ``dtype``) that computes the same thing one precision below what the
configuration states (the products
at ``Precision.HIGH``, three bf16 passes, instead of float32 at
``HIGHEST``; the estimate in float32 instead of float64; the oracle's
matmuls in float8 e4m3 instead of bfloat16): the control must fail the
comparison that the program passes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import oracles


# ---------------------------------------------------------------------------
# the stratification sweep
# ---------------------------------------------------------------------------

def scores(e1, e2, control: bool = False) -> np.ndarray:
    """(rows, n2) float64 similarities; the control takes the products on
    the device at ``Precision.HIGH``."""
    if not control:
        return np.asarray(e1, np.float64) @ np.asarray(e2, np.float64).T
    s = jnp.dot(jnp.asarray(e1, jnp.float32), jnp.asarray(e2, jnp.float32).T,
                precision=jax.lax.Precision.HIGH,
                preferred_element_type=jnp.float32)
    return np.asarray(s, np.float64)


def weights(sc: np.ndarray, exponent: float, floor: float) -> np.ndarray:
    return np.maximum(np.clip(sc, 0.0, 1.0), floor) ** exponent


def sweep_blocks(e1, e2, blocks, block_rows: int, n_bins: int, k: int,
                 exponent: float, floor: float, control: bool = False,
                 score_ids=()) -> dict:
    """Sweep outputs for the left-row blocks ``blocks``: the count tile of
    each block, and per row the top-k (values, right ids) of the clipped
    score and the walk row sum; ``at`` holds the clipped score of every
    right id that a row of any ``score_ids`` dict names."""
    out = {"tiles": {}, "vals": {}, "idx": {}, "row_sums": {}, "at": {}}
    n1 = e1.shape[0]
    for g in blocks:
        rows = np.arange(g * block_rows, min((g + 1) * block_rows, n1))
        sc = scores(e1[rows], e2, control)
        w = weights(sc, exponent, floor)
        b = np.clip((w * n_bins).astype(np.int64), 0, n_bins - 1)
        out["tiles"][g] = np.bincount(b.reshape(-1), minlength=n_bins)
        cl = np.clip(sc, 0.0, 1.0)
        top = np.argpartition(-cl, k - 1, axis=1)[:, :k]
        tv = np.take_along_axis(cl, top, axis=1)
        order = np.argsort(-tv, axis=1, kind="stable")
        for i, r in enumerate(rows.tolist()):
            out["vals"][r] = tv[i, order[i]]
            out["idx"][r] = top[i, order[i]]
            out["row_sums"][r] = float(w[i].sum())
            ids = {int(j) for d in score_ids for j in np.asarray(d[r]).tolist()}
            out["at"][r] = {j: float(cl[i, j]) for j in ids}
    return out


def sweep_numbers(got: dict, ref: dict, margin: int = 2) -> dict:
    """What is compared of a sweep, both sides in ``sweep_blocks`` form
    (``ref`` with ``at`` for ``got``'s ids): count tiles outside the
    ``margin``-bin band the rescans rely on (the program's own guarantee,
    ``stratify.SweepInfo.blocks_over``); the largest top-k value gap, both
    rank by rank and against the reference's score of each id ``got``
    names, so a wrong id reads as a value gap; and the largest relative
    row-sum gap."""
    outside = 0
    for g, want in ref["tiles"].items():
        have = np.asarray(got["tiles"][g], np.int64)
        ge_k = np.cumsum(have[::-1])[::-1]
        ge_r = np.cumsum(want[::-1])[::-1]
        hi = np.concatenate([ge_r[margin:], np.zeros(margin, np.int64)])
        lo = np.concatenate([np.repeat(ge_r[:1], margin), ge_r[:-margin]])
        outside += int(((ge_k < hi) | (ge_k > lo)).any())
    val_dev, rs_rel = 0.0, 0.0
    for r in ref["idx"]:
        vals = np.asarray(got["vals"][r], np.float64)
        k = len(vals)
        val_dev = max(val_dev, float(np.abs(vals - ref["vals"][r][:k]).max()))
        at = np.array([ref["at"][r][int(j)] for j in np.asarray(got["idx"][r])])
        val_dev = max(val_dev, float(np.abs(vals - at).max()))
        want = ref["row_sums"][r]
        rs_rel = max(rs_rel, abs(got["row_sums"][r] - want) / want)
    return {
        "sweep_tiles_outside_margin": outside,
        "sweep_topk_value_dev": val_dev,
        "sweep_row_sum_rel": rs_rel,
    }


def dense_weight_dev(got_rows: np.ndarray, e1_rows, e2, exponent: float,
                     floor: float, control: bool = False) -> float:
    """Largest gap of the dense path's pair weights on some left rows."""
    want = weights(scores(e1_rows, e2), exponent, floor)
    have = (weights(scores(e1_rows, e2, True), exponent, floor) if control
            else np.asarray(got_rows, np.float64))
    return float(np.abs(have - want).max())


# ---------------------------------------------------------------------------
# sampling and estimation
# ---------------------------------------------------------------------------

def _pair_scores(e1, e2, pairs, control: bool) -> np.ndarray:
    """e1[i] . e2[j] for each (i, j) of ``pairs``, in float64 or, for the
    control, as the program takes them: a matrix product on the device (at
    ``Precision.HIGH``) of the rows and columns named, then gathered."""
    if not control:
        return np.einsum("nd,nd->n", e1[pairs[:, 0]], e2[pairs[:, 1]])
    rows, ri = np.unique(pairs[:, 0], return_inverse=True)
    cols, ci = np.unique(pairs[:, 1], return_inverse=True)
    return scores(e1[rows], e2[cols], True)[ri, ci]


def sample_weight_dev(draws, e1, e2, exponent: float, floor: float,
                      walk_strata=(), control: bool = False) -> float:
    """Largest gap, in weight units, between the weight each drawn pair was
    sampled with, as its stated probability implies it, and the float64
    weight of the pair.

    ``draws`` is [(stratum, (n, 2) pairs, (n,) probabilities)].  Within a
    stratum a probability is affine in the pair's weight (sampling in
    proportion to weight with a uniform share); in a ``walk_strata``
    stratum (the left row uniform, then the right row in proportion to its
    weight) it is affine in the weight over its left row's weight sum.  The
    affine map is fitted per stratum, so a gap reads |implied weight -
    weight|.  The control states its probabilities from weights and row
    sums at ``Precision.HIGH``."""
    e1 = np.asarray(e1, np.float64)
    e2 = np.asarray(e2, np.float64)
    by: dict = {}
    for i, tup, q in draws:
        ts, qs = by.setdefault(int(i), ([], []))
        ts.append(np.asarray(tup))
        qs.append(np.asarray(q, np.float64))
    rows = sorted({int(r) for i in walk_strata if i in by
                   for t in by[i][0] for r in t[:, 0]})
    rsum, csum = {}, {}
    for s in range(0, len(rows), 256):
        blk = rows[s:s + 256]
        rsum.update(zip(blk, weights(scores(e1[blk], e2), exponent,
                                     floor).sum(axis=1)))
        if control:
            csum.update(zip(blk, weights(scores(e1[blk], e2, True), exponent,
                                         floor).sum(axis=1)))
    dev = 0.0
    for i, (ts, qs) in sorted(by.items()):
        t, q = np.concatenate(ts), np.concatenate(qs)
        w = weights(_pair_scores(e1, e2, t, False), exponent, floor)
        if control:
            q = weights(_pair_scores(e1, e2, t, True), exponent, floor)
        r = np.ones_like(w)
        if i in walk_strata:
            r = np.array([rsum[int(x)] for x in t[:, 0]])
            if control:
                q = q / np.array([csum[int(x)] for x in t[:, 0]])
        x = w / r
        if len(x) < 3 or np.ptp(x) == 0:
            continue
        scale = float(x.max())
        design = np.stack([x / scale, np.ones_like(x)], axis=1)
        (a, b), *_ = np.linalg.lstsq(design, q, rcond=None)
        gap = float((np.abs((q - b) / (a / scale) - x) * r).max())
        if not np.isfinite(gap):
            return float("inf")
        dev = max(dev, gap)
    return dev


def ht_count_ci(strata, blocked, p: float, n_boot: int, rng_state: dict,
                dtype=np.float64) -> tuple:
    """(estimate, lo, hi) of COUNT from one query's samples.

    ``strata`` is [(labels, within-stratum probabilities)] of the sampled
    strata, ``blocked`` the labels of the blocked pairs (counted exactly),
    ``rng_state`` the resampling stream's state when the CI began.  The
    estimate is the blocked count plus each stratum's mean term label /
    probability; its variance the sum of the strata's term variances over
    their sample counts.  The bootstrap-t resamples the centred terms of
    each stratum of two or more samples, in order, one (n_boot, n) matrix
    of indices a stratum, and takes the (1 - p) / 2 and (1 + p) / 2
    quantiles t_lo, t_hi of the studentised resampled estimate: CI =
    [est - t_hi sigma, est - t_lo sigma].  A CI with no spread is the
    point; one with under 10 finite resamples est -/+ 10 sigma.  ``dtype``
    float32 is the control."""
    bg = getattr(np.random, rng_state["bit_generator"])()
    bg.state = rng_state
    rng = np.random.Generator(bg)
    terms = [np.asarray(o, dtype) / np.asarray(q, dtype) for o, q in strata]
    est = np.sum(np.asarray(blocked, dtype), dtype=dtype)
    var = dtype(0.0)
    for c in terms:
        if len(c):
            est = est + c.mean(dtype=dtype)
        if len(c) > 1:
            var = var + c.var(ddof=1, dtype=dtype) / dtype(len(c))
    est, sigma = float(est), float(np.sqrt(max(var, 0.0)))
    usable = [c for c in terms if len(c) > 1]
    if not usable or sigma == 0.0:
        return est, est, est
    shift = np.zeros(n_boot, dtype)
    var_j = np.zeros(n_boot, dtype)
    for c in usable:
        centred = c - c.mean(dtype=dtype)
        res = centred[rng.integers(0, len(c), size=(n_boot, len(c)))]
        shift += res.mean(axis=1, dtype=dtype)
        var_j += res.var(axis=1, ddof=1, dtype=dtype) / dtype(len(c))
    with np.errstate(invalid="ignore", divide="ignore"):
        t = shift / np.sqrt(np.maximum(var_j, 0.0))
    t = t[np.isfinite(t)]
    if len(t) < 10:
        return est, est - 10 * sigma, est + 10 * sigma
    t_lo = float(np.quantile(t, (1.0 - p) / 2.0))
    t_hi = float(np.quantile(t, (1.0 + p) / 2.0))
    return est, est - t_hi * sigma, est - t_lo * sigma


# ---------------------------------------------------------------------------
# the oracle's yes / no logits
# ---------------------------------------------------------------------------

def yes_no_logits(oracle: dict, params, toks, last, yes: int, no: int,
                  control: bool = False) -> np.ndarray:
    """(B, 2) float64 [yes, no] logits at each row's ``last`` position, by
    the reference of the oracle's family (``chipbench/oracles/``)."""
    return oracles.load(oracle["family"]).yes_no_logits(
        oracle, params, toks, last, yes, no, control=control)


def logit_dev(got: np.ndarray, want: np.ndarray) -> float:
    """Largest logit gap, relative to the reference's largest logit (at
    least 1)."""
    return float(np.abs(got - want).max() / max(float(np.abs(want).max()), 1.0))
