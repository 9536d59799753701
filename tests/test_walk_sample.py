"""The WWJ walk step as a two-level draw (``repro.core.wander``).

Each step draws a block of ``BLOCK`` columns in proportion to its f32 sum,
computed on the device, then a record of the block in proportion to its
weight recomputed in f64.  Under test, on fixed seeds: the probability a
walk states is that of the draw as executed, and within 1e-6 relative of
the ideal walk's; the draws follow it (chi-square); they do not depend on
the launch size; padded columns are never drawn; and one program and one
upload of the next table serve every walk count.
"""
import jax
import numpy as np
import pytest
from scipy import stats

from repro.core import wander
from repro.core.similarity import weight_of_score

FLOOR = 1e-3


def _unit(rng, n, d):
    e = rng.standard_normal((n, d)).astype(np.float32)
    return e / np.linalg.norm(e, axis=1, keepdims=True)


def _tables(sizes, d=8, seed=0):
    rng = np.random.default_rng(seed)
    return [_unit(rng, n, d) for n in sizes]


def _weights(e1, e2, exponent):
    """(N1, N2) f64 weights."""
    return weight_of_score(e1.astype(np.float64) @ e2.astype(np.float64).T,
                           exponent, FLOOR)


def _block_sums(e1, e2, exponent, chunk=512):
    """The device's f32 block sums of every row of ``e1``, from a launch of
    the walk's own shape."""
    cur = np.zeros((chunk, e1.shape[1]), np.float32)
    cur[: len(e1)] = e1
    out = wander._block_sums(cur, e2, exponent, FLOOR)
    return np.asarray(out, np.float64)[: len(e1)]


def _step_probs(e1, e2, exponent):
    """(N1, N2) probability of each step i -> j as the walk executes it:
    (S_b / sum S) (w_j / sum_b w), with b the block of j."""
    s = _block_sums(e1, e2, exponent)
    w = _weights(e1, e2, exponent)
    blk = np.arange(e2.shape[0]) // wander.BLOCK
    w_blk = np.stack([w[:, blk == b].sum(axis=1) for b in range(s.shape[1])],
                     axis=1)
    return (s[:, blk] / s.sum(axis=1, keepdims=True)) * w / w_blk[:, blk]


CASES = [((40, 300), 1.0), ((40, 300, 200), 2.0)]


@pytest.mark.parametrize("sizes,exponent", CASES)
def test_stated_probability_is_the_draw_as_executed(sizes, exponent):
    embs = _tables(sizes)
    ws = wander.walk_sample(embs, 3000, np.random.default_rng(1), exponent,
                            FLOOR)
    want = np.full(len(ws.prob), 1.0 / sizes[0])
    ideal = want.copy()
    for step in range(len(embs) - 1):
        i, j = ws.idx[:, step], ws.idx[:, step + 1]
        want *= _step_probs(embs[step], embs[step + 1], exponent)[i, j]
        w = _weights(embs[step], embs[step + 1], exponent)
        ideal *= w[i, j] / w.sum(axis=1)[i]
    np.testing.assert_allclose(ws.prob, want, rtol=1e-12)
    np.testing.assert_allclose(ws.prob, ideal, rtol=1e-6)


@pytest.mark.parametrize("exponent", [1.0, 2.0])
def test_draws_follow_the_stated_probabilities(exponent):
    """6 x 300: three blocks, the last one partial (44 columns)."""
    e1, e2 = _tables((6, 300), d=4, seed=2)
    n = 200_000
    ws = wander.walk_sample([e1, e2], n, np.random.default_rng(3), exponent,
                            FLOOR)
    p = _step_probs(e1, e2, exponent) / 6.0
    np.testing.assert_allclose(p.sum(), 1.0, rtol=1e-12)
    np.testing.assert_allclose(ws.prob, p[ws.idx[:, 0], ws.idx[:, 1]],
                               rtol=1e-12)
    obs = np.bincount(ws.idx[:, 0] * 300 + ws.idx[:, 1], minlength=p.size)
    exp = n * p.reshape(-1)
    big = exp >= 5
    obs = np.append(obs[big], obs[~big].sum())
    exp = np.append(exp[big], exp[~big].sum())
    assert big.sum() > 600
    assert stats.chisquare(obs, exp).pvalue > 1e-3


@pytest.mark.parametrize("sizes,exponent", CASES)
def test_draws_do_not_depend_on_the_launch_size(sizes, exponent):
    embs = _tables(sizes)
    a = wander.walk_sample(embs, 1000, np.random.default_rng(4), exponent,
                           FLOOR, chunk=512)
    b = wander.walk_sample(embs, 1000, np.random.default_rng(4), exponent,
                           FLOOR, chunk=64)
    np.testing.assert_array_equal(a.idx, b.idx)
    np.testing.assert_allclose(a.prob, b.prob, rtol=1e-12)


def test_padded_columns_are_never_drawn():
    """257 columns: the last block holds one real column and 127 of
    padding.  The real one is the rows' nearest, so the block is drawn."""
    rng = np.random.default_rng(5)
    v = _unit(rng, 1, 8)
    e1 = v + 0.1 * _unit(rng, 10, 8)
    e1 /= np.linalg.norm(e1, axis=1, keepdims=True)
    e2 = np.concatenate([_unit(rng, 256, 8), v])
    s = _block_sums(e1, e2, 1.0)
    assert s.shape == (10, 3)
    np.testing.assert_allclose(s[:, 2], _weights(e1, e2, 1.0)[:, 256],
                               rtol=1e-6)
    ws = wander.walk_sample([e1, e2], 5000, np.random.default_rng(6))
    assert ws.idx[:, 1].max() == 256
    assert (ws.idx[:, 1] == 256).sum() > 100
    assert np.all(ws.prob > 0)
    # u * total rounded up to the total still lands on a real column
    pos, share = wander._draw(np.array([1.0]), np.array([[1.0, 1.0, 0.0]]))
    assert pos.tolist() == [1] and share.tolist() == [0.5]


def test_one_program_and_one_upload_per_table_pair(monkeypatch):
    e1, e2 = _tables((50, 1000), d=16, seed=7)
    uploads = []
    device_put = jax.device_put

    def counting(x, *args, **kwargs):
        if getattr(x, "shape", None) == e2.shape:
            uploads.append(x.shape)
        return device_put(x, *args, **kwargs)

    monkeypatch.setattr(jax, "device_put", counting)
    wander._block_sums.clear_cache()
    n_blocks = -(-1000 // wander.BLOCK)
    for calls, n in enumerate((37, 523, 1301), start=1):
        t = {}
        ws = wander.walk_sample([e1, e2], n, np.random.default_rng(n),
                                timings=t)
        assert ws.idx.shape == (n, 2)
        launches = -(-n // 512)
        assert t["walk_launches"] == launches
        assert t["walk_fetch_bytes"] == launches * 512 * n_blocks * 4
        assert 0 < t["walk_blocks_s"] and 0 < t["walk_draw_s"]
        assert len(uploads) == calls
    assert wander._block_sums._cache_size() == 1
