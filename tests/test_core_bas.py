import numpy as np
import pytest

from repro.core import (
    Agg,
    Query,
    run_bas,
    run_uniform,
)
from repro.core.oracle import BudgetExceeded
from repro.data import make_clustered_tables, make_syn_scores


@pytest.fixture(scope="module")
def ds():
    return make_clustered_tables(250, 250, n_entities=400, noise=0.4, seed=7)


def make_query(ds, agg, budget=4000, g=None):
    return Query(spec=ds.spec(), agg=agg, oracle=ds.oracle(), budget=budget, g=g)


def test_bas_exact_when_budget_covers_space():
    ds = make_clustered_tables(40, 40, n_entities=60, noise=0.3, seed=1)
    q = make_query(ds, Agg.COUNT, budget=40 * 40 + 10)
    res = run_bas(q, seed=0)
    assert res.estimate == ds.truth.sum()
    assert res.ci.width == 0.0


def test_bas_budget_never_exceeded(ds):
    for seed in range(3):
        q = make_query(ds, Agg.COUNT, budget=1500)
        res = run_bas(q, seed=seed)
        assert res.oracle_calls <= 1500


def test_bas_count_close_and_covered(ds):
    truth = float(ds.truth.sum())
    hits, errs = 0, []
    n_rep = 8
    for seed in range(n_rep):
        q = make_query(ds, Agg.COUNT, budget=5000)
        res = run_bas(q, seed=seed)
        errs.append(abs(res.estimate - truth) / truth)
        hits += res.ci.contains(truth)
    assert np.mean(errs) < 0.5
    assert hits >= n_rep - 2  # 95% nominal; allow slack at 8 reps


def test_bas_sum_and_avg(ds):
    g_col = ds.columns1["value"]

    def g(idx):
        return g_col[idx[:, 0]]

    m = ds.truth > 0
    truth_sum = float((g_col[:, None] * ds.truth)[m].sum())
    truth_avg = truth_sum / ds.truth.sum()
    q = make_query(ds, Agg.SUM, budget=6000, g=g)
    rs = run_bas(q, seed=0)
    assert abs(rs.estimate - truth_sum) / truth_sum < 0.6
    q = make_query(ds, Agg.AVG, budget=6000, g=g)
    ra = run_bas(q, seed=0)
    assert abs(ra.estimate - truth_avg) / truth_avg < 0.5


def test_bas_extremes_and_median(ds):
    g_col = ds.columns1["value"]

    def g(idx):
        return g_col[idx[:, 0]]

    vals = np.broadcast_to(g_col[:, None], ds.truth.shape)[ds.truth > 0]
    q = make_query(ds, Agg.MAX, budget=6000, g=g)
    q.g_bounds = (float(g_col.min()), float(g_col.max()))
    rmax = run_bas(q, seed=0)
    assert rmax.estimate <= vals.max() + 1e-9   # observed max never exceeds truth
    assert rmax.estimate >= np.quantile(vals, 0.5)  # and should find a high one
    assert rmax.ci.hi >= vals.max()             # CI upper bound = global bound
    q = make_query(ds, Agg.MEDIAN, budget=6000, g=g)
    rmed = run_bas(q, seed=0)
    assert np.quantile(vals, 0.05) <= rmed.estimate <= np.quantile(vals, 0.95)


def test_bas_beats_uniform_on_low_selectivity():
    ds = make_syn_scores(400, 400, selectivity=2e-3, fnr=0.1, fpr=0.1, seed=3)
    truth = float(ds.truth.sum())
    w = ds.weights_override
    bas_err, uni_err = [], []
    for seed in range(6):
        qb = Query(spec=ds.spec(), agg=Agg.COUNT, oracle=ds.oracle(), budget=4000)
        rb = run_bas(qb, seed=seed, weights=w)
        qu = Query(spec=ds.spec(), agg=Agg.COUNT, oracle=ds.oracle(), budget=4000)
        ru = run_uniform(qu, seed=seed)
        bas_err.append((rb.estimate - truth) ** 2)
        uni_err.append((ru.estimate - truth) ** 2)
    assert np.sqrt(np.mean(bas_err)) < np.sqrt(np.mean(uni_err))


def test_oracle_ledger_blocks_overspend():
    ds = make_clustered_tables(50, 50, n_entities=60, noise=0.3, seed=2)
    oracle = ds.oracle()
    oracle.set_budget(10)
    with pytest.raises(BudgetExceeded):
        oracle.label(np.stack([np.arange(20), np.arange(20)], axis=1))


def test_oracle_cache_free_requeries():
    ds = make_clustered_tables(50, 50, n_entities=60, noise=0.3, seed=2)
    oracle = ds.oracle()
    oracle.set_budget(10)
    idx = np.stack([np.arange(10), np.arange(10)], axis=1)
    oracle.label(idx)
    assert oracle.calls == 10
    oracle.label(idx)  # cached: no budget movement, no exception
    assert oracle.calls == 10
    assert oracle.requests == 20


def test_streaming_bas_matches_dense_and_scales():
    """The O(N1+N2+b) streaming path (histogram stratification via the
    sim_sweep kernel + walk/rejection D_0 sampling) agrees with the dense path
    and stays within budget on a cross product we never materialise."""
    from repro.core import run_bas_streaming

    ds = make_clustered_tables(400, 500, n_entities=700, noise=0.5, seed=13)
    truth = float(ds.truth.sum())
    budget = 8000
    errs_d, errs_s, covered = [], [], 0
    n_rep = 4
    for seed in range(n_rep):
        qd = make_query(ds, Agg.COUNT, budget=budget)
        rd = run_bas(qd, seed=seed)
        qs = make_query(ds, Agg.COUNT, budget=budget)
        rs = run_bas_streaming(qs, seed=seed, use_kernel=True)
        assert rs.oracle_calls <= budget
        errs_d.append(abs(rd.estimate - truth) / truth)
        errs_s.append(abs(rs.estimate - truth) / truth)
        covered += rs.ci.contains(truth)
    # streaming is statistically comparable to dense (same design)
    assert np.mean(errs_s) < max(2.5 * np.mean(errs_d), 0.30)
    assert covered >= n_rep - 2


def test_streaming_bas_sum():
    from repro.core import run_bas_streaming

    ds = make_clustered_tables(300, 300, n_entities=500, noise=0.5, seed=14)
    g_col = ds.columns1["value"]
    g = lambda idx: g_col[idx[:, 0]]  # noqa: E731
    m = ds.truth > 0
    truth_sum = float((g_col[:, None] * ds.truth)[m].sum())
    q = make_query(ds, Agg.SUM, budget=7000, g=g)
    res = run_bas_streaming(q, seed=0)
    assert abs(res.estimate - truth_sum) / truth_sum < 0.6
