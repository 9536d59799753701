"""Persistent stratification index: bit-identity, delta maintenance, store,
and on-disk IO (``core.index`` + ``checkpoint.index_io``).

The acceptance contract this file pins down:

* hydrating a :class:`~repro.core.index.IndexArtifact` is **bit-identical**
  at fp32 to the fresh sweep it replaces — strata AND end-to-end BAS
  estimates, for the streaming path and for index-routed dense-footprint
  queries, including after a save -> mmap-load round trip;
* :func:`~repro.core.index.append_rows` equals a full recompute **exactly**
  (integer tiles, merged top-k, re-derived content key) over random append
  splits, on the fp32 numpy sweep, the fp32 kernel path, and the int8
  kernel path — the property the paper's build-once/query-many economics
  rest on;
* the content key tracks exactly the quantities that change sweep output
  (tables, binning, weight transform, requested precision) and nothing
  execution-specific (block size, kernel on/off);
* :class:`~repro.core.index.IndexStore` shares one build per key, evicts
  by memory budget, falls back to the on-disk store, and exposes the
  serving counters; corrupt or misplaced on-disk artifacts fail loudly.
"""
import dataclasses
import json
import os
import shutil

import numpy as np
import pytest

from repro.core import (
    Agg,
    BASConfig,
    IndexStore,
    Query,
    append_rows,
    artifact_key,
    build_index,
    run_auto,
    run_bas_streaming,
)
from repro.core.similarity import normalize
from repro.core.stratify import stratify_streaming, sweep_pass
from repro.data import make_clustered_tables

CFG = BASConfig()
BINS = 512


def _tables(n1, n2, d=16, seed=0):
    rng = np.random.default_rng(seed)
    return (
        normalize(rng.standard_normal((n1, d))).astype(np.float32),
        normalize(rng.standard_normal((n2, d))).astype(np.float32),
    )


def _build(embs, **kw):
    kw.setdefault("n_bins", BINS)
    kw.setdefault("exponent", CFG.weight_exponent)
    kw.setdefault("floor", CFG.weight_floor)
    return build_index(list(embs), **kw)


def _assert_artifacts_equal(a, b):
    assert a.key == b.key
    assert a.sizes == b.sizes
    np.testing.assert_array_equal(np.asarray(a.counts), np.asarray(b.counts))
    np.testing.assert_array_equal(np.asarray(a.edges), np.asarray(b.edges))
    np.testing.assert_array_equal(np.asarray(a.block_counts),
                                  np.asarray(b.block_counts))
    if a.topk_vals is not None or b.topk_vals is not None:
        np.testing.assert_array_equal(np.asarray(a.topk_valid),
                                      np.asarray(b.topk_valid))
        valid = np.asarray(a.topk_valid)
        np.testing.assert_array_equal(np.asarray(a.topk_vals)[valid],
                                      np.asarray(b.topk_vals)[valid])
        np.testing.assert_array_equal(np.asarray(a.topk_idx)[valid],
                                      np.asarray(b.topk_idx)[valid])


# ----------------------------------------------------------------------------
# content key
# ----------------------------------------------------------------------------

def test_key_tracks_sweep_inputs_not_execution_details():
    e1, e2 = _tables(40, 50)
    base = artifact_key([e1, e2], BINS, 1.0, 1e-3, "fp32")
    assert base == artifact_key([e1, e2], BINS, 1.0, 1e-3, "fp32")
    # anything that changes sweep output changes the key
    assert base != artifact_key([e2, e1], BINS, 1.0, 1e-3, "fp32")
    assert base != artifact_key([e1, e2], 2 * BINS, 1.0, 1e-3, "fp32")
    assert base != artifact_key([e1, e2], BINS, 2.0, 1e-3, "fp32")
    assert base != artifact_key([e1, e2], BINS, 1.0, 1e-2, "fp32")
    assert base != artifact_key([e1, e2], BINS, 1.0, 1e-3, "int8")
    bumped = e1.copy()
    bumped[0, 0] += 1e-3
    assert base != artifact_key([normalize(bumped), e2], BINS, 1.0, 1e-3,
                                "fp32")
    # execution details (block size, kernel on/off) are NOT key components
    assert (_build([e1, e2], block=32, use_kernel=False).key
            == _build([e1, e2], block=4096, use_kernel=True).key == base)


def test_artifact_check_rejects_mismatched_query():
    e1, e2 = _tables(40, 50)
    art = _build([e1, e2])
    art.check(sizes=(40, 50), n_bins=BINS, exponent=CFG.weight_exponent,
              floor=CFG.weight_floor)
    with pytest.raises(ValueError, match="n_bins"):
        art.check(n_bins=BINS * 2)
    with pytest.raises(ValueError, match="covers tables"):
        art.check(sizes=(41, 50))
    with pytest.raises(ValueError):
        sweep_pass(e1, e2, n_bins=BINS * 2, artifact=art)


# ----------------------------------------------------------------------------
# hydration bit-identity (fp32)
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("use_kernel", [False, True])
def test_hydrated_sweep_is_bit_identical(use_kernel):
    e1, e2 = _tables(150, 130, seed=3)
    art = _build([e1, e2], use_kernel=use_kernel)
    fresh = sweep_pass(e1, e2, n_bins=BINS, exponent=CFG.weight_exponent,
                       floor=CFG.weight_floor, use_kernel=use_kernel)
    hyd = sweep_pass(e1, e2, n_bins=BINS, exponent=CFG.weight_exponent,
                     floor=CFG.weight_floor, artifact=art)
    np.testing.assert_array_equal(np.asarray(hyd.counts),
                                  np.asarray(fresh.counts))
    np.testing.assert_array_equal(np.asarray(hyd.edges),
                                  np.asarray(fresh.edges))
    assert hyd.stats["index_version"] == 1


@pytest.mark.parametrize("use_kernel", [False, True])
def test_hydrated_stratification_matches_fresh(use_kernel):
    e1, e2 = _tables(150, 130, seed=3)
    art = _build([e1, e2], use_kernel=use_kernel)
    budget = 600
    fresh = stratify_streaming(e1, e2, CFG.alpha, budget, CFG, n_bins=BINS,
                               use_kernel=use_kernel)
    hyd = stratify_streaming(e1, e2, CFG.alpha, budget, CFG, n_bins=BINS,
                             artifact=art)
    np.testing.assert_array_equal(fresh.order, hyd.order)
    np.testing.assert_array_equal(fresh.bounds, hyd.bounds)
    np.testing.assert_array_equal(fresh.order_weights, hyd.order_weights)


def test_streaming_estimates_bit_identical_with_index(tmp_path):
    """Fresh sweep, resident artifact, store-resolved artifact, and a
    save -> mmap-load round trip must all land the SAME estimate and CI."""
    ds = make_clustered_tables(130, 130, n_entities=160, noise=0.4, seed=5)

    def q():
        return Query(spec=ds.spec(), agg=Agg.COUNT, oracle=ds.oracle(),
                     budget=1500)

    base = run_bas_streaming(q(), CFG, seed=0, n_bins=BINS)
    embs = [np.asarray(e, np.float32) for e in ds.spec().embeddings]
    art = _build(embs, use_kernel=CFG.use_kernel)
    hyd = run_bas_streaming(q(), CFG, seed=0, n_bins=BINS, artifact=art)
    store = IndexStore()
    cold = run_bas_streaming(q(), CFG, seed=0, n_bins=BINS,
                             index_store=store)
    warm = run_bas_streaming(q(), CFG, seed=0, n_bins=BINS,
                             index_store=store)

    from repro.checkpoint.index_io import load_index, save_index

    save_index(str(tmp_path), art)
    loaded = load_index(str(tmp_path), art.key)
    disk = run_bas_streaming(q(), CFG, seed=0, n_bins=BINS, artifact=loaded)

    for res in (hyd, cold, warm, disk):
        assert res.estimate == base.estimate
        assert res.ci.lo == base.ci.lo and res.ci.hi == base.ci.hi
    # observability: the stratify detail says how the sweep was obtained
    assert "index_hit" not in base.detail["stratify"]
    assert hyd.detail["stratify"]["path"] == "index"
    assert hyd.detail["stratify"]["index_hit"] is True
    assert cold.detail["stratify"]["index_hit"] is False
    assert cold.detail["stratify"]["index_build_ms"] >= 0
    assert warm.detail["stratify"]["index_hit"] is True
    assert disk.detail["stratify"]["index_version"] == 1
    assert disk.detail["stratify"]["delta_blocks"] == 0


def test_run_auto_routes_through_resident_index():
    """Dense-footprint queries route dense on an empty store, but a fresh
    resident artifact overrides the memory model (``streaming-index``) and
    reproduces the plain streaming estimate bit-for-bit."""
    ds = make_clustered_tables(120, 120, n_entities=150, noise=0.4, seed=7)

    def q():
        return Query(spec=ds.spec(), agg=Agg.COUNT, oracle=ds.oracle(),
                     budget=1200)

    store = IndexStore()
    res = run_auto(q(), CFG, seed=0, n_bins=BINS, index_store=store)
    assert res.detail["dispatch"]["path"] == "dense"   # miss stays dense
    assert store.stats()["index_build"] == 0

    embs = [np.asarray(e, np.float32) for e in ds.spec().embeddings]
    store.add(_build(embs, use_kernel=CFG.use_kernel))
    routed = run_auto(q(), CFG, seed=0, n_bins=BINS, index_store=store)
    assert routed.detail["dispatch"]["path"] == "streaming-index"
    plain = run_bas_streaming(q(), CFG, seed=0, n_bins=BINS)
    assert routed.estimate == plain.estimate

    # streaming-routed miss builds through the store -> next query hits
    cfg_small = dataclasses.replace(CFG, max_dense_weight_bytes=1024)
    store2 = IndexStore()
    first = run_auto(q(), cfg_small, seed=0, n_bins=BINS, index_store=store2)
    assert first.detail["dispatch"]["path"] == "streaming"
    assert store2.stats()["index_build"] == 1
    second = run_auto(q(), cfg_small, seed=0, n_bins=BINS, index_store=store2)
    assert second.detail["dispatch"]["path"] == "streaming-index"
    assert first.estimate == second.estimate


# ----------------------------------------------------------------------------
# delta maintenance == full recompute (property, random splits)
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("table", [0, 1])
def test_append_equals_full_recompute_random_splits(use_kernel, table):
    """Property: for random table sizes and split points, building an index
    on a prefix and appending the remainder is EXACTLY a build on the full
    tables — tiles, top-k, and content key.  ``block=32`` forces multiple
    row tiles so boundary-straddling appends are exercised."""
    rng = np.random.default_rng(42 + table + 2 * use_kernel)
    for trial in range(4):
        n1, n2 = int(rng.integers(40, 120)), int(rng.integers(40, 120))
        delta = int(rng.integers(1, 40))
        full = _tables(n1 + (delta if table == 0 else 0),
                       n2 + (delta if table == 1 else 0),
                       seed=int(rng.integers(1 << 30)))
        prefix = [full[0][:n1], full[1][:n2]]
        art = _build(prefix, block=32, use_kernel=use_kernel)
        grown = append_rows(art, table, full[table][-delta:],
                            use_kernel=use_kernel)
        ref = _build(list(full), block=32, use_kernel=use_kernel)
        _assert_artifacts_equal(grown, ref)
        assert grown.version == 2 and grown.stats["appends"] == 1
        assert grown.stats["delta_rows"] == delta


def test_append_equals_full_recompute_int8():
    """The low-precision (int8 kernel) tiles must obey the same exactness:
    the delta sweep quantises identically, so appended tiles equal a full
    int8 recompute.  ``tolerance=inf`` pins the effective precision to int8
    on both sides (no fp32 fallback)."""
    rng = np.random.default_rng(11)
    for trial in range(2):
        n1, n2 = int(rng.integers(48, 100)), int(rng.integers(48, 100))
        delta = int(rng.integers(4, 32))
        full = _tables(n1, n2 + delta, seed=int(rng.integers(1 << 30)))
        prefix = [full[0], full[1][:n2]]
        art = _build(prefix, block=32, use_kernel=True, precision="int8",
                     tolerance=float("inf"))
        assert art.precision == "int8"
        grown = append_rows(art, 1, full[1][-delta:], use_kernel=True)
        ref = _build(list(full), block=32, use_kernel=True, precision="int8",
                     tolerance=float("inf"))
        _assert_artifacts_equal(grown, ref)


def test_append_lowp_without_kernel_refuses():
    """A lowp artifact whose delta could only run the fp32 numpy sweep
    must refuse rather than silently mix precisions across tiles."""
    e1, e2 = _tables(64, 64)
    art = _build([e1, e2], use_kernel=True, precision="int8",
                 tolerance=float("inf"))
    with pytest.raises(RuntimeError, match="without the sweep kernel"), \
            pytest.warns(UserWarning, match="numpy sweep"):
        append_rows(art, 1, _tables(8, 8, seed=9)[1], use_kernel=False)


def test_append_chain_artifact_not_supported():
    e1, e2 = _tables(32, 32)
    e3 = _tables(32, 32, seed=2)[0]
    art = _build([e1, e2, e3], use_kernel=False)
    with pytest.raises(NotImplementedError):
        append_rows(art, 1, e3[:4])


def test_stale_artifact_no_longer_matches_after_append():
    """Freshness is structural: once the live tables grow, the old
    artifact's key stops matching, so lookups miss instead of serving a
    stale sweep."""
    e1, e2 = _tables(60, 60)
    store = IndexStore()
    art, hit = store.get_or_build([e1, e2], n_bins=BINS)
    assert not hit
    extra = _tables(8, 8, seed=3)[1]
    grown_tables = [e1, np.concatenate([e2, extra])]
    assert store.lookup(grown_tables, n_bins=BINS) is None
    grown = append_rows(art, 1, extra, use_kernel=CFG.use_kernel)
    store.add(grown)
    found = store.lookup(grown_tables, n_bins=BINS)
    assert found is not None and found.version == 2
    assert store.stats()["delta_blocks"] == grown.stats["last_delta_blocks"]


# ----------------------------------------------------------------------------
# IndexStore behaviour
# ----------------------------------------------------------------------------

def test_store_shares_one_build_and_counts():
    e1, e2 = _tables(60, 60)
    store = IndexStore()
    a1, hit1 = store.get_or_build([e1, e2], n_bins=BINS)
    a2, hit2 = store.get_or_build([e1, e2], n_bins=BINS)
    assert (hit1, hit2) == (False, True) and a1 is a2
    s = store.stats()
    assert s["index_build"] == 1 and s["index_hit"] == 1
    assert s["index_miss"] == 1 and s["index_bytes"] == a1.nbytes
    # lookup never builds and never counts a miss
    other = _tables(30, 30, seed=9)
    assert store.lookup(list(other), n_bins=BINS) is None
    assert store.stats()["index_miss"] == 1


def test_store_evicts_lru_under_memory_budget():
    e1, e2 = _tables(60, 60, seed=0)
    probe = build_index([e1, e2], n_bins=BINS)
    store = IndexStore(max_bytes=int(probe.nbytes * 1.5))
    store.get_or_build([e1, e2], n_bins=BINS)
    f1, f2 = _tables(60, 60, seed=1)
    store.get_or_build([f1, f2], n_bins=BINS)      # evicts the first
    assert store.stats()["index_evict"] == 1
    assert store.lookup([e1, e2], n_bins=BINS) is None
    assert store.lookup([f1, f2], n_bins=BINS) is not None
    assert store.bytes_resident <= store.max_bytes


def test_store_loads_from_disk_root(tmp_path):
    from repro.checkpoint.index_io import save_index

    e1, e2 = _tables(60, 60)
    art = _build([e1, e2], use_kernel=CFG.use_kernel)
    save_index(str(tmp_path), art)
    store = IndexStore(root=str(tmp_path))
    got, hit = store.get_or_build([e1, e2], n_bins=BINS,
                                  exponent=CFG.weight_exponent,
                                  floor=CFG.weight_floor)
    assert not hit and got.key == art.key
    s = store.stats()
    assert s["index_load"] == 1 and s["index_build"] == 0
    np.testing.assert_array_equal(np.asarray(got.counts), art.counts)


# ----------------------------------------------------------------------------
# on-disk IO: roundtrip, versioning, corruption
# ----------------------------------------------------------------------------

def test_index_io_roundtrip_and_versions(tmp_path):
    from repro.checkpoint.index_io import (latest_version, list_indexes,
                                           load_index, save_index)

    root = str(tmp_path)
    e1, e2 = _tables(70, 60)
    art = _build([e1, e2], use_kernel=CFG.use_kernel)
    save_index(root, art)
    got = load_index(root, art.key)
    _assert_artifacts_equal(got, art)
    for s in ("version", "n_bins", "exponent", "floor", "precision",
              "precision_requested", "kernel", "block_rows"):
        assert getattr(got, s) == getattr(art, s), s
    assert isinstance(got.counts, np.memmap)   # zero-copy read

    # append -> v2 next to v1; loader picks newest, explicit version works
    extra = _tables(8, 8, seed=4)[1]
    v2 = append_rows(art, 1, extra, use_kernel=CFG.use_kernel)
    save_index(root, v2)
    assert latest_version(root, art.key) == 1   # old lineage untouched
    assert latest_version(root, v2.key) == 2    # version follows the lineage
    listed = list_indexes(root)
    assert sorted(x["key"] for x in listed) == sorted({art.key, v2.key})
    assert load_index(root, v2.key).sizes == (70, 68)

    # same-key versions prune beyond keep_last
    same = load_index(root, art.key, mmap=False)
    for v in (2, 3, 4):
        same = dataclasses.replace(same, version=v)
        save_index(root, same, keep_last=2)
    assert latest_version(root, art.key) == 4
    with pytest.raises(FileNotFoundError):
        load_index(root, art.key, version=1)    # pruned
    assert load_index(root, art.key, version=3).version == 3


def test_index_io_corruption_fails_loudly(tmp_path):
    from repro.checkpoint.index_io import load_index, save_index

    root = str(tmp_path)
    e1, e2 = _tables(50, 50)
    art = _build([e1, e2], use_kernel=CFG.use_kernel)
    d = save_index(root, art)

    with pytest.raises(FileNotFoundError):
        load_index(root, "deadbeef" * 8)

    # manifest/file shape mismatch (backup kept outside the store tree)
    bak = os.path.join(str(tmp_path), "bak")
    shutil.copytree(d, bak)
    np.save(os.path.join(d, "counts.npy"), np.zeros(10))
    with pytest.raises(ValueError, match="counts"):
        load_index(root, art.key)
    shutil.rmtree(d)
    shutil.copytree(bak, d)

    # missing array
    os.remove(os.path.join(d, "edges.npy"))
    with pytest.raises(ValueError, match="edges"):
        load_index(root, art.key)
    shutil.rmtree(d)
    shutil.copytree(bak, d)

    # artifact misfiled under another key's directory
    wrong = os.path.join(root, "0" * 64)
    shutil.copytree(os.path.join(root, art.key), wrong)
    with pytest.raises(ValueError, match="does not match"):
        load_index(root, "0" * 64)

    # format bump
    meta_path = os.path.join(d, "meta.json")
    with open(meta_path) as f:
        meta = json.load(f)
    meta["format"] = 999
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    with pytest.raises(ValueError, match="format"):
        load_index(root, art.key)

    # a torn write (.tmp_ dir) is never visible
    shutil.rmtree(d)
    shutil.copytree(bak, d)
    os.makedirs(os.path.join(root, art.key, ".tmp_00000002"))
    assert load_index(root, art.key).version == 1


# ----------------------------------------------------------------------------
# service observability
# ----------------------------------------------------------------------------

def test_oracle_service_stats_carry_index_counters():
    from repro.serve.oracle_service import OracleService

    e1, e2 = _tables(50, 50)
    store = IndexStore()
    with OracleService(workers=1, index_store=store) as svc:
        base = svc.stats()
        assert base["index_hit"] == 0 and base["index_miss"] == 0
        store.get_or_build([e1, e2], n_bins=BINS)
        store.get_or_build([e1, e2], n_bins=BINS)
        s = svc.stats()
    assert s["index_hit"] == 1 and s["index_build"] == 1
    assert s["index_bytes"] > 0
    with OracleService(workers=1) as svc:   # no store -> no index keys
        assert "index_hit" not in svc.stats()
