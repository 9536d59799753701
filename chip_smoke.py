"""Chip smoke test: the served BaS join query on a TPU, end to end.

    python3 chip_smoke.py [--seed N]        # one chip: sweep, oracle, query
    python3 chip_smoke.py --chips 4         # four chips: data-parallel oracle

Everything is built in this one process from ``--seed``: a DBLP-Scholar-sized
entity-matching join (Koepcke, Thor and Rahm, PVLDB 2010: 2,616 x 64,263
records, ~5,347 matches; d=384, the width of ``configs/joinml_embedder.py``)
with a record string per row, and the ``joinml-oracle`` pair scorer at its
published widths with random weights.  Phases:

1. device — prints platform, device kind, device count and the JAX version;
   exits 2 unless the first device is a TPU.  There is no CPU fallback.
2. sweep  — ``stratify.sweep_pass`` on the ``sim_sweep`` kernel at fp32,
   compared with a float64 numpy reference on the same tables.
3. oracle — the served ``PairScorer``'s last-position yes/no logits against
   a plain ``jax.numpy`` f32 forward at ``default_matmul_precision("highest")``.
4. query  — 4 concurrent ``COUNT`` queries at ``ORACLE BUDGET 20000`` through
   ``JoinMLEngine.execute`` -> ``OracleService`` -> ``ModelOracle`` ->
   ``PairScorer``.

``--chips 4`` runs only the data-parallel scorer (the launcher's ``--shard``)
against the one-device scorer on the same pairs.  Every check raises on
failure; the last line of stdout, printed only when all passed, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import numpy as np  # noqa: E402

from repro.launch.serve import PAIR_LEN, load_model, make_scorer  # noqa: E402

# DBLP-Scholar cardinalities; n_entities sets E[matches] = n1 * n2 / n_entities
JOIN = dict(n1=2616, n2=64263, d=384, n_entities=31440, noise=1.0)
ORACLE = "joinml-oracle"
BATCH = 32        # scorer batch (the compile test's B)
N_BINS = 4096
QUERY_BUDGET = 20000
N_QUERIES = 4

# Bounds of the sweep against the float64 reference.  f32 scores carry
# ~1e-7 absolute error: a pair moves at most one 1/4096-wide bin, so the
# CDF moves by the pairs within ~1e-7 of an edge, the count tiles stay
# inside the 2-bin margin the rescans rely on (stratify.SweepInfo.
# blocks_over), and the walk sums keep the 1e-6 relative contract of
# tests/test_chain_stats.py.  A bf16-precision product (~4e-3 error) fails
# the value, CDF and row-sum bounds.
SWEEP_BOUNDS = dict(cdf_shift=1e-4, tiles_outside_margin=0,
                    topk_agreement=0.99, topk_value_dev=1e-5,
                    row_sum_rel=1e-6)
# The served scorer computes in bf16 (the config's dtype); the reference in
# f32 from the same bf16-rounded weights.  Logit deviation bound: absolute,
# scaled by the reference's logit magnitude (see _oracle_bound).
ORACLE_REL = 0.05


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def make_join(seed: int, n1: int, n2: int, d: int, n_entities: int,
              noise: float):
    """Seeded clustered tables plus one record string per row and table."""
    from repro.data import make_clustered_tables
    from repro.data.pipeline import entity_records

    ds = make_clustered_tables(n1, n2, d=d, n_entities=n_entities,
                               noise=noise, seed=seed)
    left = entity_records(ds.ids1, n_entities, seed, table=0)
    right = entity_records(ds.ids2, n_entities, seed, table=1)
    return ds, left, right


# ---------------------------------------------------------------------------
# sweep
# ---------------------------------------------------------------------------

def reference_sweep(e1, e2, n_bins: int, block_rows: int, k: int,
                    exponent: float = 1.0, floor: float = 1e-3):
    """Float64 numpy reference of the sweep outputs: count tiles per
    ``block_rows`` left rows, per-row top-k (values, column ids) of the
    clipped score, and the walk row sums."""
    e2t = np.asarray(e2, np.float64).T
    n1 = e1.shape[0]
    tiles = np.zeros((-(-n1 // block_rows), n_bins), np.int64)
    vals = np.empty((n1, k))
    idx = np.empty((n1, k), np.int64)
    row_sums = np.empty(n1)
    for g, s in enumerate(range(0, n1, block_rows)):
        rows = slice(s, s + block_rows)
        sc = np.clip(np.asarray(e1[rows], np.float64) @ e2t, 0.0, 1.0)
        w = np.maximum(sc, floor) ** exponent
        row_sums[rows] = w.sum(axis=1)
        b = np.clip((w * n_bins).astype(np.int64), 0, n_bins - 1)
        tiles[g] = np.bincount(b.reshape(-1), minlength=n_bins)
        top = np.argpartition(-sc, k - 1, axis=1)[:, :k]
        tv = np.take_along_axis(sc, top, axis=1)
        order = np.argsort(-tv, axis=1, kind="stable")
        vals[rows] = np.take_along_axis(tv, order, axis=1)
        idx[rows] = np.take_along_axis(top, order, axis=1)
    return tiles, vals, idx, row_sums


def sweep_deviations(info, ref, margin: int = 2) -> dict:
    """Largest deviations of a kernel SweepInfo from ``reference_sweep``."""
    tiles, vals, idx, row_sums = ref
    total = max(int(tiles.sum()), 1)
    cdf = np.abs(np.cumsum(info.counts) - np.cumsum(tiles.sum(axis=0)))
    # per tile: mass at >= bin b must lie between the reference's mass at
    # >= b + margin and at >= b - margin
    ge_k = np.cumsum(info.block_counts[:, ::-1], axis=1)[:, ::-1]
    ge_r = np.cumsum(tiles[:, ::-1], axis=1)[:, ::-1]
    pad = np.zeros((tiles.shape[0], margin), np.int64)
    ge_r_hi = np.concatenate([ge_r[:, margin:], pad], axis=1)
    ge_r_lo = np.concatenate([np.repeat(ge_r[:, :1], margin, axis=1),
                              ge_r[:, :-margin]], axis=1)
    bad = (ge_k < ge_r_hi) | (ge_k > ge_r_lo)
    k_vals, k_idx, k_valid = info.topk
    kk = k_vals.shape[1]
    agree = np.array([
        len(set(k_idx[i][k_valid[i]].tolist()) & set(idx[i, :kk].tolist()))
        / kk for i in range(len(k_idx))
    ])
    rs = info.row_sums[0]
    return dict(
        cdf_shift=float(cdf.max()) / total,
        tiles_outside_margin=int(bad.any(axis=1).sum()),
        topk_agreement=float(agree.mean()),
        topk_agreement_min=float(agree.min()),
        topk_value_dev=float(np.abs(k_vals - vals[:, :kk]).max()),
        row_sum_rel=float(np.max(np.abs(rs - row_sums) / row_sums)),
    )


def phase_sweep(e1, e2, n_bins: int = N_BINS) -> dict:
    from repro.core.stratify import TOPK_CANDIDATES, sweep_pass

    t0 = time.perf_counter()
    info = sweep_pass(e1, e2, n_bins=n_bins, use_kernel=True,
                      precision="fp32", k_top=TOPK_CANDIDATES)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    sweep_pass(e1, e2, n_bins=n_bins, use_kernel=True, precision="fp32",
               k_top=TOPK_CANDIDATES)
    warm_s = time.perf_counter() - t0
    check(info.kernel is True, f"sweep ran off the kernel: {info.kernel}")
    check(info.topk is not None, "sweep returned no top-k")
    log(f"sweep: kernel={info.kernel} block_rows={info.block_rows} "
        f"first call (with compile) {first_s:.3f}s, warm {warm_s:.3f}s")
    ref = reference_sweep(e1, e2, n_bins, info.block_rows,
                          info.topk[0].shape[1])
    dev = sweep_deviations(info, ref)
    log("sweep deviations vs f64: " + json.dumps(dev))
    log("sweep bounds: " + json.dumps(SWEEP_BOUNDS))
    check(dev["cdf_shift"] <= SWEEP_BOUNDS["cdf_shift"], "histogram CDF")
    check(dev["tiles_outside_margin"] <= SWEEP_BOUNDS["tiles_outside_margin"],
          "count tiles outside the 2-bin margin")
    check(dev["topk_agreement"] >= SWEEP_BOUNDS["topk_agreement"],
          "top-k set agreement")
    check(dev["topk_value_dev"] <= SWEEP_BOUNDS["topk_value_dev"],
          "top-k values")
    check(dev["row_sum_rel"] <= SWEEP_BOUNDS["row_sum_rel"], "row sums")
    return dict(dev, sweep_first_s=first_s, sweep_warm_s=warm_s)


# ---------------------------------------------------------------------------
# oracle
# ---------------------------------------------------------------------------

def reference_yes_no_logits(cfg, params, toks, last, yes: int, no: int):
    """Plain jax.numpy f32 forward of a dense, tied-embedding, SwiGLU
    decoder with RoPE — written out here, independent of ``repro.models`` —
    returning the (B, 2) [yes, no] logits at each row's ``last`` position."""
    import jax
    import jax.numpy as jnp

    assert cfg.family == "dense" and cfg.tied_embeddings and cfg.act == "silu"
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    b, s = toks.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pos = np.arange(s)
    inv = 1.0 / cfg.rope_theta ** (np.arange(0, hd, 2) / hd)
    cos = f32(np.cos(pos[:, None] * inv))[None, :, None, :]
    sin = f32(np.sin(pos[:, None] * inv))[None, :, None, :]
    causal = f32(pos[:, None] >= pos[None, :]) > 0

    def norm(x, w):
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                              + cfg.norm_eps)
        return x * (1.0 + f32(w))

    def rope(t):
        t1, t2 = jnp.split(t, 2, axis=-1)
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)

    with jax.default_matmul_precision("highest"):
        embed = f32(params["embed"])
        x = embed[jnp.asarray(toks)]
        for layer in range(cfg.num_layers):
            p = jax.tree.map(lambda a: f32(a[layer]), params["layers"])
            a = norm(x, p["ln1"])
            q = rope((a @ p["attn"]["wq"]).reshape(b, s, h, hd))
            k = rope((a @ p["attn"]["wk"]).reshape(b, s, kvh, hd))
            v = (a @ p["attn"]["wv"]).reshape(b, s, kvh, hd)
            k, v = (jnp.repeat(t, h // kvh, axis=2) for t in (k, v))
            sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd**-0.5
            att = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, s, h * hd)
            x = x + o @ p["attn"]["wo"]
            m = norm(x, p["ln2"])
            x = x + (jax.nn.silu(m @ p["mlp"]["w_gate"])
                     * (m @ p["mlp"]["w_up"])) @ p["mlp"]["w_down"]
        x = norm(x, params["ln_f"])[jnp.arange(b), jnp.asarray(last)]
        logits = x @ embed.T
    return np.asarray(jnp.stack([logits[:, yes], logits[:, no]], 1),
                      np.float64)


def _oracle_bound(ref: np.ndarray) -> float:
    """bf16 activations (8 mantissa bits) over the layer stack: allow
    ``ORACLE_REL`` of the reference logits' largest magnitude."""
    return ORACLE_REL * max(float(np.abs(ref).max()), 1.0)


def phase_oracle(scorer, n_left: int, n_right: int, seed: int,
                 batches: int = 2) -> dict:
    rng = np.random.default_rng(seed)
    worst, bound = 0.0, float("inf")
    for _ in range(batches):
        pairs = np.stack([rng.integers(0, n_left, scorer.batch_size),
                          rng.integers(0, n_right, scorer.batch_size)], 1)
        toks, last = scorer.encode(pairs, PAIR_LEN)
        got = np.asarray(scorer.yes_no_logits(toks, last), np.float64)
        want = reference_yes_no_logits(scorer.cfg, scorer.params, toks, last,
                                       scorer.yes_id, scorer.no_id)
        check(got.shape == want.shape == (scorer.batch_size, 2),
              f"logit shapes {got.shape} {want.shape}")
        check(bool(np.isfinite(got).all()), "non-finite scorer logits")
        worst = max(worst, float(np.abs(got - want).max()))
        bound = min(bound, _oracle_bound(want))
    log(f"oracle: {batches} batches of {scorer.batch_size} pairs, worst "
        f"|logit - f32 reference| = {worst:.6g}, bound {bound:.6g}")
    check(worst <= bound, "oracle logits beyond the bound")
    return dict(oracle_logit_dev=worst, oracle_bound=bound)


def warm_scorer(scorer) -> float:
    """Compile the scorer for every padded length it will see."""
    t0 = time.perf_counter()
    for pad_len in scorer._buckets:
        toks = np.zeros((scorer.batch_size, int(pad_len)), np.int32)
        np.asarray(scorer.yes_no_logits(toks, np.zeros(scorer.batch_size,
                                                       np.int32)))
    return time.perf_counter() - t0


# ---------------------------------------------------------------------------
# query
# ---------------------------------------------------------------------------

def phase_query(e1, e2, scorer, seed: int, queries: int = N_QUERIES,
                budget: int = QUERY_BUDGET, bas_cfg=None) -> dict:
    from repro.core import BASConfig, Catalog, JoinMLEngine, ModelOracle, Table
    from repro.serve.oracle_service import OracleService, serve_queries

    catalog = Catalog()
    catalog.register(Table("dblp", e1))
    catalog.register(Table("scholar", e2))
    sql = ("SELECT COUNT(*) FROM dblp JOIN scholar "
           "ON NL('the two records describe the same publication') "
           f"ORACLE BUDGET {budget} WITH PROBABILITY 0.95")
    mine = threading.local()   # the oracle the current query thread built
    with OracleService(max_wait_ms=8.0) as svc:
        def factory(nl, names):
            mine.oracle = ModelOracle(scorer, threshold=0.5, name=ORACLE)
            svc.attach(mine.oracle)
            return mine.oracle

        engine = JoinMLEngine(catalog, factory, cfg=bas_cfg or BASConfig())

        def job(i: int):
            t0 = time.perf_counter()
            try:
                res = engine.execute(sql, seed=seed + i)
            finally:
                svc.detach(mine.oracle)
            return res, mine.oracle.calls, time.perf_counter() - t0

        t0 = time.perf_counter()
        out = serve_queries(svc, [lambda i=i: job(i) for i in range(queries)])
        wall = time.perf_counter() - t0
    for i, (res, calls, secs) in enumerate(out):
        tel = res.telemetry
        log(f"query {i}: path={tel.dispatch.path} "
            f"kernel={tel.stratify.extra.get('kernel')} calls={calls} "
            f"estimate={res.estimate:.6g} ci=[{res.ci.lo:.6g}, "
            f"{res.ci.hi:.6g}] seconds={secs:.3f}")
        check(tel.dispatch.path == "streaming", f"q{i} dispatch path")
        check(tel.stratify.extra.get("kernel") is True, f"q{i} off the kernel")
        check(0 < calls <= budget, f"q{i} oracle calls {calls}")
        check(bool(np.isfinite([res.estimate, res.ci.lo, res.ci.hi]).all()),
              f"q{i} estimate/CI not finite")
    log(f"queries: {queries} concurrent in {wall:.3f}s, "
        f"{scorer.forward_batches} scorer batches so far")
    return dict(query_s=wall)


# ---------------------------------------------------------------------------
# four chips: the data-parallel scorer
# ---------------------------------------------------------------------------

def phase_data_parallel(cfg, params, tok, left, right, seed: int,
                        batch: int = BATCH) -> dict:
    import jax

    n_dev = len(jax.devices())
    one = make_scorer(cfg, params, tok, left, right, batch_size=batch)
    dp = make_scorer(cfg, params, tok, left, right, batch_size=batch,
                     shard=True)
    rng = np.random.default_rng(seed)
    pairs = np.stack([rng.integers(0, len(left), 4 * batch),
                      rng.integers(0, len(right), 4 * batch)], 1)
    toks, last = dp.encode(pairs[:dp.batch_size], PAIR_LEN)
    out = dp.yes_no_logits(toks, last)
    shards = out.addressable_shards
    devices = {s.device.id for s in shards}
    log(f"data-parallel: output sharding {out.sharding}, shards "
        + ", ".join(f"dev{s.device.id}:{s.index[0]}" for s in shards))
    check(len(out.sharding.device_set) == n_dev, "output spans too few devices")
    check(len(devices) == n_dev, "shards share a device")
    check(all(s.data.shape[0] == dp.batch_size // n_dev for s in shards),
          "a device holds more than its share of the batch")
    ref_one = np.asarray(one.yes_no_logits(toks, last), np.float64)
    dev = float(np.abs(np.asarray(out, np.float64) - ref_one).max())
    p_one, p_dp = one.score(pairs), dp.score(pairs)
    p_dev = float(np.abs(p_one - p_dp).max())
    bound = _oracle_bound(ref_one)
    log(f"data-parallel vs one device: |logit| dev {dev:.6g}, |prob| dev "
        f"{p_dev:.6g} over {len(pairs)} pairs, bound {bound:.6g}")
    check(dev <= bound and p_dev <= bound, "data-parallel disagrees")
    return dict(dp_logit_dev=dev, dp_prob_dev=p_dev)


# ---------------------------------------------------------------------------

def device_info() -> dict:
    import jax

    d = jax.devices()[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}
    log(f"device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']} jax={jax.__version__}")
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel scorer phase")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache(HERE)
    dev = device_info()
    if dev["platform"] != "tpu":
        print("chip_smoke.py needs a TPU; found "
              f"{dev['platform']!r}", file=sys.stderr)
        return 2
    check(dev["count"] == args.chips,
          f"--chips {args.chips} but {dev['count']} device(s)")
    log(f"compile cache: {cache}")

    t0 = time.perf_counter()
    ds, left, right = make_join(args.seed, **JOIN)
    log(f"join: {len(left)} x {len(right)} records, d={JOIN['d']}, "
        f"{int(ds.truth.sum())} matches, built in "
        f"{time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    cfg, params, tok = load_model(ORACLE, "published", args.seed)
    model_s = time.perf_counter() - t0
    log(f"oracle model: {cfg.name} {cfg.param_count() / 1e6:.1f}M params, "
        f"d={cfg.d_model} L={cfg.num_layers} V={cfg.vocab_size} "
        f"({cfg.dtype}), init {model_s:.1f}s")

    if args.chips == 4:
        phase_data_parallel(cfg, params, tok, left, right, args.seed)
    else:
        e1, e2 = ds.emb1, ds.emb2
        scorer = make_scorer(cfg, params, tok, left, right, batch_size=BATCH)
        warm_s = warm_scorer(scorer)
        sweep = phase_sweep(e1, e2)
        log(f"setup seconds (information): model init {model_s:.3f}, "
            f"scorer compile+first call {warm_s:.3f}, sweep first call "
            f"{sweep['sweep_first_s']:.3f}; total "
            f"{model_s + warm_s + sweep['sweep_first_s']:.3f}")
        phase_oracle(scorer, len(left), len(right), args.seed)
        q = phase_query(e1, e2, scorer, args.seed)
        log(f"query seconds (information): {q['query_s']:.3f}")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
