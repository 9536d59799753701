"""Serving launcher: continuous-batching decode, batched pair scoring (the
Oracle endpoint), the in-process multi-query oracle service, or one role of
a multi-host serving fleet, for a given --arch on the host devices.

In-process modes::

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --mode decode --requests 8
    PYTHONPATH=src python -m repro.launch.serve --arch joinml-oracle \
        --mode score --pairs 64
    PYTHONPATH=src python -m repro.launch.serve --arch joinml-oracle \
        --mode service --queries 4 --budget 300
    # the scorer at its published widths (random weights from --seed)
    PYTHONPATH=src python -m repro.launch.serve --arch joinml-oracle \
        --width published --mode score --pairs 256

Multi-host modes (see docs/serving.md for the topology)::

    # host A: a worker (serves its scorer over TCP, no downstream)
    ... serve --mode worker --port 7432
    # host B: the front server; shards super-batches over itself + host A
    ... serve --mode server --port 7431 --worker-hosts hostA:7432
    # any host: a client process running BAS queries against the fleet
    ... serve --mode client --connect hostB:7431 --queries 4 --budget 300

``--mode service`` runs concurrent BAS queries against ONE served scorer
through an :class:`repro.serve.oracle_service.OracleService`: each query's
pilot/blocking/top-up flushes coalesce across queries into super-batches,
and with ``--shard`` every super-batch additionally shards its batch
dimension over the host mesh (``launch.sharding.data_parallel``).
``--mode server|worker`` expose exactly that machinery over TCP
(:class:`repro.serve.transport.OracleServiceServer`); ``--mode client``
runs the same BAS queries through :class:`repro.serve.transport.RemoteOracle`
— plan/commit stay client-side, only labelling crosses the network.
``--label-store-mb``/``--label-store-root`` give the service/server/worker
modes a shared cross-query label store (charge-once oracle caching, see
``repro.serve.label_store``); shutdown prints window fill/dedup ratios and
the store hit rate from the unified ``snapshot()`` surface.  ``--tracker
memory|jsonl`` attaches a :mod:`repro.obs` metrics tracker (JSON-lines
output via ``--tracker-out``), and ``--deadline-ms`` puts the service-mode
queries under deadline-based admission control (docs/serving.md).

Index maintenance modes (no model; see ``repro.core.index``)::

    # one cold sweep -> content-addressed artifact under --index-root
    ... serve --mode build-index --index-root runs/index --n-side 256
    # append rows to one table, version-bumped delta maintenance
    ... serve --mode refresh-index --index-root runs/index \
        --append-rows 32 --append-table 1

``--mode build-index`` builds a persistent stratification index (one fused
sweep) over ``--tables`` (comma-separated ``.npy`` embedding files) or the
synthetic demo pair, and saves it atomically.  ``--mode refresh-index``
loads the newest stored version and applies incremental ``append_rows``
maintenance — cost proportional to the appended rows, version bumped so
stale readers detect drift.  Services point an
:class:`repro.core.index.IndexStore` at the same ``--index-root`` to serve
warm queries from these artifacts.
"""
from __future__ import annotations

import argparse
import functools
import time

import numpy as np


PAIR_LEN = 48  # tokens per serialised record pair


def load_model(arch: str, width: str = "smoke", seed: int = 0):
    """Model for the scoring/decode modes: ``width="published"`` builds the
    architecture at its published widths (``repro.configs.get_config``),
    ``"smoke"`` the reduced CPU-sized variant.  Weights come from
    ``init_params`` at ``seed``.  Returns ``(cfg, params, tokenizer)``."""
    import jax

    from repro.configs import get_config, get_smoke_config
    from repro.data.pipeline import ByteTokenizer
    from repro.models import init_params

    tok = ByteTokenizer()
    if width == "published":
        cfg = get_config(arch)
        if cfg.vocab_size < tok.vocab_size:
            raise ValueError(f"{arch}: vocabulary of {cfg.vocab_size} cannot "
                             f"hold the {tok.vocab_size} byte-tokenizer ids")
    else:
        cfg = get_smoke_config(arch, vocab_size=tok.vocab_size)
    # one compiled program instead of one dispatch per parameter op
    params = jax.jit(functools.partial(init_params, cfg))(jax.random.key(seed))
    return cfg, params, tok


def make_scorer(cfg, params, tok, left: list, right: list, batch_size: int,
                shard: bool = False):
    """Scorer for the score/service/fleet modes: a pair ``(i, j)`` is the
    prompt over ``left[i]`` and ``right[j]`` — one record list per table, so
    tables of any sizes join.  ``shard`` spreads every score batch over all
    host devices (data-parallel, ``--shard``)."""
    from repro.data.pipeline import pair_example
    from repro.serve.serve_loop import PairScorer

    def tok_pair(pair):
        t, _ = pair_example(tok, left[pair[0]], right[pair[1]], None,
                            PAIR_LEN)
        return t[t != tok.PAD]

    mesh = None
    if shard:
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh()
        print(f"[serve] sharding score batches over mesh {dict(mesh.shape)}")
    return PairScorer(cfg, params, tok_pair, tok.YES, tok.NO,
                      max_len=PAIR_LEN, batch_size=batch_size, mesh=mesh)


def _run_client(args) -> None:
    """``--mode client``: BAS queries against a remote serving fleet.  Builds
    the same synthetic join the demo server scores (seeded, so every process
    agrees on table sizes), runs ``--queries`` concurrent queries through
    per-query :class:`RemoteOracle`\\ s, and prints estimates + latency."""
    from repro.core import Agg, BASConfig, Query, run_bas
    from repro.data import make_clustered_tables
    from repro.serve.oracle_service import serve_queries
    from repro.serve.transport import RemoteOracle, parse_address

    address = parse_address(args.connect)
    n = args.n_side
    ds = make_clustered_tables(n, n, n_entities=max(2 * n // 3, 4),
                               noise=0.4, seed=0)
    oracles = [RemoteOracle(address, args.group) for _ in range(args.queries)]
    queries = [Query(spec=ds.spec(), agg=Agg.COUNT, oracle=o,
                     budget=args.budget) for o in oracles]
    lat = np.zeros(args.queries)

    def job(i: int):
        t0 = time.time()
        try:
            return run_bas(queries[i], BASConfig(n_bootstrap=100), seed=i)
        finally:
            lat[i] = time.time() - t0
            oracles[i].close()       # free the server's window bookkeeping

    t0 = time.time()
    results = serve_queries(None, [lambda i=i: job(i)
                                   for i in range(args.queries)])
    dt = time.time() - t0
    labels = sum(o.calls for o in oracles)
    reconnects = sum(o.conn.reconnects for o in oracles)
    print(f"[client] {args.queries} queries against "
          f"{address[0]}:{address[1]}, {labels} labels in {dt:.2f}s "
          f"({labels/max(dt,1e-9):.1f} labels/s, {reconnects} reconnects); "
          f"p50={np.quantile(lat, 0.5)*1e3:.0f}ms "
          f"p99={np.quantile(lat, 0.99)*1e3:.0f}ms")
    for i, r in enumerate(results):
        print(f"[client]   q{i}: estimate={r.estimate:.1f} "
              f"ci=[{r.ci.lo:.1f}, {r.ci.hi:.1f}] calls={oracles[i].calls}")


def _index_tables(args) -> list:
    """Embedding tables for the index modes: ``--tables a.npy,b.npy`` or the
    same seeded synthetic pair the demo server scores."""
    if args.tables:
        return [np.load(p.strip()) for p in args.tables.split(",")]
    from repro.data import make_clustered_tables

    n = args.n_side
    ds = make_clustered_tables(n, n, n_entities=max(2 * n // 3, 4),
                               noise=0.4, seed=0)
    return [np.asarray(e, np.float32) for e in ds.spec().embeddings]


def _run_build_index(args) -> None:
    """``--mode build-index``: one cold sweep -> saved artifact."""
    from repro.checkpoint.index_io import save_index
    from repro.core.index import build_index

    embs = _index_tables(args)
    t0 = time.time()
    art = build_index(embs, n_bins=args.bins, precision=args.precision)
    path = save_index(args.index_root, art)
    print(f"[index] built key={art.key[:16]}... v{art.version} over tables "
          f"{art.sizes} in {time.time()-t0:.2f}s "
          f"(kernel={art.kernel}, {art.nbytes/1e6:.1f} MB) -> {path}")


def _run_refresh_index(args) -> None:
    """``--mode refresh-index``: incremental append maintenance on the
    newest stored version (delta-proportional cost, version bump)."""
    from repro.checkpoint.index_io import list_indexes, load_index, save_index
    from repro.core.index import append_rows
    from repro.core.similarity import normalize

    key = args.key
    if not key:
        stored = list_indexes(args.index_root)
        if not stored:
            raise SystemExit(f"[index] nothing stored under {args.index_root}")
        # newest lineage: append_rows re-keys (content-addressing) but keeps
        # bumping version, so the highest version is the latest refresh
        key = max(stored, key=lambda s: s["version"])["key"]
    art = load_index(args.index_root, key)
    if args.append_file:
        new_rows = np.load(args.append_file)
    else:
        rng = np.random.default_rng(art.version)
        d = art.embeddings[args.append_table].shape[1]
        new_rows = normalize(rng.standard_normal((args.append_rows, d)))
    t0 = time.time()
    art2 = append_rows(art, args.append_table, new_rows)
    path = save_index(args.index_root, art2)
    print(f"[index] refreshed key={art.key[:16]}... -> {art2.key[:16]}... "
          f"v{art.version}->v{art2.version}: +{len(new_rows)} rows on table "
          f"{args.append_table}, {art2.stats['last_delta_blocks']} delta "
          f"tile(s) in {time.time()-t0:.2f}s -> {path}")


def _make_label_store(args):
    """Optional service-resident :class:`repro.serve.label_store.LabelStore`
    for the service/server/worker modes: ``--label-store-mb 0`` (the default)
    disables it; ``--label-store-root`` additionally persists stable segments
    across restarts."""
    if not args.label_store_mb and not args.label_store_root:
        return None
    from repro.serve.label_store import LabelStore

    store = LabelStore(max_bytes=int((args.label_store_mb or 256) * 2**20),
                       root=args.label_store_root or None)
    where = args.label_store_root or "memory-only"
    print(f"[serve] label store: {args.label_store_mb or 256} MB budget, "
          f"root={where}, {store.loads} segment(s) hydrated")
    return store


def _make_tracker(args):
    """Tracker for the service/server/worker modes: ``--tracker none`` (the
    default, zero-cost hooks), ``memory`` (in-process snapshot), or ``jsonl``
    (append every signal to ``--tracker-out``)."""
    from repro.obs import make_tracker

    tracker = make_tracker(args.tracker,
                           path=args.tracker_out or "tracker.jsonl")
    if args.tracker == "jsonl":
        print(f"[serve] tracker: jsonl -> {tracker.path}")
    return tracker


def _start_metrics(args, *sources):
    """``--metrics-port N``: start the OpenMetrics ``/metrics`` endpoint
    over the given ``snapshot()`` sources (0, the default, disables it).
    Returns the running :class:`repro.obs.MetricsExporter` or ``None``."""
    if not getattr(args, "metrics_port", 0):
        return None
    from repro.obs import MetricsExporter

    exp = MetricsExporter(list(sources), host=args.host,
                          port=args.metrics_port).start()
    host, port = exp.address
    print(f"[serve] metrics: http://{host}:{port}/metrics")
    return exp


def _print_service_stats(role: str, snap: dict) -> None:
    """Shutdown observability lines shared by the fleet and service modes —
    read exclusively from the unified ``snapshot()`` surface.  The *_recent
    ratios are last-N window means (steady state), unlike the lifetime
    ratios that average warmup in forever."""
    charges_saved = (snap.get("label_store.shared", 0.0)
                     + snap.get("label_store.hits", 0.0))
    print(f"[{role}] windows: "
          f"fill={snap.get('service.window.fill_ratio', 0.0):.2f} "
          f"(recent={snap.get('service.window.fill_ratio_recent', 0.0):.2f}) "
          f"dedup={snap.get('service.window.dedup_ratio', 0.0):.2f} "
          f"(recent={snap.get('service.window.dedup_ratio_recent', 0.0):.2f}); "
          f"store: hit_rate={snap.get('label_store.hit_rate', 0.0):.2f} "
          f"charges_saved={charges_saved:.0f}")
    if snap.get("service.admission.rejected") or snap.get(
            "service.worker.deaths"):
        print(f"[{role}] admission: "
              f"rejected={snap.get('service.admission.rejected', 0.0):.0f} "
              f"rate={snap.get('service.rate_rows_per_s', 0.0):.0f} rows/s; "
              f"workers: deaths={snap.get('service.worker.deaths', 0.0):.0f} "
              f"rejoins={snap.get('service.worker.rejoins', 0.0):.0f}")
    for line in _service_class_lines(snap):
        print(f"[{role}] {line}")


def _service_class_lines(snap: dict) -> list[str]:
    """One line per deadline/query class seen by the service: flush-latency
    histogram percentiles (``service.class.<name>.flush_ms.*``, written by a
    tracker) and the class's own admission EWMA
    (``service.class.<name>.rate_rows_per_s``)."""
    classes: set[str] = set()
    for key in snap:
        if key.startswith("service.class."):
            rest = key[len("service.class."):]
            classes.add(rest.rsplit(".", 1)[0].split(".")[0])
    lines = []
    for qc in sorted(classes):
        prefix = f"service.class.{qc}"
        parts = [f"class {qc!r}:"]
        if f"{prefix}.flush_ms.count" in snap:
            parts.append(
                f"flushes={snap[f'{prefix}.flush_ms.count']:.0f} "
                f"p50={snap.get(f'{prefix}.flush_ms.p50', 0.0):.1f}ms "
                f"p99={snap.get(f'{prefix}.flush_ms.p99', 0.0):.1f}ms"
            )
        if f"{prefix}.rate_rows_per_s" in snap:
            parts.append(
                f"rate={snap[f'{prefix}.rate_rows_per_s']:.0f} rows/s"
            )
        if len(parts) > 1:
            lines.append(" ".join(parts))
    return lines


def _run_fleet_role(args, scorer) -> None:
    """``--mode server|worker``: expose the scorer over TCP.  A worker is a
    server with no downstream hosts; ``--worker-hosts`` turns a server into
    the fleet front that shards super-batches across hosts."""
    from repro.serve.transport import (OracleServiceServer, parse_address,
                                       scorer_group)

    role = args.mode
    tracker = _make_tracker(args)
    server = OracleServiceServer(
        {args.group: scorer_group(scorer, threshold=0.5)},
        host=args.host, port=args.port,
        workers=args.workers, max_wait_ms=8.0,
        label_store=_make_label_store(args),
        tracker=tracker,
    )
    host, port = server.address
    print(f"[{role}] group {args.group!r} listening on {host}:{port}")
    metrics = _start_metrics(args, server.service.snapshot)
    for spec in (args.worker_hosts.split(",") if args.worker_hosts else []):
        w = server.register_worker(parse_address(spec))
        print(f"[{role}] registered worker {w.address[0]}:{w.address[1]} "
              f"groups={sorted(w.groups)}")
    try:
        deadline = time.time() + args.duration if args.duration else None
        while deadline is None or time.time() < deadline:
            time.sleep(0.2)
    except KeyboardInterrupt:
        pass
    finally:
        snap = server.service.snapshot()
        if metrics is not None:
            metrics.stop()
        server.close()
        tracker.close()
        print(f"[{role}] shut down; {snap['service.windows']:.0f} windows, "
              f"{snap['service.rows_labelled']:.0f} rows labelled, "
              f"{snap['service.remote_shards']:.0f} remote shards")
        _print_service_stats(role, snap)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--width", choices=("smoke", "published"),
                    default="smoke",
                    help="model widths: the reduced smoke config or the "
                         "architecture's published config")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the randomly initialised weights")
    ap.add_argument("--mode",
                    choices=("decode", "score", "service",
                             "server", "client", "worker",
                             "build-index", "refresh-index"),
                    default="decode")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--pairs", type=int, default=64)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--queries", type=int, default=4,
                    help="service/client mode: concurrent BAS queries")
    ap.add_argument("--budget", type=int, default=300,
                    help="service/client mode: oracle budget per query")
    ap.add_argument("--workers", type=int, default=1,
                    help="service/server/worker mode: scorer worker threads")
    ap.add_argument("--shard", action="store_true",
                    help="data-parallel pair scoring over all host devices")
    ap.add_argument("--host", default="127.0.0.1",
                    help="server/worker mode: bind address")
    ap.add_argument("--port", type=int, default=0,
                    help="server/worker mode: bind port (0 = ephemeral)")
    ap.add_argument("--connect", default="127.0.0.1:7431",
                    help="client mode: front server host:port")
    ap.add_argument("--worker-hosts", default="",
                    help="server mode: comma-separated worker host:port list")
    ap.add_argument("--group", default="default",
                    help="server/worker/client mode: wire group name")
    ap.add_argument("--label-store-mb", type=float, default=0.0,
                    help="service/server/worker mode: shared label store "
                         "memory budget in MB (0 = disabled)")
    ap.add_argument("--label-store-root", default="",
                    help="service/server/worker mode: persist stable label "
                         "store segments under this directory")
    ap.add_argument("--tracker", choices=("none", "memory", "jsonl"),
                    default="none",
                    help="service/server/worker mode: metrics tracker "
                         "(repro.obs) — none keeps the zero-cost hooks")
    ap.add_argument("--metrics-port", type=int, default=0,
                    help="service/server/worker mode: serve the unified "
                         "snapshot as OpenMetrics on this port (0=off)")
    ap.add_argument("--tracker-out", default="",
                    help="jsonl tracker output path (default tracker.jsonl)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="service mode: declare a deadline class for the "
                         "queries — flushes are shed with AdmissionRejected "
                         "when the queue predicts a miss (0 = no deadline)")
    ap.add_argument("--n-side", type=int, default=48,
                    help="server/client mode: synthetic table side length")
    ap.add_argument("--duration", type=float, default=0.0,
                    help="server/worker mode: seconds to serve (0 = forever)")
    ap.add_argument("--index-root", default="runs/index",
                    help="build-index/refresh-index mode: artifact store dir")
    ap.add_argument("--tables", default="",
                    help="build-index mode: comma-separated .npy embedding "
                         "files (default: synthetic --n-side pair)")
    ap.add_argument("--bins", type=int, default=4096,
                    help="build-index mode: sweep histogram bins")
    ap.add_argument("--precision", default="fp32",
                    help="build-index mode: sweep precision "
                         "(fp32 | bf16 | int8)")
    ap.add_argument("--key", default="",
                    help="refresh-index mode: content key (default: newest "
                         "stored index)")
    ap.add_argument("--append-rows", type=int, default=32,
                    help="refresh-index mode: synthetic rows to append")
    ap.add_argument("--append-table", type=int, default=1, choices=(0, 1),
                    help="refresh-index mode: table receiving the rows")
    ap.add_argument("--append-file", default="",
                    help="refresh-index mode: .npy of rows to append "
                         "(overrides --append-rows)")
    args = ap.parse_args()

    if args.mode == "client":
        # the client holds no model — plan/commit are local, labelling is
        # remote — so skip scorer construction entirely
        _run_client(args)
        return
    if args.mode == "build-index":
        _run_build_index(args)
        return
    if args.mode == "refresh-index":
        _run_refresh_index(args)
        return

    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve.serve_loop import ContinuousBatcher, Request

    enable_compile_cache()
    cfg, params, tok = load_model(args.arch, args.width, args.seed)
    print(f"[serve] {cfg.name} ({cfg.param_count()/1e6:.1f}M) mode={args.mode}")

    if args.mode == "decode":
        cb = ContinuousBatcher(cfg, params, batch_size=args.batch_slots,
                               max_len=128, eos_id=tok.EOS)
        rng = np.random.default_rng(0)
        for i in range(args.requests):
            cb.submit(Request(
                uid=i,
                prompt=np.array([tok.BOS] + tok.encode(f"req {i}: ")[:12], np.int32),
                max_new_tokens=args.max_new,
            ))
        t0 = time.time()
        done = cb.run_until_done()
        dt = time.time() - t0
        toks = sum(len(r.out_tokens) for r in done)
        print(f"[serve] {len(done)} requests, {toks} tokens, {dt:.2f}s "
              f"({toks/max(dt,1e-9):.1f} tok/s)")
    elif args.mode in ("server", "worker"):
        n_side = args.n_side
        records = [f"entity record {i:03d}" for i in range(n_side)]
        scorer = make_scorer(cfg, params, tok, records, records,
                             batch_size=32, shard=args.shard)
        _run_fleet_role(args, scorer)
    elif args.mode == "service":
        from repro.core import Agg, BASConfig, ModelOracle, Query, run_bas
        from repro.data import make_clustered_tables
        from repro.serve.oracle_service import OracleService, serve_queries

        n_side = 48
        ds = make_clustered_tables(n_side, n_side, n_entities=64, noise=0.4,
                                   seed=0)
        records = [f"entity record {i:03d}" for i in range(n_side)]
        scorer = make_scorer(cfg, params, tok, records, records,
                             batch_size=32, shard=args.shard)
        cfg_bas = BASConfig(n_bootstrap=100)
        # named oracles share one LabelStore segment group (an unnamed
        # ModelOracle's group is process-local and can never be persisted)
        oracles = [ModelOracle(scorer, threshold=0.5, name=args.group)
                   for _ in range(args.queries)]
        queries = [
            Query(spec=ds.spec(), agg=Agg.COUNT, oracle=o, budget=args.budget)
            for o in oracles
        ]
        lat = np.zeros(args.queries)
        tracker = _make_tracker(args)
        shed = [0]
        with OracleService(workers=args.workers, max_wait_ms=8.0,
                           label_store=_make_label_store(args),
                           tracker=tracker) as svc:
            from repro.serve.oracle_service import AdmissionRejected

            metrics = _start_metrics(args, svc.snapshot)
            svc.attach(*oracles,
                       deadline_ms=args.deadline_ms or None)

            def job(i: int):
                t0 = time.time()
                try:
                    while True:
                        try:
                            return run_bas(queries[i], cfg_bas, seed=i)
                        except AdmissionRejected as e:
                            # typed + retryable: ledger untouched, cache kept,
                            # so re-running the (deterministic) query is safe
                            shed[0] += 1
                            time.sleep(min(e.predicted_ms, 1e3) / 1e3)
                finally:
                    lat[i] = time.time() - t0
                    svc.detach(oracles[i])

            t0 = time.time()
            results = serve_queries(
                svc, [lambda i=i: job(i) for i in range(args.queries)]
            )
            dt = time.time() - t0
            snap = svc.snapshot()
            if metrics is not None:
                metrics.stop()
        tracker.close()
        labels = sum(o.calls for o in oracles)
        print(f"[serve] {args.queries} concurrent queries, {labels} oracle "
              f"labels in {dt:.2f}s ({labels/max(dt,1e-9):.1f} labels/s, "
              f"{scorer.forward_batches} device batches)")
        print(f"[serve] p50={np.quantile(lat, 0.5)*1e3:.0f}ms "
              f"p99={np.quantile(lat, 0.99)*1e3:.0f}ms per query; "
              f"service: {snap['service.windows']:.0f} windows, "
              f"{snap['service.segments_per_window']:.2f} flushes/window"
              + (f"; {shed[0]} flush(es) shed and retried" if shed[0] else ""))
        _print_service_stats("serve", snap)
        for i, r in enumerate(results):
            print(f"[serve]   q{i}: estimate={r.estimate:.1f} "
                  f"ci=[{r.ci.lo:.1f}, {r.ci.hi:.1f}] "
                  f"calls={oracles[i].calls}")
    else:
        records = [f"entity {i % 16} record {i}" for i in range(64)]
        scorer = make_scorer(cfg, params, tok, records, records,
                             batch_size=16, shard=args.shard)
        rng = np.random.default_rng(0)
        pairs = rng.integers(0, 64, size=(args.pairs, 2))
        t0 = time.time()
        p = scorer.score(pairs)
        dt = time.time() - t0
        print(f"[serve] scored {len(pairs)} pairs in {dt:.2f}s "
              f"({len(pairs)/max(dt,1e-9):.1f} pairs/s, "
              f"{scorer.forward_batches} device batches), mean={p.mean():.3f}")


if __name__ == "__main__":
    main()
