"""One run of one cell: set-up, the measured window, the comparison with the
plain references, and the metrics.

The cell's configuration (``configs/<name>.json``), traffic mix
(``traffic/<name>.json``) and metric readers (``metrics/<name>.py``) are
found by the names in ``BENCHMARK.json``; nothing here names a cell.

The timed path is the served query:
``JoinMLEngine.execute`` -> ``OracleService`` -> ``ModelOracle`` ->
``PairScorer`` -> ``models.forward``.  The harness wraps a few of the
program's entry points from the outside, without changing what they compute:
host spans for the trace (``query``, ``stratify``, ``tokenize``,
``scorer.forward``, ``bootstrap``), counts of the scorer's padded blocks and
of the sweeps, and a copy of what the timed path produced for the
comparison after the window: scorer blocks, the last sweep or dense weights,
and per query the pairs it drew with their stated probabilities and the
samples, blocked labels and resampling stream its estimate and CI came from.
"""
from __future__ import annotations

import copy
import dataclasses
import importlib.util
import json
import os
import sys
import threading
import time
from pathlib import Path
from typing import Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE = HERE / ".cache"
SPANS = ("query", "stratify", "tokenize", "scorer.forward", "bootstrap")
N_BINS = 4096          # the engine's streaming histogram (dispatch.run_auto)
SAMPLE_BLOCKS = 8      # scorer blocks compared with the reference, per run
SWEEP_BLOCKS = 2       # sweep row blocks compared with the reference
DENSE_ROWS = 64        # dense-path weight rows compared with the reference
WEIGHT_QUERIES = 2     # queries whose drawn pairs' weights are compared


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the cell
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list       # BENCHMARK.json metric entries this cell reports
    per_layer: list


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    w = cells[workload]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((root / cfg["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    mine = lambda ms: [m for m in ms  # noqa: E731
                       if workload in m.get("workloads", [workload])]
    return Cell(workload, int(w["chips"]), config, traffic,
                mine(bench["end_to_end"]), mine(bench["per_layer"]))


def read_metric(name: str, ctx) -> Optional[float]:
    """Run ``metrics/<name>.py``'s ``read(ctx)``; ``None`` means nothing to
    read in this run."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}",
        HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


# ---------------------------------------------------------------------------
# caches and the device
# ---------------------------------------------------------------------------

def enable_caches() -> str:
    """JAX's persistent compile cache (``JAX_COMPILATION_CACHE_DIR`` when
    set, else a fixed directory in the checkout) for every program, and the
    sweep autotuner's winners on disk beside it."""
    import jax
    from repro.kernels import autotune

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    autotune.configure(str(CACHE / "autotune.json"))
    return path


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise NoChip(f"needs a TPU; JAX found {info['platform']!r}")
    if require_tpu and info["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {info['count']}")
    return info


def load_peaks(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]


# ---------------------------------------------------------------------------
# wrappers around the program's entry points
# ---------------------------------------------------------------------------

class Probe:
    """What the harness sees of the timed path.  ``active`` is set for the
    measured window; outside it nothing is recorded."""

    def __init__(self, seed: int, fault: Optional[str] = None):
        self.active = False
        self.fault = fault      # a planted fault, for the harness's own tests
        self.lock = threading.Lock()
        from chipbench.data import rng_for

        self.rng = rng_for(seed, 5)
        self.blocks = []        # (pad_len, batch, real lengths) per forward
        self.sweeps = []        # shapes of each sweep call
        self.kept = []          # reservoir of (toks, last, device logits)
        self.seen = 0
        self.longest = None     # a block of the longest padded length
        self.sweep_info = None  # last stratification sweep of the window
        self.sweep_tables = None
        self.dense = None       # (weights, embeddings) of the dense path
        self.compiles = [0, 0.0]  # programs compiled or loaded in the window
        self.local = threading.local()  # the running query's estimation

    def begin_query(self) -> None:
        self.local.draws, self.local.estimation = [], None

    def end_query(self) -> tuple:
        """(draws, estimation) of the query that ran in this thread:
        [(stratum, (n, 2) pairs, (n,) stated probabilities)] and the inputs
        of its estimate and CI, both empty outside the window."""
        return self.local.draws, self.local.estimation

    def on_event(self, event: str, seconds: float, **kwargs) -> None:
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            with self.lock:
                self.compiles[0] += 1
                self.compiles[1] += seconds

    def keep_block(self, toks, last, out) -> None:
        item = (toks, last, out)
        if self.longest is None or toks.shape[1] > self.longest[0].shape[1]:
            self.longest = item
        self.seen += 1
        if len(self.kept) < SAMPLE_BLOCKS - 1:
            self.kept.append(item)
        else:
            j = int(self.rng.integers(0, self.seen))
            if j < len(self.kept):
                self.kept[j] = item

    def sampled_blocks(self) -> list:
        out = list(self.kept)
        if self.longest is not None and not any(b is self.longest for b in out):
            out.append(self.longest)
        return out


class WindowTracker:
    """A ``repro.obs`` tracker that keeps the window's observations of each
    series (attached to the service in traced runs only)."""

    def __init__(self, probe: Probe):
        self.probe = probe
        self.lock = threading.Lock()
        self.series: dict = {}

    def observe(self, name: str, value: float) -> None:
        if self.probe.active:
            with self.lock:
                self.series.setdefault(name, []).append(float(value))

    def count(self, name: str, value: int = 1) -> None:
        pass

    def gauge(self, name: str, value: float) -> None:
        pass

    def event(self, name: str, **fields) -> None:
        pass

    def snapshot(self) -> dict:
        return {}

    def close(self) -> None:
        pass


def span(name: str, fn):
    import jax

    def wrapped(*args, **kwargs):
        with jax.profiler.TraceAnnotation(name):
            return fn(*args, **kwargs)

    wrapped.__wrapped__ = fn
    return wrapped


def instrument(probe: Probe, scorer) -> None:
    """Spans, counts and captures around the program's entry points."""
    import jax
    import jax.monitoring
    import repro.core.bas as bas
    import repro.core.bas_streaming as bas_streaming
    import repro.core.stratify as stratify
    import repro.kernels.sim_sweep.ops as sweep_ops

    for mod, attr, name in ((bas_streaming, "build_streaming_space", "stratify"),
                            (bas, "build_dense_space", "stratify")):
        fn = getattr(mod, attr)
        setattr(mod, attr, span(name, getattr(fn, "__wrapped__", fn)))

    label_draws = getattr(bas._label_draws, "__wrapped__", bas._label_draws)

    def _label_draws(query, draws):
        if probe.active:
            for i, d in enumerate(draws):
                if d is None:
                    continue
                if probe.fault == "sampler":   # probabilities misstated
                    d.q[: len(d.q) // 2] *= 1.05
                probe.local.draws.append((i, np.array(d.tup), np.array(d.q)))
        return label_draws(query, draws)

    _label_draws.__wrapped__ = label_draws
    bas._label_draws = _label_draws

    boot = getattr(bas.bootstrap_t_ci, "__wrapped__", bas.bootstrap_t_ci)

    def bootstrap_t_ci(samples, blocked, agg, p, n_boot, rng):
        est = None
        if probe.active:
            est = dict(strata=[(np.array(s.o), np.array(s.q)) for s in samples],
                       blocked=np.array(blocked.o), agg=agg.name,
                       rng=copy.deepcopy(rng.bit_generator.state))
            if probe.fault == "short_bootstrap":
                n_boot //= 2
        with jax.profiler.TraceAnnotation("bootstrap"):
            out = boot(samples, blocked, agg, p, n_boot, rng)
        if est is not None:
            probe.local.estimation = est
        return out

    bootstrap_t_ci.__wrapped__ = boot
    bas.bootstrap_t_ci = bootstrap_t_ci

    sweep_chain = getattr(stratify.sweep_pass_chain, "__wrapped__",
                          stratify.sweep_pass_chain)

    def sweep_pass_chain(embeddings, *args, **kwargs):
        info = sweep_chain(embeddings, *args, **kwargs)
        if probe.active and probe.fault == "sweep" and info.row_sums is not None:
            info.row_sums = [r * 1.001 for r in info.row_sums]
        if probe.active:
            probe.sweep_info, probe.sweep_tables = info, embeddings
        return info

    sweep_pass_chain.__wrapped__ = sweep_chain
    stratify.sweep_pass_chain = sweep_pass_chain

    kernel = getattr(sweep_ops.sim_sweep, "__wrapped__", sweep_ops.sim_sweep)

    def sim_sweep(e1, e2=None, n_bins=4096, *args, **kwargs):
        out = kernel(e1, e2, n_bins, *args, **kwargs)
        if probe.active:
            n2 = e2.shape[0] if e2 is not None else kwargs["right"].n2
            with probe.lock:
                probe.sweeps.append(dict(
                    n1=int(np.shape(e1)[0]), n2=int(n2),
                    d=int(np.shape(e1)[1]), n_bins=int(n_bins),
                    k=int(out.vals.shape[1])))
        return out

    sim_sweep.__wrapped__ = kernel
    sweep_ops.sim_sweep = sim_sweep

    chain_weights = getattr(bas.chain_weights, "__wrapped__", bas.chain_weights)

    def dense_weights(embeddings, *args, **kwargs):
        w = chain_weights(embeddings, *args, **kwargs)
        if probe.active:
            probe.dense = (w, embeddings)
        return w

    dense_weights.__wrapped__ = chain_weights
    bas.chain_weights = dense_weights

    tokenize, forward = scorer._tokenize, scorer.yes_no_logits
    scorer._tokenize = span("tokenize", tokenize)

    def yes_no_logits(toks, last):
        with jax.profiler.TraceAnnotation("scorer.forward"):
            out = forward(toks, last)
        fault = probe.fault if probe.active else None
        if fault == "logits":
            out = out.at[0, 0].add(1.0)
        elif fault == "half_batch":
            half = out.shape[0] // 2
            out = out.at[half:].set(out[:half][: out.shape[0] - half])
        if probe.active:
            real = np.asarray(toks)[:, 0] != 0
            lens = np.asarray(last)[real].astype(np.int64) + 1
            with probe.lock:
                probe.blocks.append((int(toks.shape[1]), int(toks.shape[0]), lens))
                probe.keep_block(np.asarray(toks), np.asarray(last), out)
        return out

    scorer.yes_no_logits = yes_no_logits
    jax.monitoring.register_event_duration_secs_listener(probe.on_event)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def oracle_config(oracle: dict):
    """The program's model configuration with the file's sizes, of a family
    that the file names as ``repro.configs`` registers it for the ``arch``,
    and that has a reference (``chipbench/oracles/<family>.py``) that can
    represent it."""
    from chipbench import oracles
    from repro.configs import get_config

    base = get_config(oracle["arch"])
    if oracle.get("family") != base.family:
        raise ValueError(f"{oracle['arch']} is of family {base.family!r}; "
                         f"the oracle section says {oracle.get('family')!r}")
    family = oracles.load(base.family)
    fields = {f.name for f in dataclasses.fields(base)}
    cfg = dataclasses.replace(base, **{k: v for k, v in oracle.items()
                                       if k in fields and k != "name"})
    family.check(cfg)
    return cfg


def build_scorer(config: dict, cfg, params, left: list, right: list):
    from repro.data.pipeline import ByteTokenizer, pair_example
    from repro.serve.serve_loop import PairScorer

    o = config["oracle"]
    tok = ByteTokenizer()
    max_len = int(o["max_len"])

    def tokenize_pair(pair):
        t, _ = pair_example(tok, left[pair[0]], right[pair[1]], None, max_len)
        return t[t != tok.PAD]

    return PairScorer(cfg, params, tokenize_pair, tok.YES, tok.NO,
                      max_len=max_len, batch_size=int(o["batch_size"]))


SCORER_COUNTERS = ("tokens", "token_slots", "causal_pairs", "pairs_scored")


def scorer_counters(scorer) -> dict:
    """The scorer's counters by name: ``scorer.counters()`` where the
    program offers it (a family's own counters with the common ones), else
    the common ones read one by one."""
    own = getattr(scorer, "counters", None)
    if own is not None:
        return dict(own())
    return {k: getattr(scorer, k) for k in SCORER_COUNTERS}


def used_buckets(scorer, left: list, right: list) -> list:
    """Padded lengths the cell's pairs can take: a pair's prompt is
    [BOS] r1 [SEP] r2 [SCORE], each record cut at max_len // 2 - 3."""
    cut = scorer.max_len // 2 - 3
    lens = lambda recs: [min(len(r), cut) for r in recs]  # noqa: E731
    lo = 3 + min(lens(left)) + min(lens(right))
    hi = min(3 + max(lens(left)) + max(lens(right)), scorer.max_len)
    b = scorer._buckets
    return [int(x) for x in b[np.searchsorted(b, lo):np.searchsorted(b, hi) + 1]]


def query_seed(seed: int, analyst: int, kind: int, count: int) -> int:
    """An engine seed: kind 0 fresh, 1 an analyst's dashboard query, 2 a
    warm-up query."""
    from chipbench.data import seed_seq

    return int(seed_seq(seed, 6, analyst, kind, count).generate_state(1)[0])


POOL_BLOCK = 16     # fresh queries are permuted within blocks of the pool
MIX_BLOCK = 4       # an analyst's repeats and fresh queries, per block


class Analysts:
    """The traffic mix: a closed loop of analysts, each sending its next
    query when the previous answer returns.

    Every run sends the same queries in another order, so the seed changes
    the order and not the work: the c-th fresh query of the window takes
    the engine seed of entry c of a fixed pool, the entries permuted by the
    run's seed within blocks of ``POOL_BLOCK``.  With ``repeat_prob`` an
    analyst's queries come in blocks of ``MIX_BLOCK`` holding exactly
    ``MIX_BLOCK * (1 - repeat_prob)`` fresh ones at positions the seed
    draws; the others re-run the analyst's saved dashboard query (same SQL,
    same engine seed)."""

    def __init__(self, traffic: dict, seed: int):
        from chipbench.data import rng_for

        self.t = traffic
        self.seed = seed
        self.n = int(traffic["analysts"])
        fresh = MIX_BLOCK * (1.0 - float(traffic["repeat_prob"]))
        if abs(fresh - round(fresh)) > 1e-9:
            raise ValueError(f"repeat_prob must leave a whole number of fresh "
                             f"queries in {MIX_BLOCK}")
        self.fresh_per_block = int(round(fresh))
        self.rng = rng_for(seed, 7)
        self.plans = [[] for _ in range(self.n)]
        self.order = []
        self.count = 0
        self.lock = threading.Lock()

    def dashboard(self, i: int) -> int:
        return query_seed(0, i, 1, 0)

    def warmup(self, i: int) -> tuple:
        if self.fresh_per_block < MIX_BLOCK:
            return "repeat", self.dashboard(i)
        return "warmup", query_seed(0, i, 2, 0)

    def next(self, i: int) -> tuple:
        with self.lock:
            if not self.plans[i]:
                block = [True] * self.fresh_per_block + [False] * (
                    MIX_BLOCK - self.fresh_per_block)
                self.plans[i] = list(self.rng.permutation(block))
            if not self.plans[i].pop(0):
                return "repeat", self.dashboard(i)
            if self.count == len(self.order):
                base = len(self.order)
                self.order += (base + self.rng.permutation(POOL_BLOCK)).tolist()
            k = self.order[self.count]
            self.count += 1
        return "fresh", query_seed(0, 0, 0, int(k))


class System:
    """Catalog, engine and oracle service of one cell."""

    def __init__(self, config: dict, traffic: dict, e1, e2, scorer, tracker,
                 probe: Probe):
        from repro.core import BASConfig, Catalog, JoinMLEngine, ModelOracle, Table
        from repro.core.index import IndexStore
        from repro.serve.label_store import LabelStore
        from repro.serve.oracle_service import OracleService

        t = config["tables"]
        cat = Catalog()
        cat.register(Table(t["left"], e1))
        cat.register(Table(t["right"], e2))
        self.sql = (f"SELECT {traffic['agg']}(*) FROM {t['left']} JOIN "
                    f"{t['right']} ON NL('{config['predicate']}') ORACLE BUDGET "
                    f"{int(traffic['budget'])} WITH PROBABILITY "
                    f"{traffic['confidence']}")
        store = (LabelStore(max_bytes=int(traffic["label_store_mb"]) << 20)
                 if traffic["label_store_mb"] else None)
        self.svc = OracleService(max_wait_ms=float(traffic["max_wait_ms"]),
                                 label_store=store, tracker=tracker)
        self.local = threading.local()
        o = config["oracle"]

        def factory(nl, names):
            self.local.oracle = ModelOracle(scorer, threshold=float(o["threshold"]),
                                            name=o["arch"])
            self.svc.attach(self.local.oracle)
            return self.local.oracle

        bas = BASConfig(sweep_precision=config["sweep_precision"],
                        **config.get("bas", {}))
        self.engine = JoinMLEngine(
            cat, factory, cfg=bas,
            index_store=IndexStore() if traffic["index_store"] else None)
        self.probe = probe
        self.lock = threading.Lock()

    def run_query(self, qseed: int) -> dict:
        import jax

        self.probe.begin_query()
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("query"):
                res = self.engine.execute(self.sql, seed=qseed)
        finally:
            oracle = getattr(self.local, "oracle", None)
            if oracle is not None:
                self.svc.detach(oracle)
        t1 = time.perf_counter()
        fault = self.probe.fault if self.probe.active else None
        if fault == "answer":
            res.estimate = float("nan")
        elif fault == "answer_shift":
            res.estimate *= 1.001
        tel = res.telemetry
        draws, estimation = self.probe.end_query()
        return dict(start=t0, end=t1, seconds=t1 - t0,
                    calls=int(self.local.oracle.calls),
                    estimate=float(res.estimate), lo=float(res.ci.lo),
                    hi=float(res.ci.hi), path=tel.dispatch.path,
                    timings=dict(tel.timings), draws=draws,
                    estimation=estimation)

    def close(self) -> None:
        self.svc.close()


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Context:
    """What a metric reader is given."""
    config: dict
    chips: int
    peaks: Optional[dict]
    setup_s: float
    window_s: float
    queries: list          # the window's queries, in order of start
    labels: int            # oracle labels acquired inside the window
    blocks: list           # (pad_len, batch, real lengths) per scorer forward
    sweeps: list           # shapes of each sweep in the window
    tracker: object = None
    trace: object = None   # trace.Summary of the traced window
    # what the scorer's counters (``scorer_counters``) rose by in the window
    scorer_counters: dict = dataclasses.field(default_factory=dict)


def run(cell: Cell, seed: int, seconds: float, traced: bool, t_start: float,
        require_tpu: bool = True, fault: Optional[str] = None,
        controls: bool = False) -> dict:
    """One run of ``cell``; returns the result line as a dict.  With
    ``controls`` it also holds the controls' readings (calibration only)."""
    cache = enable_caches() if require_tpu else None
    dev = device_info(cell.chips, require_tpu)
    peaks = load_peaks(dev["kind"]) if require_tpu else None
    log(f"device {dev}, compile cache {cache}")

    import jax

    from chipbench import data
    config, traffic = cell.config, cell.traffic
    t = config["tables"]
    e1, e2 = data.make_tables(seed, t["n1"], t["n2"], t["d"], t["n_entities"],
                              t["noise"])
    left, right = (data.make_records(seed, i, n, config["records"][side])
                   for i, (side, n) in enumerate((("left", t["n1"]),
                                                  ("right", t["n2"]))))
    cfg = oracle_config(config["oracle"])
    params = data.make_params(cfg, seed)
    scorer = build_scorer(config, cfg, params, left, right)
    probe = Probe(seed, fault)
    instrument(probe, scorer)
    buckets = used_buckets(scorer, left, right)
    for pad in buckets:
        z = np.zeros((scorer.batch_size, pad), np.int32)
        np.asarray(scorer.yes_no_logits(z, np.zeros(scorer.batch_size, np.int32)))
    log(f"tables {t['n1']} x {t['n2']} d={t['d']}, oracle {cfg.name} "
        f"L={cfg.num_layers} d={cfg.d_model} buckets {buckets}")

    tracker = WindowTracker(probe) if traced else None
    system = System(config, traffic, e1, e2, scorer, tracker, probe)
    analysts = Analysts(traffic, seed)
    warm = {}
    try:
        # one round of warm-up queries, one per analyst: the shapes and the
        # programs of the window, and each dashboard query's first run
        jobs = [analysts.warmup(i) for i in range(analysts.n)]
        outs = _concurrent([lambda q=q: system.run_query(q[1]) for q in jobs])
        for i, o in enumerate(outs):
            if isinstance(o, BaseException):
                raise o
            warm[i] = o
        jax.effects_barrier()
        setup_s = time.perf_counter() - t_start
        log(f"setup {setup_s:.3f}s")

        trace_dir = CACHE / "trace" / cell.name
        if traced:
            import shutil
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        before = scorer_counters(scorer)
        queries, labels, window_s, failures = _window(
            system, analysts, probe, seconds, float(traffic.get("stagger_s", 0)))
        counted = {k: v - before[k]
                   for k, v in scorer_counters(scorer).items()}
        if traced:
            jax.profiler.stop_trace()
    finally:
        system.close()

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()[:cell.chips])
    dev["memory_peak_bytes"] = int(peak)
    log(f"window {window_s:.3f}s: {len(queries)} queries, {labels} labels, "
        f"{len(probe.blocks)} scorer blocks, {len(probe.sweeps)} sweeps, "
        f"{failures} failed; {probe.compiles[0]} programs compiled or loaded "
        f"in the window ({probe.compiles[1]:.3f}s)")
    jax.monitoring.unregister_event_duration_listener(probe.on_event)

    summary = None
    if traced:
        from chipbench import trace
        summary = trace.reduce(trace_dir, SPANS)
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s

    ctl = {} if controls else None
    checks = compare(config, traffic, probe, params, queries, warm, analysts,
                     scorer, failures, (e1, e2), ctl)
    correct = all(v <= lim for v, lim in checks.values())
    ctx = Context(config, cell.chips, peaks, setup_s, window_s, queries, labels,
                  probe.blocks, probe.sweeps, tracker, summary, counted)
    metrics = {}
    for m in cell.per_layer if traced else cell.end_to_end:
        v = read_metric(m["name"], ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    line = {"correct": bool(correct), "attempted": len(queries) + failures,
            "failed": failures, "metrics": metrics, "device": dev}
    if summary is not None:
        line["breakdown"] = summary.breakdown()
    if ctl is not None:
        line["controls"] = ctl
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k} = {v!r} limit {lim!r} "
            f"{'ok' if v <= lim else 'FAILED'}")
    return line


def _concurrent(jobs: list) -> list:
    out = [None] * len(jobs)

    def runner(i, job):
        try:
            out[i] = job()
        except BaseException as e:  # reported by the caller
            out[i] = e

    threads = [threading.Thread(target=runner, args=(i, j), daemon=True)
               for i, j in enumerate(jobs)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    return out


def _window(system: System, analysts: Analysts, probe: Probe, seconds: float,
            stagger_s: float = 0.0):
    """The measured window: every analyst loops until the window closes;
    each query started inside it runs to its end and counts.  Analyst i
    sends its first query ``i * stagger_s / analysts`` into the window, so
    the analysts do not all start at once (a burst that set the tail)."""
    import jax

    queries, failures = [], [0]
    t0 = time.perf_counter()
    t_end = t0 + seconds

    def analyst(i):
        time.sleep(i * stagger_s / analysts.n)
        while time.perf_counter() < t_end:
            kind, qseed = analysts.next(i)
            try:
                q = system.run_query(qseed)
            except Exception as e:  # an answer that never comes
                log(f"analyst {i} query failed: {type(e).__name__}: {e}")
                with system.lock:
                    failures[0] += 1
                continue
            q.update(analyst=i, kind=kind, seed=qseed)
            with system.lock:
                queries.append(q)

    created = []
    factory = system.engine.oracle_factory

    def counting_factory(nl, names):
        o = factory(nl, names)
        with system.lock:
            created.append(o)
        return o

    system.engine.oracle_factory = counting_factory
    probe.active = True
    with jax.profiler.TraceAnnotation("window"):
        threads = [threading.Thread(target=analyst, args=(i,), daemon=True)
                   for i in range(analysts.n)]
        for th in threads:
            th.start()
        time.sleep(max(t_end - time.perf_counter(), 0.0))
        with system.lock:
            labels = sum(o.calls for o in created)
        window_s = time.perf_counter() - t0
    for th in threads:
        th.join(timeout=300)
    probe.active = False
    system.engine.oracle_factory = factory
    if any(th.is_alive() for th in threads):
        raise RuntimeError("a query did not finish within 300 s of the window")
    queries.sort(key=lambda q: q["start"])
    return queries, labels, window_s, failures[0]


# ---------------------------------------------------------------------------
# the comparison with the references
# ---------------------------------------------------------------------------

def _worst(a: float, b: float) -> float:
    """The larger gap; a gap that is not a number is infinite."""
    return float("inf") if not (np.isfinite(a) and np.isfinite(b)) else max(a, b)


def compare(config: dict, traffic: dict, probe: Probe, params, queries: list,
            warm: dict, analysts: Analysts, scorer, failures: int, tables,
            controls: Optional[dict] = None) -> dict:
    """Every number compared, as {name: (value, limit)}; a number is sound
    when it is at most its limit.  With a ``controls`` dict, the same
    numbers of the controls (the reference one precision lower, in the
    program's place) are filled into it."""
    from chipbench import reference
    from chipbench.data import rng_for

    limits = config["limits"]
    checks = {}
    exp, floor = 1.0, 1e-3      # BASConfig's weight transform

    # scorer: sampled blocks of the window against the f32 reference; a
    # window whose queries all re-ran dashboard queries scored nothing
    blocks = probe.sampled_blocks()
    scored_nothing = queries and all(q["kind"] == "repeat" for q in queries)
    if not blocks and scored_nothing:
        dev = 0.0
    elif blocks:
        dev = cdev = 0.0
        for toks, last, out in blocks:
            real = toks[:, 0] != 0
            got = np.asarray(out, np.float64)[real]
            args = (config["oracle"], params, toks, last, scorer.yes_id,
                    scorer.no_id)
            want = reference.yes_no_logits(*args)[real]
            dev = max(dev, reference.logit_dev(got, want))
            if controls is not None:
                ctl = reference.yes_no_logits(*args, control=True)[real]
                cdev = max(cdev, reference.logit_dev(ctl, want))
        if controls is not None:
            controls["scorer_logit_dev"] = cdev
    else:
        dev = float("inf")
    checks["scorer_logit_dev"] = (dev, limits["scorer_logit_dev"])

    # stratification: the window's sweep, or the dense path's weights
    if config["dispatch"] == "streaming":
        got = sweep_view(probe.sweep_info, probe.sweep_tables)
        if got is None:
            nums = {k: float("inf") for k in ("sweep_tiles_outside_margin",
                                              "sweep_topk_value_dev",
                                              "sweep_row_sum_rel")}
        else:
            e1, e2 = probe.sweep_tables
            rng = rng_for(analysts.seed, 8)
            n_blocks = got["n_blocks"]
            pick = sorted(rng.choice(n_blocks, min(SWEEP_BLOCKS, n_blocks),
                                     replace=False).tolist())
            args = (e1, e2, pick, got["block_rows"], N_BINS, got["k"], exp,
                    floor)
            ctl = (reference.sweep_blocks(*args, control=True)
                   if controls is not None else None)
            ids = [got["idx"]] + ([ctl["idx"]] if ctl else [])
            ref = reference.sweep_blocks(*args, score_ids=ids)
            nums = reference.sweep_numbers(got, ref)
            if ctl is not None:
                controls.update(reference.sweep_numbers(ctl, ref))
        for k, v in nums.items():
            checks[k] = (v, limits[k])
    else:
        if probe.dense is None:
            dev = float("inf")
        else:
            w, (e1, e2) = probe.dense
            rng = rng_for(analysts.seed, 8)
            rows = np.sort(rng.choice(e1.shape[0], min(DENSE_ROWS, e1.shape[0]),
                                      replace=False))
            got = np.asarray(w).reshape(e1.shape[0], e2.shape[0])[rows]
            dev = reference.dense_weight_dev(got, e1[rows], e2, exp, floor)
            if controls is not None:
                controls["dense_weight_dev"] = reference.dense_weight_dev(
                    None, e1[rows], e2, exp, floor, control=True)
        checks["dense_weight_dev"] = (dev, limits["dense_weight_dev"])

    # estimation: every window query's estimate and CI, recomputed from the
    # samples and blocked labels it used, by a plain Horvitz-Thompson sum
    # and bootstrap-t on the same resampling stream
    n_boot = int(config["bas"]["n_bootstrap"])
    est_dev = ci_dev = 0.0 if queries else float("inf")
    cest = cci = 0.0
    for q in queries:
        rec = q["estimation"]
        if rec is None or rec["agg"] != traffic["agg"]:
            est_dev = ci_dev = float("inf")
            continue
        args = (rec["strata"], rec["blocked"], float(traffic["confidence"]),
                n_boot, rec["rng"])
        ref = reference.ht_count_ci(*args)
        scale = max(abs(ref[0]), 1.0)
        est_dev = _worst(est_dev, abs(q["estimate"] - ref[0]) / scale)
        ci_dev = _worst(ci_dev, max(abs(q["lo"] - ref[1]),
                                    abs(q["hi"] - ref[2])) / scale)
        if controls is not None:
            ctl = reference.ht_count_ci(*args, dtype=np.float32)
            cest = _worst(cest, abs(ctl[0] - ref[0]) / scale)
            cci = _worst(cci, max(abs(ctl[1] - ref[1]),
                                  abs(ctl[2] - ref[2])) / scale)
    checks["estimate_rel_dev"] = (est_dev, limits["estimate_rel_dev"])
    checks["ci_rel_dev"] = (ci_dev, limits["ci_rel_dev"])
    if controls is not None:
        controls.update(estimate_rel_dev=cest, ci_rel_dev=cci)

    # sampling: the weight each drawn pair was sampled with, as its stated
    # probability implies it, against the float64 weight, for queries drawn
    # from the seed (stratum 0 of the streaming path is the walk)
    drew = [q for q in queries if q["draws"]]
    wdev = 0.0 if drew else float("inf")
    rng = rng_for(analysts.seed, 9)
    walk = (0,) if config["dispatch"] == "streaming" else ()
    for j in sorted(rng.choice(len(drew), min(WEIGHT_QUERIES, len(drew)),
                               replace=False).tolist()):
        args = (drew[j]["draws"], tables[0], tables[1], exp, floor, walk)
        wdev = _worst(wdev, reference.sample_weight_dev(*args))
        if controls is not None:
            controls["sample_weight_dev"] = _worst(
                controls.get("sample_weight_dev", 0.0),
                reference.sample_weight_dev(*args, control=True))
    checks["sample_weight_dev"] = (wdev, limits["sample_weight_dev"])

    # answers: finite, within budget, on the path the configuration names
    path = config["dispatch"]
    if traffic["index_store"] and path == "streaming":
        path = "streaming-index"
    bad = failures + (0 if queries else 1)
    for q in queries:
        ok = (np.isfinite([q["estimate"], q["lo"], q["hi"]]).all()
              and q["lo"] <= q["hi"] and 0 < q["calls"] <= traffic["budget"]
              and q["path"] == path)
        bad += not ok
    checks["bad_answers"] = (bad, limits["bad_answers"])

    # a re-run dashboard query answers as its first run did, to the bit
    mismatch = 0
    for q in queries:
        if q["kind"] == "repeat":
            w = warm[q["analyst"]]
            mismatch += any(q[k] != w[k] for k in ("estimate", "lo", "hi", "calls"))
    checks["repeat_mismatch"] = (mismatch, limits["repeat_mismatch"])
    return checks


def sweep_view(info, tables) -> Optional[dict]:
    """A program SweepInfo in ``reference.sweep_blocks`` form (all rows)."""
    if info is None or info.topk is None or info.row_sums is None:
        return None
    vals, idx, valid = info.topk
    n1 = tables[0].shape[0]
    bc = np.asarray(info.block_counts)
    return {
        "n_blocks": bc.shape[0], "block_rows": int(info.block_rows),
        "k": int(vals.shape[1]),
        "tiles": {g: bc[g] for g in range(bc.shape[0])},
        "vals": {r: np.asarray(vals[r])[np.asarray(valid[r])] for r in range(n1)},
        "idx": {r: np.asarray(idx[r])[np.asarray(valid[r])] for r in range(n1)},
        "row_sums": {r: float(info.row_sums[0][r]) for r in range(n1)},
    }
