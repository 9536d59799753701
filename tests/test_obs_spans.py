"""Spans and counters inside the served query (``repro.obs.spans``).

A profiler trace of tiny streaming and dense queries through an
``OracleService`` with a ``PairScorer`` holds every span name of the
registry, on the thread that opens it, with its query or window id; the
query's timings split sampling and oracle waits out of its stages; the
service observes queue wait beside window assembly and counts starved
waits only while a client is attached; the cascade path nests its walk
spans in ``sample`` too; the scorer counts its padding and required work.  The last tests guard what the chip benchmark hooks: the scorer's
program name and the instance attributes ``score`` calls through.
"""
import threading
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import BASConfig, Catalog, JoinMLEngine, ModelOracle, Table
from repro.data import make_clustered_tables
from repro.obs import spans
from repro.serve.oracle_service import OracleService

MAX_LEN = 64
BATCH = 8


def _records(n: int, seed: int) -> list:
    rng = np.random.default_rng(seed)
    return [f"rec {i} " + "x" * int(rng.integers(0, 40)) for i in range(n)]


@pytest.fixture(scope="module")
def scorer():
    from repro.configs import get_smoke_config
    from repro.data.pipeline import ByteTokenizer, pair_example
    from repro.models import init_params
    from repro.serve.serve_loop import PairScorer

    tok = ByteTokenizer()
    cfg = get_smoke_config(
        "qwen2-1.5b", vocab_size=tok.vocab_size, remat=False, num_layers=1,
        d_model=32, num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
    )
    params = init_params(cfg, jax.random.key(0))
    left, right = _records(40, 1), _records(60, 2)

    def tok_pair(pair):
        t, _ = pair_example(tok, left[pair[0]], right[pair[1]], None, MAX_LEN)
        return t[t != tok.PAD]

    return PairScorer(cfg, params, tok_pair, tok.YES, tok.NO,
                      max_len=MAX_LEN, batch_size=BATCH)


class ListTracker:
    """Keeps every observation of each series, in order."""

    def __init__(self):
        self.lock = threading.Lock()
        self.series = {}

    def observe(self, name, value):
        with self.lock:
            self.series.setdefault(name, []).append(float(value))

    def count(self, name, value=1):
        pass

    def gauge(self, name, value):
        pass

    def event(self, name, **fields):
        pass

    def snapshot(self):
        return {}

    def close(self):
        pass


def _events(trace_dir) -> list:
    """(line key, name, stats) of every registry span in the trace."""
    from jax.profiler import ProfileData

    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    out = []
    for p, plane in enumerate(ProfileData.from_file(str(path)).planes):
        if not plane.name.startswith("/host:"):
            continue
        for j, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name in spans.SPANS:
                    out.append(((p, j), ev.name, dict(ev.stats)))
    return out


def _ids(value) -> set:
    return {int(x) for x in str(value).split()}


def _traced_queries(tmp_path, scorer, dense: bool, n_queries: int = 2):
    ds = make_clustered_tables(40, 60, d=16, n_entities=50, noise=0.4, seed=3)
    cat = Catalog()
    cat.register(Table("l", ds.emb1))
    cat.register(Table("r", ds.emb2))
    tracker = ListTracker()
    svc = OracleService(max_wait_ms=8.0, tracker=tracker)
    local = threading.local()

    def factory(nl, names):
        local.oracle = ModelOracle(scorer, threshold=0.5)
        svc.attach(local.oracle)
        return local.oracle

    cfg = BASConfig(n_bootstrap=50,
                    max_dense_weight_bytes=1 << 30 if dense else 0)
    engine = JoinMLEngine(cat, factory, cfg=cfg)
    sql = ("SELECT COUNT(*) FROM l JOIN r ON NL('same entity') "
           "ORACLE BUDGET 300 WITH PROBABILITY 0.95")
    results = [None] * n_queries

    def run(i):
        try:
            results[i] = engine.execute(sql, seed=i)
        finally:
            svc.detach(local.oracle)

    trace_dir = tmp_path / ("dense" if dense else "streaming")
    jax.profiler.start_trace(str(trace_dir))
    try:
        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(n_queries)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=300)
        assert not any(th.is_alive() for th in threads)
    finally:
        jax.profiler.stop_trace()
        svc.close()
    assert all(r is not None for r in results)
    return results, tracker, _events(trace_dir)


@pytest.fixture(scope="module")
def traced(tmp_path_factory, scorer):
    tmp = tmp_path_factory.mktemp("spans")
    return {dense: _traced_queries(tmp, scorer, dense)
            for dense in (False, True)}


@pytest.mark.parametrize("dense", [False, True])
def test_spans_on_their_threads_with_ids(traced, dense):
    results, _, events = traced[dense]
    query_lines = {k for k, n, _ in events if n == "query"}
    dispatcher_lines = {k for k, n, _ in events if n == "service.window"}
    assert len(query_lines) == len(results)   # one analyst thread each
    assert len(dispatcher_lines) == 1
    seen = {n for _, n, _ in events}
    walk = {"walk_sample", "walk.blocks", "walk.draw"}
    want = set(spans.SPANS) - (walk if dense else set())
    assert want <= seen, want - seen
    if dense:
        assert not walk & seen
    qids = {r.telemetry.query_id for r in results}
    assert len(qids) == len(results) and None not in qids
    for key, name, stats in events:
        if name in spans.QUERY_SPANS:
            assert key in query_lines, name
            assert stats["query_id"] in qids, (name, stats)
        else:
            assert key in dispatcher_lines, name
            assert stats["window_id"] >= 1, (name, stats)


@pytest.mark.parametrize("dense", [False, True])
def test_window_names_the_queries_it_served(traced, dense):
    results, _, events = traced[dense]
    qids = {r.telemetry.query_id for r in results}
    windows = [s for _, n, s in events if n == "service.window"]
    served = set()
    for stats in windows:
        ids = _ids(stats["query_ids"])
        assert ids and ids <= qids, stats
        assert stats["rows"] > 0
        served |= ids
    assert served == qids
    assert len({s["window_id"] for s in windows}) == len(windows)
    # a query's spans name one query: its own, on its own thread
    by_line = {}
    for key, name, stats in events:
        if name in spans.QUERY_SPANS:
            by_line.setdefault(key, set()).add(stats["query_id"])
    assert all(len(v) == 1 for v in by_line.values())


@pytest.mark.parametrize("dense", [False, True])
def test_timings_split_sampling_and_oracle_wait(traced, dense):
    results, _, _ = traced[dense]
    for r in results:
        t = r.telemetry.timings
        assert t["sample_s"] > 0 and t["oracle_wait_s"] > 0
        assert ("walk_s" in t) != dense
        if not dense:
            assert 0 < t["walk_s"] <= t["sample_s"]
            assert t["walk_blocks_s"] + t["walk_draw_s"] <= t["walk_s"]
            assert t["walk_launches"] >= 1 and t["walk_fetch_bytes"] > 0
        assert t["sample_s"] + t["oracle_wait_s"] <= (
            t["pilot_s"] + t["execute_s"] + 1e-6)


@pytest.mark.parametrize("dense", [False, True])
def test_queue_wait_within_assembly(traced, dense):
    _, tracker, _ = traced[dense]
    assembly = tracker.series["service.window.assembly_ms"]
    queue = tracker.series["service.window.queue_ms"]
    assert len(assembly) == len(queue) > 0
    assert all(0 <= q <= a for q, a in zip(queue, assembly))
    assert all(v >= 0 for v in tracker.series["service.dispatcher.starved_ms"])


def test_starved_counts_only_while_a_client_is_attached():
    """The dispatcher's wait on an empty queue is starved only while a
    client is attached; before the first attach and after a detach it is
    idle, and not counted."""
    from repro.core import ArrayOracle, OracleBatch

    tracker = ListTracker()
    svc = OracleService(max_wait_ms=1.0, tracker=tracker)
    try:
        for _ in range(2):
            oracle = ArrayOracle(np.ones((4, 4)))
            time.sleep(0.3)                  # idle: nothing attached
            svc.attach(oracle)
            time.sleep(0.1)                  # starved: attached, no flush in
            batch = OracleBatch(oracle)
            batch.submit(np.array([[0, 1], [2, 3]]))
            batch.flush_async().result(timeout=60)
            svc.detach(oracle)
    finally:
        svc.close()
    starved = tracker.series["service.dispatcher.starved_ms"]
    assert len(starved) == 2
    assert all(50 <= v < 300 for v in starved), starved


@pytest.mark.parametrize("path", ["dense", "streaming"])
def test_cascade_sampling_spans_nest_the_walk(path):
    """The cascade path shares the streaming space builder, so its walk
    spans sit inside ``sample`` spans as on the plain paths."""
    from repro.core import Agg, ArrayOracle, Query, run_bas_cascade

    ds = make_clustered_tables(80, 80, n_entities=120, noise=0.4, seed=3)
    q = Query(spec=ds.spec(), agg=Agg.COUNT, oracle=ds.oracle(), budget=600,
              proxy=ArrayOracle(ds.truth.astype(np.float64)))
    t = run_bas_cascade(q, seed=2, path=path).telemetry.timings
    assert t["sample_s"] > 0
    assert ("walk_s" in t) == (path == "streaming")
    assert t.get("walk_s", 0.0) <= t["sample_s"]


def test_pool_shards_carry_the_window_id(tmp_path, scorer):
    """With a worker pool the scorer runs on the pool's threads; its spans
    still name the window they serve."""
    from repro.core import OracleBatch

    svc = OracleService(workers=2, min_shard=8, max_wait_ms=1.0)
    oracle = ModelOracle(scorer, threshold=0.5)
    oracle.bind_sizes((40, 60))
    svc.attach(oracle)
    rng = np.random.default_rng(4)
    batch = OracleBatch(oracle)
    batch.submit(np.stack([rng.integers(0, 40, 48), rng.integers(0, 60, 48)],
                          axis=1))
    jax.profiler.start_trace(str(tmp_path))
    try:
        batch.flush_async().result(timeout=300)
    finally:
        jax.profiler.stop_trace()
        svc.close()
    events = _events(tmp_path)
    windows = {s["window_id"] for _, n, s in events if n == "service.window"}
    dispatcher = {k for k, n, _ in events if n == "service.window"}
    forwards = [(k, s) for k, n, s in events if n == "scorer.forward"]
    assert len(windows) == 1 and forwards
    assert {k for k, _ in forwards}.isdisjoint(dispatcher)
    assert {s["window_id"] for _, s in forwards} == windows


def test_span_accumulates_and_binds():
    timings = {}
    for _ in range(3):
        with spans.span("sample", timings, "sample_s"):
            pass
    assert set(timings) == {"sample_s"} and timings["sample_s"] >= 0
    assert spans.current_ids() == {} and spans.bound_timings() is None
    with spans.bind(timings=timings, query_id=7):
        with spans.bind(window_id=2):
            assert spans.current_ids() == {"query_id": 7, "window_id": 2}
            assert spans.bound_timings() is timings
        assert spans.current_ids() == {"query_id": 7}
    assert spans.current_ids() == {} and spans.bound_timings() is None
    a, b = spans.next_query_id(), spans.next_query_id()
    assert b > a >= 1


def _mixed_pairs(scorer, n: int = 50):
    rng = np.random.default_rng(9)
    pairs = np.stack([rng.integers(0, 40, n), rng.integers(0, 60, n)], axis=1)
    lens = np.array([min(len(scorer.tokenize_pair(p)), MAX_LEN)
                     for p in pairs])
    return pairs, lens


def _hand_slots(lens) -> int:
    """Token slots of one ``score`` call: rows at buckets 16, 32, 64, in
    blocks of ``BATCH`` rows, the last block padded with empty rows."""
    slots, lo = 0, 0
    for b in (16, 32, MAX_LEN):
        rows = int(((lens > lo) & (lens <= b)).sum())
        slots += -(-rows // BATCH) * BATCH * b
        lo = b
    return slots


def test_token_counters_match_hand_count(scorer):
    from repro.serve.serve_loop import PairScorer

    pairs, lens = _mixed_pairs(scorer)
    assert len(set(lens.tolist())) > 3
    s = PairScorer(scorer.cfg, scorer.params, scorer.tokenize_pair,
                   scorer.yes_id, scorer.no_id, max_len=MAX_LEN,
                   batch_size=BATCH)
    s.score(pairs[:20])
    s.score(pairs[20:])
    assert s.token_slots == _hand_slots(lens[:20]) + _hand_slots(lens[20:])
    assert s.tokens == int(lens.sum()) < s.token_slots
    assert s.causal_pairs == int(sum(n * (n + 1) // 2 for n in lens))
    assert s.pairs_scored == len(pairs)
    # required work by hand for this dense SwiGLU config: q, k, v, o and
    # the three MLP matrices per layer, causal attention, a 2-column head
    c = s.cfg
    per_layer = (c.d_model * c.head_dim * (2 * c.num_heads + 2 * c.num_kv_heads)
                 + 3 * c.d_model * c.d_ff)
    hand = (2 * c.num_layers * per_layer * int(lens.sum())
            + 4 * c.num_layers * c.num_heads * c.head_dim
            * sum(int(n) * (int(n) + 1) // 2 for n in lens)
            + 4 * c.d_model * len(pairs))
    assert s.required_flops() == pytest.approx(hand, rel=1e-12)


def test_scorer_program_keeps_its_name(scorer):
    batch = {"tokens": jax.numpy.zeros((BATCH, 16), jax.numpy.int32),
             "last": jax.numpy.zeros(BATCH, jax.numpy.int32)}
    text = scorer._fwd.lower(scorer.params, batch).as_text()
    assert "module @jit_fwd" in text


def test_instance_hooks_see_every_block(scorer):
    """``score`` calls ``_tokenize`` and ``yes_no_logits`` through the
    instance, so a wrapper set there sees all of its work."""
    from repro.serve.serve_loop import PairScorer

    pairs, lens = _mixed_pairs(scorer)
    s = PairScorer(scorer.cfg, scorer.params, scorer.tokenize_pair,
                   scorer.yes_id, scorer.no_id, max_len=MAX_LEN,
                   batch_size=BATCH)
    tokenized, blocks = [], []
    tokenize, forward = s._tokenize, s.yes_no_logits

    def hook_tokenize(p):
        tokenized.append(len(p))
        return tokenize(p)

    def hook_forward(toks, last):
        blocks.append((toks.shape, int((np.asarray(toks)[:, 0] != 0).sum())))
        return forward(toks, last)

    s._tokenize, s.yes_no_logits = hook_tokenize, hook_forward
    out = s.score(pairs)
    assert tokenized == [len(pairs)]
    assert len(blocks) == s.forward_batches
    assert sum(real for _, real in blocks) == len(pairs)
    assert all(shape[0] == BATCH for shape, _ in blocks)
    assert sum(shape[0] * shape[1] for shape, _ in blocks) == s.token_slots
    np.testing.assert_allclose(out, scorer.score(pairs), atol=1e-6)


def test_expert_counters_match_hand_count(scorer):
    """A latent-attention MoE scorer counts every real token's routes in each
    MoE layer, the grouped matmul's rows by whole tiles per expert, and the
    largest expert's rows; ``counters()`` returns them with the common four,
    and the required work is counted by hand."""
    from repro.configs import get_smoke_config
    from repro.models import init_params
    from repro.models.layers import GMM_ROWS
    from repro.serve.serve_loop import PairScorer

    cfg = get_smoke_config("deepseek-v2-lite", vocab_size=scorer.cfg.vocab_size,
                           num_layers=3)
    s = PairScorer(cfg, init_params(cfg, jax.random.key(1)), scorer.tokenize_pair,
                   scorer.yes_id, scorer.no_id, max_len=MAX_LEN, batch_size=BATCH)
    blocks = []
    forward = s.yes_no_logits

    def hook_forward(toks, last):
        out = forward(toks, last)
        real = np.asarray(toks)[:, 0] != 0
        blocks.append((np.asarray(s._side.sizes),
                       int((np.asarray(last)[real] + 1).sum())))
        return out

    s.yes_no_logits = hook_forward
    pairs, lens = _mixed_pairs(scorer)
    s.score(pairs[:20])
    s.score(pairs[20:])
    k, moe_layers = cfg.num_experts_per_tok, 2
    rows = load = 0
    for sizes, real in blocks:
        assert sizes.shape == (moe_layers, cfg.num_experts)
        assert (sizes.sum(axis=1) == real * k).all()   # dropless, no padding
        for layer in sizes:
            start = 0
            for n in layer.tolist():
                if n:
                    first, end = start // GMM_ROWS, -(-(start + n) // GMM_ROWS)
                    rows += (end - first) * GMM_ROWS
                start += n
            load += int(layer.max())
    tokens = int(lens.sum())
    assert s.counters() == {
        "tokens": tokens, "token_slots": s.token_slots,
        "causal_pairs": int(sum(n * (n + 1) // 2 for n in lens)),
        "pairs_scored": len(pairs), "routed_rows": tokens * k * moe_layers,
        "expert_rows": rows, "expert_load_max": load}
    # per token: three MLA blocks, the dense SwiGLU, and per MoE layer the
    # router, the shared expert and k routed ones; per causal pair the
    # query-key and value widths of each head; a 2-column head per pair
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv, ef = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                      cfg.moe_d_ff)
    mla = d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d
    moe = d * cfg.num_experts + (cfg.num_shared_experts + k) * 3 * d * ef
    per_tok = 3 * mla + 3 * d * cfg.d_ff + moe_layers * moe
    hand = (2 * per_tok * tokens
            + 3 * 2 * h * (dn + dr + dv) * sum(int(n) * (int(n) + 1) // 2 for n in lens)
            + 4 * d * len(pairs))
    assert s.required_flops() == pytest.approx(hand, rel=1e-12)
