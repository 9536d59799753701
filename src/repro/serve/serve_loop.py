"""Serving layer: batched pair scoring (the Oracle endpoint BAS calls) and a
slot-based continuous batcher for autoregressive decode.

PairScorer — the paper's Oracle as a service: serialize a record pair to
tokens, run the scoring LM, read P(match) from the final-position logits of
the YES/NO token ids.  The Oracle batch layer (``repro.core.oracle``) hands
it one deduped request per pipeline stage; the scorer buckets those requests
into a small set of padded (batch, length) shapes — power-of-two sequence
buckets × a fixed batch dim — so the jitted forward compiles O(log max_len)
times total, and optionally shards the batch dimension over a device mesh
(``mesh=``, data-parallel ``shard_map``) so throughput scales with device
count.

ContinuousBatcher — fixed B decode slots; finished sequences vacate their
slot and queued requests are admitted mid-flight (per-slot positions), the
standard serving pattern for mixed-length batches.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.sharding import data_parallel, mesh_batch_shards
from repro.models import decode_step, init_cache, last_position_logits
from repro.models.config import ModelConfig
from repro.models.layers import grouped_rows
from repro.obs.spans import span


def _stable_yes_no_prob(lg: np.ndarray) -> np.ndarray:
    """P(yes) from (n, 2) [yes, no] logits, max-subtracted so large logits
    cannot overflow ``exp`` into NaN."""
    m = lg.max(axis=1, keepdims=True)
    e = np.exp(lg - m)
    return e[:, 0] / (e[:, 0] + e[:, 1])


def required_flops(cfg: ModelConfig, tokens: int, causal_pairs: int,
                   pairs: int) -> float:
    """FLOPs that scoring ``pairs`` pairs of ``tokens`` real tokens in all
    needs, padding excluded: 2 x the active parameters outside the
    embeddings and norms per token, 4 x ``num_heads x head_dim`` per causal
    (query, key) pair in each attention layer, and the 2-column yes/no head
    per pair (the head the scorer applies).  Latent
    attention counts its query-key and value widths per pair, and its
    latent norm among the norms."""
    d = cfg.d_model
    embed = cfg.vocab_size * d * (1 if cfg.tied_embeddings else 2)
    norms = (2 * d + cfg.kv_lora_rank) * cfg.num_layers
    matmul = cfg.active_param_count() - embed - norms
    attn_layers = sum(t in ("dense", "moe", "attn") for t in cfg.layer_types())
    if cfg.kv_lora_rank:
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        per_pair = 2 * cfg.num_heads * (qk + cfg.v_head_dim)
    else:
        per_pair = 4 * cfg.num_heads * cfg.head_dim
    return (2.0 * matmul * tokens
            + 1.0 * attn_layers * per_pair * causal_pairs
            + 4.0 * d * pairs)


class PairScorer:
    """Batched Oracle scoring: score(idx_pairs) -> P(match) per pair.

    ``mesh`` (optional) enables the data-parallel path: the batch dimension
    of the jitted forward is sharded over the mesh's batch axes (SERVE_RULES)
    via ``shard_map``; ``batch_size`` is rounded up to a multiple of the
    shard count.  ``forward_batches`` counts compiled-forward invocations —
    the unit the ceil(unique/batch_size) bound is stated in.  Per padded
    block ``score`` also counts ``token_slots`` (``pad_len x batch_size``),
    ``tokens`` (real tokens) and ``causal_pairs`` (sum of L(L+1)/2 over the
    real rows), so ``1 - tokens / token_slots`` is the padding share and
    :meth:`required_flops` the model work done.  Its spans: ``tokenize``, then per block ``scorer.pad``, ``scorer.forward``
    and ``scorer.fetch`` (waiting for the device's logits).

    Every family scores through :func:`~repro.models.last_position_logits`
    (the program ``jit_fwd``): the head at each row's last position, over
    the yes and no columns only, with padding routed to no expert.  The
    latent-attention family (``cfg.kv_lora_rank``) also counts
    ``routed_rows`` (real tokens x experts per token x MoE layers),
    ``expert_rows`` (rows the grouped expert matmul computed, tile rounding
    included) and ``expert_load_max`` (the largest expert's rows, summed
    over layers and blocks); :meth:`counters` returns them with the common
    four.
    """

    def __init__(self, cfg: ModelConfig, params, tokenize_pair: Callable,
                 yes_id: int, no_id: int, max_len: int = 128,
                 batch_size: int = 32, mesh=None, min_bucket: int = 16):
        self.cfg = cfg
        self.params = params
        self.tokenize_pair = tokenize_pair
        self.yes_id, self.no_id = yes_id, no_id
        self.max_len = max_len
        self.mesh = mesh
        self.forward_batches = 0   # compiled forward invocations
        self.pairs_scored = 0
        self.token_slots = 0
        self.tokens = 0
        self.causal_pairs = 0
        self.moe_layers = cfg.layer_types().count("moe") if cfg.kv_lora_rank else 0
        self.routed_rows = self.expert_rows = self.expert_load_max = 0
        self._count_lock = threading.Lock()   # score may run on several threads
        self._side = threading.local()        # a block's expert group sizes

        if cfg.kv_lora_rank and mesh is not None:
            raise NotImplementedError(
                f"{cfg.name}: the data-parallel scorer serves the dense family")

        def fwd(p, b):
            # [yes, no] logits at each row's last real position, computed and
            # gathered on the device so only (B, 2) values come back
            toks, last = b["tokens"], b["last"]
            # pad columns after ``last`` and pad rows (token 0 first)
            valid = ((jnp.arange(toks.shape[1])[None, :] <= last[:, None])
                     & (toks[:, :1] != 0))
            logits, sizes = last_position_logits(cfg, p, toks, last,
                                                 (yes_id, no_id), valid)
            return logits if sizes is None else (logits, sizes)

        if mesh is not None:
            shards = mesh_batch_shards(mesh)
            batch_size = -(-batch_size // shards) * shards
            fwd = data_parallel(fwd, mesh)
        self.batch_size = batch_size
        self._fwd = jax.jit(fwd)
        # power-of-two padded lengths: a bounded shape set, so long flushes
        # never recompile and short pairs don't pay max_len compute
        buckets = []
        b = max(min(min_bucket, max_len), 1)
        while b < max_len:
            buckets.append(b)
            b *= 2
        buckets.append(max_len)
        self._buckets = np.array(buckets, np.int64)

    def yes_no_logits(self, toks: np.ndarray, last: np.ndarray) -> jax.Array:
        """Device (B, 2) [yes, no] logits of one padded block (``B`` =
        ``batch_size``); sharded over the batch axes under ``mesh``."""
        self.forward_batches += 1
        out = self._fwd(self.params, {"tokens": jnp.asarray(toks),
                                      "last": jnp.asarray(last)})
        if self.moe_layers:
            out, self._side.sizes = out
        return out

    def encode(self, pairs: np.ndarray, pad_len: int) -> tuple:
        """Tokenize and pad ``pairs`` -> ((n, pad_len) tokens, (n,) last
        real position)."""
        return self._pad_block(self._tokenize(np.asarray(pairs)), pad_len)

    def required_flops(self) -> float:
        """FLOPs the pairs scored so far needed (:func:`required_flops`)."""
        with self._count_lock:
            return required_flops(self.cfg, self.tokens, self.causal_pairs,
                                  self.pairs_scored)

    def counters(self) -> dict:
        """The counters by name: the common four, and the expert counters of
        the latent-attention family."""
        names = ["tokens", "token_slots", "causal_pairs", "pairs_scored"]
        if self.moe_layers:
            names += ["routed_rows", "expert_rows", "expert_load_max"]
        with self._count_lock:
            return {k: getattr(self, k) for k in names}

    def _tokenize(self, pairs: np.ndarray) -> list:
        return [
            np.asarray(self.tokenize_pair(p), np.int32)[: self.max_len]
            for p in pairs
        ]

    @staticmethod
    def _pad_block(seqs: list, pad_len: int) -> tuple[np.ndarray, np.ndarray]:
        """Vectorized ragged->padded scatter: one fancy-index assignment for
        the whole block instead of a Python loop over rows."""
        n = len(seqs)
        lens = np.fromiter((len(s) for s in seqs), np.int64, n)
        toks = np.zeros((n, pad_len), np.int32)
        flat = np.concatenate(seqs) if n else np.zeros(0, np.int32)
        rows = np.repeat(np.arange(n), lens)
        starts = np.cumsum(lens) - lens
        cols = np.arange(int(lens.sum())) - np.repeat(starts, lens)
        toks[rows, cols] = flat
        return toks, np.maximum(lens - 1, 0).astype(np.int32)

    def score(self, pairs: np.ndarray) -> np.ndarray:
        pairs = np.asarray(pairs)
        n = len(pairs)
        if n == 0:
            return np.zeros(0, np.float64)
        with span("tokenize"):
            seqs = self._tokenize(pairs)
        lens = np.fromiter((len(s) for s in seqs), np.int64, n)
        pad_of = self._buckets[np.searchsorted(self._buckets, lens)]
        out = np.empty(n, np.float64)
        bs = self.batch_size
        slots = expert_rows = load_max = 0
        for pad_len in np.unique(pad_of):
            sel = np.nonzero(pad_of == pad_len)[0]
            for s in range(0, len(sel), bs):
                idxs = sel[s : s + bs]
                with span("scorer.pad"):
                    toks, last = self._pad_block([seqs[i] for i in idxs],
                                                 int(pad_len))
                    pad_rows = bs - len(idxs)
                    if pad_rows:
                        toks = np.concatenate(
                            [toks, np.zeros((pad_rows, int(pad_len)), np.int32)]
                        )
                        last = np.concatenate([last, np.zeros(pad_rows, np.int32)])
                slots += int(pad_len) * bs
                with span("scorer.forward"):
                    dev = self.yes_no_logits(toks, last)
                with span("scorer.fetch"):
                    lg = np.asarray(dev, np.float64)
                out[idxs] = _stable_yes_no_prob(lg)[: len(idxs)]
                if self.moe_layers:
                    sizes = np.asarray(self._side.sizes)
                    expert_rows += grouped_rows(sizes)
                    load_max += int(sizes.max(axis=-1).sum())
        with self._count_lock:
            self.routed_rows += (int(lens.sum()) * self.cfg.num_experts_per_tok
                                 * self.moe_layers)
            self.expert_rows += expert_rows
            self.expert_load_max += load_max
            self.pairs_scored += n
            self.token_slots += slots
            self.tokens += int(lens.sum())
            self.causal_pairs += int((lens * (lens + 1) // 2).sum())
        return out


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray           # (L,) int32
    max_new_tokens: int = 16
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False


class ContinuousBatcher:
    """Slot-based continuous batching over the single-token decode step.

    Prefill is run through decode steps token-by-token per slot (correct and
    simple; a production setup runs a separate prefill graph).  All slots
    advance together each step; empty slots decode a pad token into a junk
    region that is never read.

    Admission: for the attention families the batcher passes **per-slot
    positions** to ``decode_step``, so a queued request is admitted into any
    freed slot mid-flight — its position rewinds to 0 and the per-slot causal
    mask keeps it from attending to the previous occupant's stale KV entries.
    The recurrent families (ssm / hybrid ring-buffer) carry state that cannot
    be rewound per slot — and even an idle slot absorbs pad tokens into its
    state every step — so admission is gated there: requests are only
    admitted at step 0, and when every slot has drained the batcher resets
    the cache and admits the next wave.
    """

    def __init__(self, cfg: ModelConfig, params, batch_size: int = 4,
                 max_len: int = 256, eos_id: int = 1,
                 greedy: bool = True):
        self.cfg = cfg
        self.params = params
        self.b = batch_size
        self.max_len = max_len
        self.eos_id = eos_id
        self.cache = init_cache(cfg, batch_size, max_len)
        self.slots: list = [None] * batch_size
        self.pos = np.zeros(batch_size, np.int64)         # per-slot next write position
        self.prompt_left: list = [0] * batch_size
        self.queue: list = []
        self.finished: list = []
        self._step = jax.jit(
            lambda p, c, t, pos: decode_step(cfg, p, c, t, pos)
        )
        self.global_pos = 0
        self.per_slot_pos = cfg.has_positional_cache

    def submit(self, req: Request):
        self.queue.append(req)

    def _admit(self):
        if self.per_slot_pos:
            for i in range(self.b):
                if self.slots[i] is None and self.queue:
                    req = self.queue.pop(0)
                    self.slots[i] = req
                    self.prompt_left[i] = len(req.prompt)
                    self.pos[i] = 0
            return
        # gated admission (scalar position): recurrent state absorbs pad
        # tokens even in idle slots, so only step 0 is safe; once everything
        # drained, reset the cache and start a new wave
        if self.queue and self.global_pos > 0 and all(s is None for s in self.slots):
            self.cache = init_cache(self.cfg, self.b, self.max_len)
            self.global_pos = 0
        if self.global_pos != 0:
            return
        for i in range(self.b):
            if self.slots[i] is None and self.queue:
                req = self.queue.pop(0)
                self.slots[i] = req
                self.prompt_left[i] = len(req.prompt)
                self.pos[i] = 0

    def step(self):
        """Advance every active slot by one token."""
        self._admit()
        toks = np.zeros((self.b, 1), np.int32)
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            consumed = len(req.prompt) - self.prompt_left[i]
            if self.prompt_left[i] > 0:
                toks[i, 0] = req.prompt[consumed]
            else:
                toks[i, 0] = req.out_tokens[-1] if req.out_tokens else self.eos_id
        if self.per_slot_pos:
            position = jnp.asarray(np.minimum(self.pos, self.max_len - 1), jnp.int32)
        else:
            position = jnp.int32(self.global_pos)
        logits, self.cache = self._step(
            self.params, self.cache, jnp.asarray(toks), position
        )
        logits = np.asarray(logits, np.float32)
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            self.pos[i] += 1
            if self.per_slot_pos and self.pos[i] >= self.max_len:
                # positional cache capacity exhausted (possibly still
                # mid-prompt): keep this step's token if we were generating,
                # then terminate rather than clobber the last KV position.
                # Recurrent families have no positional capacity to exhaust.
                if self.prompt_left[i] <= 1:
                    req.out_tokens.append(int(np.argmax(logits[i])))
                req.done = True
                self.finished.append(req)
                self.slots[i] = None
                continue
            if self.prompt_left[i] > 1:
                self.prompt_left[i] -= 1
                continue
            if self.prompt_left[i] == 1:
                self.prompt_left[i] = 0  # last prompt token consumed: sample
            nxt = int(np.argmax(logits[i]))
            req.out_tokens.append(nxt)
            if nxt == self.eos_id or len(req.out_tokens) >= req.max_new_tokens:
                req.done = True
                self.finished.append(req)
                self.slots[i] = None
        self.global_pos += 1

    def run_until_done(self, max_steps: int = 10_000):
        while (any(s is not None for s in self.slots) or self.queue) and max_steps:
            self.step()
            max_steps -= 1
        return self.finished
