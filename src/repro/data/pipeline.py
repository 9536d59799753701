"""Host data pipeline for Oracle training/serving.

* :class:`ByteTokenizer` — reversible byte-level tokenizer with specials.
* :func:`make_entity_corpus` — synthetic record corpus with latent entities
  (noisy string variants), the learnable analog of the paper's EM datasets:
  the Oracle LM is trained to answer whether two records denote one entity.
* :func:`pair_example` — serializes a record pair into the pair-scoring
  prompt  ``[BOS] r1 [SEP] r2 [SCORE] -> {YES|NO}`` (Narayan et al. style).
* :class:`ShardedLoader` — deterministic per-host batch shards with
  background prefetch; the batch at step s is a pure function of (seed, s)
  so restarts resume the exact stream (fault tolerance).
"""
from __future__ import annotations

import queue
import string
import threading
from typing import Optional

import numpy as np


class ByteTokenizer:
    PAD, BOS, EOS, SEP, SCORE, YES, NO = 0, 1, 2, 3, 4, 5, 6
    N_SPECIAL = 8

    @property
    def vocab_size(self) -> int:
        return 256 + self.N_SPECIAL

    def encode(self, text: str) -> list:
        return [b + self.N_SPECIAL for b in text.encode("utf-8")]

    def decode(self, ids) -> str:
        return bytes(
            int(i) - self.N_SPECIAL for i in ids if int(i) >= self.N_SPECIAL
        ).decode("utf-8", errors="replace")


_WORDS = (
    "data systems corp labs global tech media group solutions net "
    "works dynamics micro quantum logic apex vertex nova prime delta"
).split()


def make_entity_corpus(
    n_entities: int = 64,
    records_per_entity: int = 4,
    noise: float = 0.1,
    seed: int = 0,
) -> tuple[list, np.ndarray]:
    """Returns (records, entity_ids): noisy string variants per entity."""
    rng = np.random.default_rng(seed)
    records, ids = [], []
    for e in range(n_entities):
        base = " ".join(rng.choice(_WORDS, size=3)) + f" {e % 97}"
        for _ in range(records_per_entity):
            chars = list(base)
            for i in range(len(chars)):
                if rng.random() < noise:
                    chars[i] = rng.choice(list(string.ascii_lowercase))
            records.append("".join(chars))
            ids.append(e)
    return records, np.array(ids)


def entity_records(entity_ids: np.ndarray, n_entities: int, seed: int = 0,
                   table: int = 0, noise: float = 0.1) -> list:
    """One record string per row of a table whose rows denote the latent
    entities ``entity_ids`` (< ``n_entities``): every entity has a base
    string shared by all tables (drawn from ``seed``), and each row is a
    noisy copy of it (character substitutions at rate ``noise``, drawn from
    ``(seed, table)``) — the string side of
    :func:`repro.data.make_clustered_tables`."""
    words = np.asarray(_WORDS)
    picks = np.random.default_rng(seed).integers(0, len(words),
                                                 (n_entities, 3))
    rng = np.random.default_rng([seed, table])
    out = []
    for e in np.asarray(entity_ids).tolist():
        base = np.frombuffer(
            (f"{e} " + " ".join(words[picks[e]])).encode(), np.uint8
        ).copy()
        hit = rng.random(len(base)) < noise
        base[hit] = rng.integers(ord("a"), ord("z") + 1, int(hit.sum()))
        out.append(base.tobytes().decode())
    return out


def pair_example(
    tok: ByteTokenizer, r1: str, r2: str, label: Optional[int], max_len: int
) -> tuple[np.ndarray, np.ndarray]:
    """Returns (tokens, loss_mask).  Label token is the final position."""
    ids = (
        [tok.BOS]
        + tok.encode(r1)[: max_len // 2 - 3]
        + [tok.SEP]
        + tok.encode(r2)[: max_len // 2 - 3]
        + [tok.SCORE]
    )
    mask = [0.0] * len(ids)
    if label is not None:
        ids.append(tok.YES if label else tok.NO)
        mask.append(1.0)
    ids = ids[:max_len]
    mask = mask[:max_len]
    pad = max_len - len(ids)
    return (
        np.array(ids + [tok.PAD] * pad, np.int32),
        np.array(mask + [0.0] * pad, np.float32),
    )


def make_pair_batch(
    tok: ByteTokenizer,
    records: list,
    entity_ids: np.ndarray,
    batch: int,
    max_len: int,
    rng: np.random.Generator,
    positive_fraction: float = 0.5,
):
    """Balanced labelled pair batch for Oracle training."""
    n = len(records)
    by_entity: dict = {}
    for i, e in enumerate(entity_ids):
        by_entity.setdefault(int(e), []).append(i)
    multi = [e for e, v in by_entity.items() if len(v) >= 2]
    toks = np.zeros((batch, max_len), np.int32)
    masks = np.zeros((batch, max_len), np.float32)
    labels = np.zeros((batch,), np.int32)
    for b in range(batch):
        if rng.random() < positive_fraction and multi:
            e = multi[rng.integers(len(multi))]
            i, j = rng.choice(by_entity[e], size=2, replace=False)
            label = 1
        else:
            i, j = rng.integers(n), rng.integers(n)
            label = int(entity_ids[i] == entity_ids[j])
        toks[b], masks[b] = pair_example(tok, records[i], records[j], label, max_len)
        labels[b] = label
    return {"tokens": toks, "loss_mask": masks, "labels": labels}


class ShardedLoader:
    """Deterministic, restartable, host-sharded loader with prefetch.

    ``batch_fn(rng) -> dict of np arrays (global_batch, ...)``; each host
    slices its contiguous shard [host_id * per_host : (host_id+1) * per_host].
    """

    def __init__(
        self,
        batch_fn,
        global_batch: int,
        num_hosts: int = 1,
        host_id: int = 0,
        seed: int = 0,
        start_step: int = 0,
        prefetch: int = 2,
    ):
        assert global_batch % num_hosts == 0
        self.batch_fn = batch_fn
        self.per_host = global_batch // num_hosts
        self.host_id = host_id
        self.seed = seed
        self.step = start_step
        self._q: queue.Queue = queue.Queue(maxsize=prefetch)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._work, daemon=True)
        self._thread.start()

    def _make(self, step: int):
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, step]))
        full = self.batch_fn(rng)
        lo = self.host_id * self.per_host
        hi = lo + self.per_host
        return jax_tree_slice(full, lo, hi)

    def _work(self):
        step = self.step
        while not self._stop.is_set():
            try:
                self._q.put((step, self._make(step)), timeout=0.2)
                step += 1
            except queue.Full:
                continue

    def __next__(self):
        step, batch = self._q.get()
        self.step = step + 1
        return step, batch

    def close(self):
        self._stop.set()


def jax_tree_slice(tree, lo, hi):
    import jax

    return jax.tree.map(lambda x: x[lo:hi], tree)
