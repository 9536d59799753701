"""Inputs of a cell, made from ``--seed``: the two tables' embeddings, one
record string per row, and the oracle's weights.

Every seed gives the same multiset of record lengths (a fixed quantile grid
of the configuration's lognormal, permuted by the seed), so seeds change
which pairs are scored and not how much work a pair is.
"""
from __future__ import annotations

import functools
import statistics

import numpy as np

# printable record bytes: letters, digits and spaces, one byte one token
_ALPHABET = np.frombuffer(
    b"abcdefghijklmnopqrstuvwxyz0123456789     ", np.uint8)


def seed_seq(seed: int, *tags: int) -> np.random.SeedSequence:
    """A stream of its own for each tag path; any whole seed, also one
    beyond 32 bits or below 0."""
    return np.random.SeedSequence([int(seed) % 2**64, *tags])


def rng_for(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng(seed_seq(seed, *tags))


def make_tables(seed: int, n1: int, n2: int, d: int, n_entities: int,
                noise: float):
    """Clustered unit embeddings: rows are noisy copies of latent entity
    vectors, and two rows match when they share an entity (the generator of
    ``repro.data.make_clustered_tables``, without its dense truth matrix)."""
    rng = rng_for(seed, 1)
    ents = rng.standard_normal((n_entities, d), dtype=np.float32)
    ids1 = rng.integers(0, n_entities, size=n1)
    ids2 = rng.integers(0, n_entities, size=n2)
    emb1 = ents[ids1] + noise * rng.standard_normal((n1, d), dtype=np.float32)
    emb2 = ents[ids2] + noise * rng.standard_normal((n2, d), dtype=np.float32)
    return _normalize(emb1), _normalize(emb2)


def _normalize(e: np.ndarray) -> np.ndarray:
    n = np.linalg.norm(e, axis=1, keepdims=True)
    return (e / np.maximum(n, 1e-12)).astype(np.float32)


def record_lengths(n: int, median: float, sigma: float, lo: int, hi: int,
                   rng: np.random.Generator) -> np.ndarray:
    """``n`` token lengths: the lognormal's quantiles at (i + 0.5) / n,
    clipped to [lo, hi], in an order drawn from ``rng``."""
    z = np.array([statistics.NormalDist().inv_cdf((i + 0.5) / n)
                  for i in range(n)])
    lens = np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(np.int64)
    return rng.permutation(lens)


def make_records(seed: int, table: int, n: int, spec: dict) -> list:
    """One record string per row, with token lengths from ``spec`` (a
    table's ``records`` entry: ``median_tokens``, ``sigma``, ``min_tokens``,
    ``max_tokens``)."""
    rng = rng_for(seed, 2, table)
    lens = record_lengths(n, spec["median_tokens"], spec["sigma"],
                          spec["min_tokens"], spec["max_tokens"], rng)
    buf = _ALPHABET[rng.integers(0, len(_ALPHABET), int(lens.sum()))]
    text = buf.tobytes().decode("ascii")
    ends = np.cumsum(lens)
    starts = ends - lens
    return [text[s:e] for s, e in zip(starts.tolist(), ends.tolist())]


def weight_seed(seed: int) -> int:
    """A 32-bit key for ``jax.random`` from a seed of any size."""
    return int(seed_seq(seed, 3).generate_state(1)[0])


def make_params(cfg, seed: int):
    """Random weights of the oracle, made on the device in one jitted call
    and in the types the model serves them in.  The tree (names, shapes,
    dtypes) is the model's own; matrices are N(0, 1/fan_in) (the embedding
    N(0, 0.02^2)), and norm weights N(0, 0.1^2) so the reference sees them."""
    import jax
    import jax.numpy as jnp
    from repro.models import init_params

    shapes = jax.eval_shape(functools.partial(init_params, cfg),
                            jax.random.key(0))
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def scale(path, shape):
        name = str(getattr(path[-1], "key", path[-1]))
        if name.startswith("ln"):
            return 0.1
        if name == "embed":
            return 0.02
        return float(shape[-2]) ** -0.5

    @jax.jit
    def build(key):
        out = []
        for i, (path, s) in enumerate(paths):
            x = jax.random.normal(jax.random.fold_in(key, i), s.shape,
                                  jnp.float32)
            out.append((x * scale(path, s.shape)).astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return build(jax.random.key(weight_seed(seed)))
