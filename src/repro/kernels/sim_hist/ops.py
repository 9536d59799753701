"""Public op: fused similarity histogram with numpy in/out for the core
stratifier.  Runs the Pallas kernel (compiled on a TPU, interpreted
elsewhere — :func:`repro.kernels.interpret_mode`) and pads inputs to block
multiples.  The optional ``scale`` vector (per-left-row
multiplier, e.g. chain-prefix weights) turns the pair histogram into a chain
weight histogram — see ``repro.core.stratify``."""
import jax.numpy as jnp
import numpy as np

from .. import autotune, interpret_mode
from ..padding import col_block, pad_rows, remove_pad_counts, row_block
from .kernel import sim_hist_pallas
from .ref import sim_hist_ref  # noqa: F401  (oracle for tests/benchmarks)


def sim_hist(e1, e2, n_bins=4096, exponent=1.0, floor=1e-3, block=256,
             scale=None):
    """Returns (counts[n_bins], edges[n_bins+1]); histogram of (optionally
    row-scaled) pair weights.

    Padded left rows get scale 0 (weight 0 -> bin 0); padded right columns
    pair with real rows at weight ``scale_i * floor**exponent``.  Both
    contributions are computed exactly on the host and subtracted
    (``repro.kernels.padding`` — shared with ``sim_sweep`` so the two stay
    bit-identical).
    """
    e1 = np.asarray(e1, np.float32)
    e2 = np.asarray(e2, np.float32)
    n1, n2 = e1.shape[0], e2.shape[0]
    sched = autotune.schedule("sim_hist", n1, n2, e1.shape[1])
    bm_cap, bn_cap = sched or (block, block)  # tuned shapes on a TPU only
    bm, bn = row_block(bm_cap, n1), col_block(bn_cap, n2)
    e1p, p1 = pad_rows(e1, bm)
    e2p, p2 = pad_rows(e2, bn)
    s = np.ones(n1, np.float32) if scale is None else np.asarray(scale, np.float32)
    sp = np.concatenate([s, np.zeros(p1, np.float32)]) if p1 else s
    counts = np.asarray(
        sim_hist_pallas(
            jnp.asarray(e1p), jnp.asarray(e2p), jnp.asarray(sp), n_bins=n_bins,
            exponent=exponent, floor=floor, bm=bm, bn=bn,
            interpret=interpret_mode(),
        )
    ).astype(np.int64)
    # remove padded-pair contributions (one global "block": bm >= n1)
    remove_pad_counts(counts.reshape(1, -1), s, p1, p2, e2p.shape[0], n_bins,
                      exponent, floor, bm=max(n1, 1))
    edges = np.linspace(0.0, 1.0, n_bins + 1)
    return counts, edges
