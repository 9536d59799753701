"""Public op: per-left-row top-k similar right rows (NN blocking)."""
import jax.numpy as jnp
import numpy as np

from .. import interpret_mode
from ..padding import col_block, pad_rows, row_block
from .kernel import sim_topk_pallas
from .ref import sim_topk_ref  # noqa: F401


def sim_topk(e1, e2, k=8, block=256):
    e1 = np.asarray(e1, np.float32)
    e2 = np.asarray(e2, np.float32)
    n1, n2 = e1.shape[0], e2.shape[0]
    bm, bn = row_block(block, n1), col_block(block, n2)
    e1, _ = pad_rows(e1, bm)
    e2, _ = pad_rows(e2, bn)
    vals, idx = sim_topk_pallas(
        jnp.asarray(e1), jnp.asarray(e2), k=min(k, bn), bm=bm, bn=bn,
        interpret=interpret_mode(),
    )
    vals, idx = np.asarray(vals)[:n1], np.asarray(idx)[:n1]
    # drop hits pointing at padded right rows (score 0 ties)
    valid = idx < n2
    return vals, idx, valid
