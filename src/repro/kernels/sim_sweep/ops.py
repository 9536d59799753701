"""Public op: fused single-sweep stratification pass with numpy in/out.

One blocked pass over ``E1 @ E2^T`` yields everything the streaming
stratifier needs: the global weight histogram (exact integer column sum of
the per-block tiles), per-(row-block, bin) count tiles for targeted rescans,
the per-left-row top-k similar right rows for blocking-regime collection,
and compensated per-row walk sums (the wandering-join proposal normaliser —
see ``repro.core.bas_streaming``).  Padding corrections for the counts are
the shared ``repro.kernels.padding`` helpers, exact, so the fp32 counts
equal ``sim_sweep_ref`` on the unpadded inputs bit for bit; the walk sums
need none because the backward vector is zero in padded columns.

``precision`` selects the compute path: ``"fp32"`` (default, histogram
bit-identical to ``sim_sweep_ref``), ``"bf16"`` (bf16 MXU inputs,
f32 accumulation), or ``"int8"`` (per-row symmetric quantisation via
``repro.core.similarity.quantize_rows_int8``, int32 MXU accumulation).

Chain callers sweep many left blocks against one fixed right table: build a
:class:`PreparedRight` once with :func:`prepare_right` and pass it as
``right=`` so padding/quantisation/upload of the right side happen once, not
per prefix block.

Block shapes route through :mod:`repro.kernels.autotune` on a TPU (the
tuned (bm, bn) schedule is cached on disk next to the index store); under
the interpreter the power-of-two defaults of
:func:`repro.kernels.padding.row_block` / ``col_block`` apply.  Whether the
kernel is interpreted is decided once, by
:func:`repro.kernels.interpret_mode`.
"""
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import autotune, interpret_mode
from ..padding import col_block, pad_rows, remove_pad_counts, row_block
from .kernel import sim_sweep_pallas, sim_sweep_q_pallas
from .ref import sim_sweep_ref  # noqa: F401  (oracle for tests/benchmarks)

PRECISIONS = ("fp32", "bf16", "int8")


class PreparedRight(NamedTuple):
    """Right table, padded (and quantised for int8) once for many sweeps."""

    n2: int
    bn: int
    p2: int
    precision: str
    e2p: jax.Array            # padded f32 embeddings (device)
    q2: Optional[jax.Array]   # int8 path only
    rs2: Optional[jax.Array]  # int8 path only


class SweepOut(NamedTuple):
    counts: np.ndarray        # (n_bins,) int64, padding-corrected
    edges: np.ndarray         # (n_bins + 1,) bin edges over [0, 1]
    block_counts: np.ndarray  # (ceil(n1/block_rows), n_bins) int64
    block_rows: int           # left rows per count tile
    vals: np.ndarray          # (n1, k) f32 clipped top-k scores
    idx: np.ndarray           # (n1, k) i32 right-row indices
    valid: np.ndarray         # (n1, k) bool — False for padded-column hits
    row_sums: np.ndarray      # (n1,) f64 compensated walk sums


def prepare_right(e2, block=256, precision="fp32",
                  n1_hint: Optional[int] = None) -> PreparedRight:
    assert precision in PRECISIONS, precision
    e2 = np.asarray(e2, np.float32)
    n2 = e2.shape[0]
    sched = autotune.schedule("sim_sweep", n1_hint or n2, n2, e2.shape[1],
                              precision)
    bn = col_block(block if sched is None else sched[1], n2)
    e2p, p2 = pad_rows(e2, bn)
    q2 = rs2 = None
    if precision == "int8":
        from repro.core.similarity import quantize_rows_int8

        q2np, rs2np = quantize_rows_int8(e2p)
        q2, rs2 = jnp.asarray(q2np), jnp.asarray(rs2np)
    return PreparedRight(n2=n2, bn=bn, p2=p2, precision=precision,
                         e2p=jnp.asarray(e2p), q2=q2, rs2=rs2)


def sim_sweep(e1, e2=None, n_bins=4096, exponent=1.0, floor=1e-3, k=8,
              block=256, scale=None, precision="fp32",
              right: Optional[PreparedRight] = None, back_v=None,
              rs_exponent=None) -> SweepOut:
    """``back_v`` (optional, (n2,) f32) is the backward chain vector applied
    inside the walk sums; ``rs_exponent`` (optional) overrides the weight
    power for the sums only (chain sweeps bin at ``exponent * root`` but
    need the raw full-exponent edge weight in the walk sums)."""
    assert precision in PRECISIONS, precision
    e1 = np.asarray(e1, np.float32)
    n1 = e1.shape[0]
    if right is None:
        assert e2 is not None, "pass e2 or a PreparedRight"
        right = prepare_right(e2, block, precision, n1_hint=n1)
    assert right.precision == precision, (right.precision, precision)
    n2 = right.n2
    sched = autotune.schedule("sim_sweep", n1, n2, e1.shape[1], precision)
    bm = row_block(block if sched is None else sched[0], n1)
    bn = right.bn
    e1p, p1 = pad_rows(e1, bm)
    s = np.ones(n1, np.float32) if scale is None else np.asarray(scale, np.float32)
    sp = np.concatenate([s, np.zeros(p1, np.float32)]) if p1 else s
    # backward vector, zero-padded so padded right columns drop out of the
    # walk sums with no host-side correction
    vp = np.zeros(right.e2p.shape[0], np.float32)
    vp[:n2] = 1.0 if back_v is None else np.asarray(back_v, np.float32)
    kk = min(k, bn)
    common = dict(n_bins=n_bins, exponent=exponent, rs_exponent=rs_exponent,
                  floor=floor, k=kk, bm=bm, bn=bn, interpret=interpret_mode())
    if precision == "int8":
        from repro.core.similarity import quantize_rows_int8

        q1, rs1 = quantize_rows_int8(e1p)
        bc, vals, idx, rs = sim_sweep_q_pallas(
            jnp.asarray(q1), right.q2, jnp.asarray(rs1), right.rs2,
            jnp.asarray(sp), jnp.asarray(vp), **common,
        )
    else:
        dtype = jnp.bfloat16 if precision == "bf16" else jnp.float32
        bc, vals, idx, rs = sim_sweep_pallas(
            jnp.asarray(e1p), right.e2p, jnp.asarray(sp), jnp.asarray(vp),
            compute_dtype=dtype, **common,
        )
    bc = np.asarray(bc).astype(np.int64)
    remove_pad_counts(bc, s, p1, right.p2, right.e2p.shape[0], n_bins,
                      exponent, floor, bm)
    counts = bc.sum(axis=0)
    edges = np.linspace(0.0, 1.0, n_bins + 1)
    vals = np.asarray(vals)[:n1]
    idx = np.asarray(idx)[:n1]
    row_sums = np.asarray(rs)[:n1, 0].astype(np.float64)
    return SweepOut(
        counts=counts, edges=edges, block_counts=bc, block_rows=bm,
        vals=vals, idx=idx, valid=idx < n2, row_sums=row_sums,
    )
