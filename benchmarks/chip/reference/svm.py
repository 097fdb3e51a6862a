"""Plain reference of sparse linear-SVM scoring: float64 numpy on the
host, over the records as generated (``benchmarks.chip.data``) and not
as read back.  It imports nothing of the program.

A margin is ``Σ_k v_k · w[i_k]`` over a record's nonzeros.  The program
computes it in float32 (the configuration's ``margin_dtype``); its error
is reported as a share of ``Σ_k |v_k · w[i_k]|``, the scale the rounding
of each term and of the sum is bounded by.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from benchmarks.chip import data

WEIGHT_STREAM = 4  # the weights' numpy stream of a seed


def weights(seed: int, dim: int) -> np.ndarray:
    """The fixed float64 weight vector a run scores with."""
    return data.rng(seed, WEIGHT_STREAM).normal(size=dim)


def csr_of(recs: Dict[str, np.ndarray], ids: np.ndarray) -> Dict[str, np.ndarray]:
    """The CSR arrays of records ``ids``, in that order."""
    rp = recs["row_ptr"]
    starts, ends = rp[ids], rp[ids + 1]
    nnz = ends - starts
    row_ptr = np.zeros(len(ids) + 1, np.int64)
    np.cumsum(nnz, out=row_ptr[1:])
    take = np.repeat(starts - row_ptr[:-1], nnz) + np.arange(row_ptr[-1])
    return {"indices": recs["indices"][take], "values": recs["values"][take],
            "row_ptr": row_ptr, "labels": recs["labels"][ids]}


def same_csr(csr, want: Dict[str, np.ndarray]) -> bool:
    """Exact equality of a packed batch (``CSRBatch``) with ``want``."""
    return (np.array_equal(np.asarray(csr.row_ptr, np.int64), want["row_ptr"])
            and np.array_equal(np.asarray(csr.indices, np.int64),
                               want["indices"].astype(np.int64))
            and np.array_equal(csr.values, want["values"])
            and np.array_equal(csr.labels, want["labels"]))


def terms(want: Dict[str, np.ndarray], w: np.ndarray):
    """Each row's float64 margin and its sum of |terms|."""
    rows = np.repeat(np.arange(len(want["row_ptr"]) - 1),
                     np.diff(want["row_ptr"]))
    t = want["values"].astype(np.float64) * w[want["indices"].astype(np.int64)]
    n = len(want["row_ptr"]) - 1
    return (np.bincount(rows, t, minlength=n),
            np.bincount(rows, np.abs(t), minlength=n))


def margin_error(margins: np.ndarray, want: Dict[str, np.ndarray],
                 w: np.ndarray) -> float:
    """The widest gap between ``margins`` and the float64 margins, each
    over its row's sum of |terms|.  A batch of the wrong length, or a
    margin that is not a number, reads infinity."""
    exact, scale = terms(want, w)
    m = np.asarray(margins, np.float64).reshape(-1)
    if m.shape != exact.shape or not np.isfinite(m).all():
        return float("inf")
    return float(np.max(np.abs(m - exact) / np.maximum(scale, 1e-300)))


def pad_width(csr, multiple: int = 8) -> int:
    """The padded row width ``K`` of a batch (the widest row rounded up)."""
    need = int(np.max(np.diff(csr.row_ptr)))
    return max(multiple, -(-need // multiple) * multiple)


def control_margins(want: Dict[str, np.ndarray], w: np.ndarray) -> np.ndarray:
    """The control: the same margins with values, weights and products
    rounded to bfloat16, the precision below float32 (on the host)."""
    import ml_dtypes

    bf16 = ml_dtypes.bfloat16
    rows = np.repeat(np.arange(len(want["row_ptr"]) - 1),
                     np.diff(want["row_ptr"]))
    v = want["values"].astype(bf16)
    g = w[want["indices"].astype(np.int64)].astype(bf16)
    t = (v * g).astype(np.float64)
    return np.bincount(rows, t, minlength=len(want["row_ptr"]) - 1)
