"""Inputs made from ``--seed``, in bulk.

The record files have the distributions of ``repro.data.synthetic``
(a bigram token corpus; kdd-shaped sparse classification records) and
are written through the program's ``RecordWriter``, but every draw is a
whole-array numpy call: the program's generators loop in Python per
token and per record, which a run's set-up cannot afford.  The same
functions give the reference the records' contents without reading the
files back.
"""
from __future__ import annotations

import struct
from typing import Dict, Tuple

import numpy as np

TOKEN_CHOICES = 4        # bigram successors per token
TOKEN_FOLLOW = 0.8       # share of tokens drawn from the bigram table
LABEL_NOISE = 0.05       # share of sparse labels flipped


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy streams of one seed."""
    return np.random.default_rng([seed, stream])


def jax_key(seed: int, stream: int = 0):
    """A JAX key from a seed of any size (``PRNGKey`` keeps 32 bits)."""
    import jax

    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


# ---------------------------------------------------------------- tokens


def token_rows(seed: int, records: int, seq_len: int, vocab: int) -> np.ndarray:
    """``(records, seq_len + 1)`` int32 rows of a bigram corpus: a row
    starts uniform, then each token follows the previous one's bigram
    table with probability 0.8 and is uniform otherwise."""
    g = rng(seed, 1)
    trans = g.integers(0, vocab, size=(vocab, TOKEN_CHOICES))
    follow = g.random((records, seq_len)) < TOKEN_FOLLOW
    choice = g.integers(0, TOKEN_CHOICES, size=(records, seq_len))
    fresh = g.integers(0, vocab, size=(records, seq_len))
    rows = np.empty((records, seq_len + 1), np.int64)
    rows[:, 0] = g.integers(0, vocab, size=records)
    for t in range(seq_len):  # a Markov chain: vectorised over records
        nxt = trans[rows[:, t], choice[:, t]]
        rows[:, t + 1] = np.where(follow[:, t], nxt, fresh[:, t])
    return rows.astype(np.int32)


def write_token_corpus(path: str, rows: np.ndarray) -> None:
    from repro.storage.record_store import RecordWriter

    with RecordWriter(path, record_size=rows.shape[1] * 4) as w:
        for row in rows:
            w.append(row.tobytes())


# --------------------------------------------------------- sparse records


def sparse_records(seed: int, records: int, dim: int,
                   nnz_range: Tuple[int, int]) -> Dict[str, np.ndarray]:
    """kdd-shaped instances: ``nnz`` uniform in ``nnz_range``, distinct
    feature ids per record, normal values, labels from a random
    hyperplane with 5% flipped.  CSR arrays over all records."""
    g = rng(seed, 2)
    nnz = g.integers(nnz_range[0], nnz_range[1] + 1, size=records)
    row_ptr = np.zeros(records + 1, np.int64)
    np.cumsum(nnz, out=row_ptr[1:])
    total = int(row_ptr[-1])
    rows = np.repeat(np.arange(records), nnz)
    idx = g.integers(0, dim, size=total)
    # ids are distinct within a record: the few records that drew one
    # twice draw theirs again without replacement
    key = np.sort(rows * np.int64(dim) + idx)
    for r in np.unique(key[1:][key[1:] == key[:-1]] // dim):
        idx[row_ptr[r]:row_ptr[r + 1]] = g.choice(dim, size=nnz[r],
                                                  replace=False)
    val = g.normal(size=total).astype(np.float32)
    w_true = g.normal(size=dim) / np.sqrt(dim)
    margin = np.bincount(rows, val.astype(np.float64) * w_true[idx],
                         minlength=records)
    labels = np.where(margin >= 0, 1.0, -1.0)
    labels[g.random(records) < LABEL_NOISE] *= -1
    return {"indices": idx.astype(np.uint32), "values": val,
            "row_ptr": row_ptr, "labels": labels.astype(np.float32)}


def write_sparse(path: str, recs: Dict[str, np.ndarray]) -> float:
    """Records ``label f32 || nnz u32 || idx u32[nnz] || val f32[nnz]``;
    returns the mean record size in bytes."""
    from repro.storage.record_store import RecordWriter

    rp, idx, val, lab = (recs["row_ptr"], recs["indices"], recs["values"],
                         recs["labels"])
    idx_b, val_b = idx.tobytes(), val.tobytes()
    total = 0
    with RecordWriter(path) as w:
        for j in range(len(lab)):
            s, e = int(rp[j]), int(rp[j + 1])
            rec = (struct.pack("<fI", lab[j], e - s) + idx_b[4 * s:4 * e]
                   + val_b[4 * s:4 * e])
            w.append(rec)
            total += len(rec)
    return total / len(lab)
