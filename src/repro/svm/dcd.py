"""Dual coordinate descent for L2-loss (squared-hinge) linear SVM —
the LIBLINEAR algorithm the paper's BMF baseline uses (Hsieh et al. 2008).

Block-minimization training (Yu et al. 2012): load one block of instances,
run ``sweeps`` DCD passes over its dual variables, move to the next block.
The dual variables persist across epochs; only the *block composition*
differs between BMF (fixed random partition) and LIRS (fresh partition per
epoch) — which is exactly the variable the paper studies.

``solve_block_csr`` consumes CSR batches straight off the ragged read
path (repro.svm.sparse) without densifying: the sequential dual updates
touch only each instance's nonzeros, and the O(B·nnz) batch inner
products (``margins_csr``) run on-device through ``ops.csr_dot``, a
gather-and-reduce over the dense weight vector.

The weights: ``w`` is a float64 vector on the host.  Assigning
``solver.w = v`` copies ``v``; reading ``solver.w`` gives a read-only
view, so an in-place write from outside raises ``ValueError`` (assign a
new vector instead).  ``margins_csr`` keeps a float32 copy of ``w`` on
the device and uploads it again only after ``w`` has changed: by
assignment, ``solve_block`` or ``solve_block_csr``.  Scoring many
batches against one ``w`` uploads it once; training, which changes
``w`` between calls, uploads it on every call.  Two counters, always
on: ``margins_calls`` (calls to ``margins_csr``) and ``w_uploads``
(uploads of ``w`` among them).
"""
from __future__ import annotations

import numpy as np

from repro.obs import trace as _trace


class DCDSolver:
    def __init__(self, dim: int, n: int, C: float = 1.0):
        self.C = C
        self._w = np.zeros(dim)
        self._w_dev = None  # float32 copy of _w on the device; None = stale
        self.alpha = np.zeros(n)
        self.margins_calls = 0
        self.w_uploads = 0

    @property
    def w(self) -> np.ndarray:
        """The float64 weights, as a read-only view."""
        view = self._w.view()
        view.flags.writeable = False
        return view

    @w.setter
    def w(self, value) -> None:
        self._w = np.array(value, dtype=np.float64)
        self._w_dev = None

    def solve_block(self, xs: np.ndarray, ys: np.ndarray, idx: np.ndarray, sweeps: int = 5):
        """Run DCD sweeps over the dual coordinates of one block."""
        self._w_dev = None
        w, alpha, C = self._w, self.alpha, self.C
        xb = xs[idx]
        yb = ys[idx]
        xsq = (xb * xb).sum(1) + 1.0 / (2 * C)
        for _ in range(sweeps):
            for j, i in enumerate(idx):
                g = yb[j] * (xb[j] @ w) - 1.0 + alpha[i] / (2 * C)
                if alpha[i] > 0 or g < 0:
                    na = max(alpha[i] - g / xsq[j], 0.0)
                    if na != alpha[i]:
                        w += (na - alpha[i]) * yb[j] * xb[j]
                        alpha[i] = na

    def solve_block_csr(self, csr, idx: np.ndarray, sweeps: int = 5):
        """DCD sweeps over one block of CSR instances (no densification).

        ``csr`` is a :class:`repro.svm.sparse.CSRBatch` whose row ``j``
        is global instance ``idx[j]`` (the dual coordinate it owns).
        Labels come from the batch itself — the ragged read path carries
        them inside each record.  Identical update rule to
        :meth:`solve_block`; each coordinate step touches only the
        instance's nonzeros, so a sweep is O(block nnz), not O(B·dim).
        """
        self._w_dev = None
        w, alpha, C = self._w, self.alpha, self.C
        rp = csr.row_ptr
        cols = csr.indices.astype(np.int64)
        vals = csr.values.astype(np.float64)
        yb = csr.labels.astype(np.float64)
        xsq = self._row_sq_norms(rp, cols, vals) + 1.0 / (2 * C)
        for _ in range(sweeps):
            for j, i in enumerate(idx):
                s, e = rp[j], rp[j + 1]
                cj = cols[s:e]
                vj = vals[s:e]
                g = yb[j] * (vj @ w[cj]) - 1.0 + alpha[i] / (2 * C)
                if alpha[i] > 0 or g < 0:
                    na = max(alpha[i] - g / xsq[j], 0.0)
                    if na != alpha[i]:
                        # np.add.at, not fancy +=: a row listing the same
                        # feature twice must accumulate both coefficients
                        # (CSR semantics, matching csr_to_dense / csr_dot)
                        np.add.at(w, cj, (na - alpha[i]) * yb[j] * vj)
                        alpha[i] = na

    @staticmethod
    def _row_sq_norms(rp, cols, vals) -> np.ndarray:
        """Per-row ||x_j||² under CSR accumulate semantics: duplicate
        feature ids sum *before* squaring (exactly what densification
        yields), so the coordinate minimizer's denominator matches the
        dense solver bit-for-bit on duplicate-bearing rows too."""
        b = len(rp) - 1
        nnz = len(cols)
        if nnz == 0:
            return np.zeros(b)
        rows = np.repeat(np.arange(b), np.diff(rp).astype(np.int64))
        perm = np.lexsort((cols, rows))
        rc, cc, vv = rows[perm], cols[perm], vals[perm]
        starts = np.flatnonzero(
            np.concatenate(
                ([True], (rc[1:] != rc[:-1]) | (cc[1:] != cc[:-1]))
            )
        )
        combined = np.add.reduceat(vv, starts)
        return np.bincount(rc[starts], combined * combined, minlength=b)

    def margins_csr(self, csr) -> np.ndarray:
        """Batch inner products ``X w`` on-device (``ops.csr_dot``).

        The device sees ``w`` in float32, cast on the host.  That copy is
        uploaded on the first call and again only after ``w`` has changed
        (assignment or a solve); otherwise a call uploads just the padded
        batch.  Each call adds one to ``margins_calls``, each upload of
        ``w`` one to ``w_uploads``.
        """
        import jax
        import jax.numpy as jnp

        from repro.kernels import ops
        from repro.svm.sparse import pad_csr

        self.margins_calls += 1
        with _trace.span("svm/margins", "svm"):
            with _trace.span("svm/pad", "svm"):
                idx2d, val2d = pad_csr(csr)
            # the batch's uploads, and w's (with its host cast) if stale
            with _trace.span("svm/put", "svm"):
                if self._w_dev is None:
                    with _trace.span("svm/put_w", "svm"):
                        self._w_dev = jax.device_put(
                            self._w.astype(np.float32))
                    self.w_uploads += 1
                operands = (jnp.asarray(idx2d), jnp.asarray(val2d),
                            self._w_dev)
            with _trace.span("svm/csr_dot", "svm"):
                out = ops.csr_dot(*operands)
            # waits for the device, then copies the margins back
            with _trace.span("svm/get", "svm"):
                return np.asarray(out)

    def primal_objective_csr(self, csr) -> float:
        """Squared-hinge primal on one CSR batch, margins via the kernel."""
        m = np.maximum(0.0, 1.0 - csr.labels * self.margins_csr(csr))
        return float(0.5 * self.w @ self.w + self.C * (m * m).sum())

    def primal_objective(self, xs: np.ndarray, ys: np.ndarray) -> float:
        m = np.maximum(0.0, 1.0 - ys * (xs @ self.w))
        return float(0.5 * self.w @ self.w + self.C * (m * m).sum())

    def accuracy(self, xs: np.ndarray, ys: np.ndarray) -> float:
        pred = np.sign(xs @ self.w)
        pred[pred == 0] = 1
        return float((pred == ys).mean())
