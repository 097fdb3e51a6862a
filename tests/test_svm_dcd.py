"""DCD solver (LIBLINEAR-style) unit tests + the sparse CSR path:
vectorized arena packing, the on-device csr_dot, and end-to-end
training through the ragged multi-producer pipeline."""
import numpy as np
import pytest

from repro.svm.dcd import DCDSolver


def _separable(n=400, dim=32, seed=0, margin=0.5):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=dim)
    w /= np.linalg.norm(w)
    xs, ys = [], []
    while len(xs) < n:
        x = rng.normal(size=dim)
        m = x @ w
        if abs(m) > margin:
            xs.append(x)
            ys.append(np.sign(m))
    return np.asarray(xs, np.float32), np.asarray(ys, np.float32)


def test_dcd_solves_separable_problem():
    xs, ys = _separable()
    solver = DCDSolver(xs.shape[1], len(xs))
    idx = np.arange(len(xs))
    objs = []
    for _ in range(10):
        solver.solve_block(xs, ys, idx, sweeps=2)
        objs.append(solver.primal_objective(xs, ys))
    assert solver.accuracy(xs, ys) > 0.99
    # monotone-ish decreasing objective
    assert objs[-1] < objs[0]


def test_dcd_duals_stay_feasible():
    xs, ys = _separable(n=200, seed=3)
    solver = DCDSolver(xs.shape[1], len(xs))
    solver.solve_block(xs, ys, np.arange(len(xs)), sweeps=3)
    assert (solver.alpha >= 0).all()  # box constraint of the L2-loss dual
    # primal w must equal sum alpha_i y_i x_i (the maintained invariant)
    w_ref = (solver.alpha * ys) @ xs
    np.testing.assert_allclose(solver.w, w_ref, rtol=1e-6, atol=1e-8)


# ------------------------------------------------------- sparse CSR path
def _sparse_store(tmp_path, n=400, dim=128, nnz=(2, 12), seed=7):
    from repro.core.location import LocationGenerator
    from repro.data.synthetic import make_classification_dataset
    from repro.storage.record_store import RecordStore

    meta = make_classification_dataset(
        str(tmp_path / "svm.rrec"), n, dim, sparse=True,
        nnz_range=nnz, noise=0.02, seed=seed,
    )
    store = RecordStore(meta.path)
    LocationGenerator().generate(store)
    return store, meta


def test_pack_csr_batch_vectorized_matches_bytes_path(tmp_path):
    from repro.svm.sparse import csr_to_dense, pack_csr_batch

    store, meta = _sparse_store(tmp_path)
    idx = np.random.default_rng(0).integers(0, meta.num_records, size=150)
    fast = pack_csr_batch(store.read_batch_ragged(idx), meta.dim)
    ref = pack_csr_batch(store.read_batch(idx), meta.dim)
    for a, b in zip(fast, ref):
        np.testing.assert_array_equal(a, b)
    # and the densified batch matches the seed per-record decoder exactly
    from repro.data.synthetic import decode_sparse_batch

    xs_ref, ys_ref = decode_sparse_batch(store.read_batch(idx), meta.dim)
    xs, ys = csr_to_dense(fast, meta.dim)
    np.testing.assert_array_equal(xs, xs_ref)
    np.testing.assert_array_equal(ys, ys_ref)
    # decode_sparse_batch takes the arena fast path transparently
    xs2, ys2 = decode_sparse_batch(store.read_batch_ragged(idx), meta.dim)
    np.testing.assert_array_equal(xs2, xs_ref)
    store.close()


def test_pack_csr_batch_rejects_garbage(tmp_path):
    from repro.storage.record_store import RecordStore, RecordWriter
    from repro.core.location import LocationGenerator
    from repro.svm.sparse import pack_csr_batch

    path = str(tmp_path / "bad.rrec")
    with RecordWriter(path) as w:
        w.append(b"\x00" * 13)  # not 8 + 8*nnz
    store = RecordStore(path)
    LocationGenerator().generate(store)
    with pytest.raises(ValueError, match="not sparse SVM"):
        pack_csr_batch(store.read_batch_ragged([0]))
    store.close()


def test_duplicate_feature_ids_accumulate_everywhere(tmp_path):
    """One contract for duplicate ids in a row: coefficients accumulate
    (CSR semantics) — in the decoder, the densifier, the kernel, and the
    CSR solver, which must then match the dense solver on densified data."""
    import struct

    from repro.storage.record_store import RecordStore, RecordWriter
    from repro.core.location import LocationGenerator
    from repro.data.synthetic import decode_sparse_batch
    from repro.svm.sparse import csr_to_dense, pack_csr_batch

    dim = 8
    recs = [
        struct.pack("<fI", 1.0, 3)
        + np.array([2, 2, 5], np.uint32).tobytes()
        + np.array([1.0, 2.0, 3.0], np.float32).tobytes(),
        struct.pack("<fI", -1.0, 2)
        + np.array([0, 7], np.uint32).tobytes()
        + np.array([-1.0, 4.0], np.float32).tobytes(),
    ]
    path = str(tmp_path / "dup.rrec")
    with RecordWriter(path) as w:
        for r in recs:
            w.append(r)
    store = RecordStore(path)
    LocationGenerator().generate(store)
    rb = store.read_batch_ragged([0, 1])
    # decoder parity: bytes path and arena path agree (x[2] == 1+2)
    xs_b, ys_b = decode_sparse_batch(recs, dim)
    xs_r, ys_r = decode_sparse_batch(rb, dim)
    np.testing.assert_array_equal(xs_b, xs_r)
    assert xs_b[0, 2] == 3.0
    # CSR solver == dense solver on the densified data
    csr = pack_csr_batch(rb, dim)
    xs, ys = csr_to_dense(csr, dim)
    np.testing.assert_array_equal(xs, xs_b)
    dense = DCDSolver(dim, 2)
    sparse = DCDSolver(dim, 2)
    idx = np.array([0, 1])
    for _ in range(4):
        dense.solve_block(xs, ys, idx, sweeps=3)
        sparse.solve_block_csr(csr, idx, sweeps=3)
    np.testing.assert_allclose(sparse.w, dense.w, rtol=1e-12, atol=1e-15)
    np.testing.assert_allclose(sparse.alpha, dense.alpha, rtol=1e-12, atol=1e-15)
    store.close()


@pytest.mark.parametrize("bad_id", [2**31, 2**32 - 1])
def test_pack_csr_batch_rejects_wrapping_feature_ids(tmp_path, bad_id):
    """u32 ids >= 2^31 must raise, not wrap negative through the int32
    cast (2^32-1 would become -1 — a silently *valid* index into w)."""
    import struct

    from repro.storage.record_store import RecordStore, RecordWriter
    from repro.core.location import LocationGenerator
    from repro.svm.sparse import pack_csr_batch

    path = str(tmp_path / "wrap.rrec")
    rec = struct.pack("<fI", 1.0, 1) + struct.pack("<I", bad_id) + b"\x00" * 4
    with RecordWriter(path) as w:
        w.append(rec)
    store = RecordStore(path)
    LocationGenerator().generate(store)
    for batch in (store.read_batch_ragged([0]), store.read_batch([0])):
        with pytest.raises(ValueError, match="feature index"):
            pack_csr_batch(batch, dim=128)
        with pytest.raises(ValueError, match="feature index"):
            pack_csr_batch(batch)  # no dim: still must refuse the wrap
    store.close()


def test_dcd_csr_matches_dense_solver(tmp_path):
    """solve_block_csr must track solve_block on the same block sequence
    (same update rule, sparse arithmetic)."""
    from repro.svm.sparse import csr_to_dense, pack_csr_batch

    store, meta = _sparse_store(tmp_path)
    n, dim = meta.num_records, meta.dim
    all_csr = pack_csr_batch(store.read_batch_ragged(np.arange(n)), dim)
    xs, ys = csr_to_dense(all_csr, dim)
    dense = DCDSolver(dim, n)
    sparse = DCDSolver(dim, n)
    for e in range(3):
        order = np.random.default_rng(e).permutation(n)
        for blk in np.array_split(order, 6):
            dense.solve_block(xs, ys, blk, sweeps=2)
            sparse.solve_block_csr(
                pack_csr_batch(store.read_batch_ragged(blk), dim), blk,
                sweeps=2,
            )
    np.testing.assert_allclose(sparse.w, dense.w, rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(sparse.alpha, dense.alpha, rtol=1e-4, atol=1e-7)
    # kernel-backed objective agrees with the dense objective
    obj_csr = sparse.primal_objective_csr(all_csr)
    obj_dense = dense.primal_objective(xs, ys)
    assert abs(obj_csr - obj_dense) / obj_dense < 1e-4
    store.close()


def test_svm_end_to_end_through_ragged_pipeline(tmp_path):
    """The acceptance path: sparse store → LIRS shuffler → multi-producer
    ragged pipeline (ring-recycled arenas) → vectorized CSR packing → DCD,
    with the on-device csr_dot bit-exact against the jnp reference on
    the trained weights."""
    import jax.numpy as jnp

    from repro.core.pipeline import InputPipeline, store_fetch_fn
    from repro.core.shuffler import LIRSShuffler
    from repro.kernels import ops, ref
    from repro.storage.record_store import RaggedBufferRing
    from repro.svm.sparse import csr_to_dense, pack_csr_batch, pad_csr

    store, meta = _sparse_store(tmp_path, n=320, dim=64, nnz=(4, 16), seed=1)
    n, dim, batch = meta.num_records, meta.dim, 64
    solver = DCDSolver(dim, n)
    sh = LIRSShuffler(n, batch, seed=5)
    ring = RaggedBufferRing(batch * 200, batch, depth=6)
    consumed = [0]

    def run_epoch(e):
        # the shuffler's batches and the pipeline items arrive in the same
        # deterministic order, so row j of a batch owns dual idx[j]
        idx_iter = sh.epoch_batches(e)
        pipe = InputPipeline(
            sh.epoch_batches,
            store_fetch_fn(store, ring=ring, workers=2),
            prefetch=2,
            num_producers=3,
            recycle_fn=ring.recycle,
        )
        for item in pipe.epoch(e):
            idx = next(idx_iter)
            csr = pack_csr_batch(item, dim)
            solver.solve_block_csr(csr, idx, sweeps=3)
            consumed[0] += len(csr)

    for e in range(4):
        run_epoch(e)
    assert consumed[0] == 4 * (n // batch) * batch
    # converged well past chance on the full set
    full = pack_csr_batch(store.read_batch_ragged(np.arange(n)), dim)
    xs, ys = csr_to_dense(full, dim)
    assert solver.accuracy(xs, ys) > 0.9
    # csr_dot bit-exact vs the jnp reference on the trained weights
    idx2d, val2d = pad_csr(full)
    w32 = jnp.asarray(solver.w, jnp.float32)
    kernel = ops.csr_dot(jnp.asarray(idx2d), jnp.asarray(val2d), w32)
    oracle = ref.csr_dot_ref(jnp.asarray(idx2d), jnp.asarray(val2d), w32)
    np.testing.assert_array_equal(np.asarray(kernel), np.asarray(oracle))
    # and the kernel margins equal the dense matvec numerically
    np.testing.assert_allclose(
        np.asarray(kernel), xs @ np.asarray(w32), rtol=1e-4, atol=1e-5
    )
    store.close()


@pytest.mark.slow
def test_svm_ragged_pipeline_convergence_tier(tmp_path):
    """Convergence-tier (nightly) check: CSR training through the ragged
    pipeline reaches the same objective level as dense in-memory DCD on
    the same shuffled block sequence — the Table 3 setup, storage-backed."""
    from repro.core.shuffler import LIRSShuffler
    from repro.svm.sparse import csr_to_dense, pack_csr_batch

    store, meta = _sparse_store(
        tmp_path, n=2000, dim=512, nnz=(8, 48), seed=11
    )
    n, dim, blocks = meta.num_records, meta.dim, 10
    full = pack_csr_batch(store.read_batch_ragged(np.arange(n)), dim)
    xs, ys = csr_to_dense(full, dim)
    dense = DCDSolver(dim, n)
    ragged = DCDSolver(dim, n)
    sh = LIRSShuffler(n, n // blocks, seed=2)
    for e in range(8):
        for blk in sh.epoch_batches(e):
            dense.solve_block(xs, ys, blk, sweeps=4)
            ragged.solve_block_csr(
                pack_csr_batch(store.read_batch_ragged(blk), dim), blk,
                sweeps=4,
            )
    obj_dense = dense.primal_objective(xs, ys)
    obj_ragged = ragged.primal_objective_csr(full)
    assert abs(obj_ragged - obj_dense) / obj_dense < 1e-3
    assert ragged.accuracy(xs, ys) > 0.95
    store.close()


# ------------------------------------------- the device copy of w
def _csr_batch(seed=0, rows=6, dim=48, nnz=(1, 9)):
    from repro.svm.sparse import CSRBatch

    rng = np.random.default_rng(seed)
    lens = rng.integers(nnz[0], nnz[1], size=rows)
    row_ptr = np.concatenate(([0], np.cumsum(lens))).astype(np.int32)
    return CSRBatch(
        indices=rng.integers(0, dim, size=row_ptr[-1]).astype(np.int32),
        values=rng.normal(size=row_ptr[-1]).astype(np.float32),
        row_ptr=row_ptr,
        labels=np.where(rng.random(rows) < 0.5, -1.0, 1.0).astype(np.float32),
    )


def _fresh_margins(w, csr):
    """``ops.csr_dot`` over a fresh float32 upload of ``w``."""
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.svm.sparse import pad_csr

    idx2d, val2d = pad_csr(csr)
    return np.asarray(ops.csr_dot(jnp.asarray(idx2d), jnp.asarray(val2d),
                                  jnp.asarray(w, jnp.float32)))


@pytest.mark.parametrize("calls", [1, 2, 5])
def test_margins_csr_uploads_an_unchanged_w_once(calls):
    dim = 48
    solver = DCDSolver(dim, 6)
    solver.w = np.random.default_rng(1).normal(size=dim)
    for k in range(calls):
        csr = _csr_batch(seed=k, dim=dim)
        np.testing.assert_array_equal(solver.margins_csr(csr),
                                      _fresh_margins(solver.w, csr))
    assert solver.w_uploads == 1
    assert solver.margins_calls == calls


def _change_by_assignment(solver, csr, xs, ys):
    solver.w = solver.w + np.linspace(-1.0, 1.0, len(solver.w))


def _change_by_solve_block(solver, csr, xs, ys):
    solver.solve_block(xs, ys, np.arange(len(ys)), sweeps=2)


def _change_by_solve_block_csr(solver, csr, xs, ys):
    solver.solve_block_csr(csr, np.arange(len(ys)), sweeps=2)


@pytest.mark.parametrize("change", [_change_by_assignment,
                                    _change_by_solve_block,
                                    _change_by_solve_block_csr])
def test_margins_csr_uploads_again_after_w_changes(change):
    from repro.svm.sparse import csr_to_dense

    dim = 48
    csr = _csr_batch(seed=3, dim=dim)
    xs, ys = csr_to_dense(csr, dim)
    solver = DCDSolver(dim, len(ys))
    solver.w = np.random.default_rng(2).normal(size=dim) * 0.1
    before = solver.margins_csr(csr)
    w_before = solver.w.copy()
    change(solver, csr, xs, ys)
    assert not np.array_equal(solver.w, w_before)
    after = solver.margins_csr(csr)
    assert solver.w_uploads == 2 and solver.margins_calls == 2
    assert not np.array_equal(after, before)
    fresh = DCDSolver(dim, len(ys))
    fresh.w = solver.w
    np.testing.assert_array_equal(after, fresh.margins_csr(csr))
    np.testing.assert_array_equal(after, _fresh_margins(solver.w, csr))


def test_primal_objective_csr_reflects_a_solve():
    from repro.svm.sparse import csr_to_dense

    dim = 48
    csr = _csr_batch(seed=4, rows=12, dim=dim)
    xs, ys = csr_to_dense(csr, dim)
    solver = DCDSolver(dim, len(ys))
    first = solver.primal_objective_csr(csr)  # w = 0: uploads the zeros
    assert first == pytest.approx(solver.primal_objective(xs, ys))
    solver.solve_block_csr(csr, np.arange(len(ys)), sweeps=3)
    second = solver.primal_objective_csr(csr)
    assert second < first
    assert second == pytest.approx(solver.primal_objective(xs, ys), rel=1e-5)
    assert solver.w_uploads == 2


def test_w_refuses_in_place_writes():
    solver = DCDSolver(8, 2)
    with pytest.raises(ValueError):
        solver.w[3] = 1.0
    with pytest.raises(ValueError):
        solver.w += 1.0
    v = np.ones(8)
    solver.w = v
    v[0] = 5.0  # the setter copied: the solver does not see this write
    assert solver.w[0] == 1.0
    np.testing.assert_array_equal(solver.w, np.ones(8))
