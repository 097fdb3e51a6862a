"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(interpret mode: the Pallas kernel bodies execute on CPU; TPU is the
target, and tests/test_chip_compile.py compiles them for it)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _rand(shape, dtype):
    x = RNG.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype=dtype)


# ------------------------------------------------------------ batch_gather


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize("n,d,b,block_d", [(64, 256, 16, 128), (128, 512, 5, 512), (32, 128, 32, 128)])
def test_batch_gather_sweep(n, d, b, block_d, dtype):
    table = _rand((n, d), dtype) if dtype != jnp.int32 else jnp.asarray(
        RNG.integers(0, 100, size=(n, d)), jnp.int32
    )
    idx = jnp.asarray(RNG.integers(0, n, size=b), jnp.int32)
    out = ops.batch_gather(table, idx, block_d=block_d)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.batch_gather_ref(table, idx))
    )


@pytest.mark.parametrize("rows", [2, 4, 8])
def test_batch_gather_page_blocks(rows):
    """rows_per_block is the device-side page-aware knob."""
    table = _rand((128, 256), jnp.float32)
    idx = jnp.asarray(RNG.integers(0, 128 // rows, size=8), jnp.int32)
    out = ops.batch_gather(table, idx, rows_per_block=rows)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.batch_gather_ref(table, idx, rows))
    )


def test_batch_gather_duplicate_indices():
    table = _rand((32, 128), jnp.float32)
    idx = jnp.asarray([3, 3, 3, 0], jnp.int32)
    out = ops.batch_gather(table, idx)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out[1]))


# ----------------------------------------------------------------- csr_dot


@pytest.mark.parametrize(
    "b,k,d,block_b",
    [(16, 8, 128, 8), (5, 24, 64, 8), (32, 16, 256, 4), (1, 8, 32, 8),
     (33, 40, 512, 16)],
)
def test_csr_dot_bit_exact(b, k, d, block_b):
    """Padded-CSR inner products must match the jnp reference bit-exactly
    (same gather values, same reduction order) at any batch extent.
    ``block_b`` is the row tile the earlier Pallas form used; it only
    shapes the sweep now."""
    idx = jnp.asarray(RNG.integers(0, d, size=(b, k)), jnp.int32)
    val = _rand((b, k), jnp.float32)
    # zero-pad a random suffix of each row (the pad_csr contract)
    keep = RNG.integers(1, k + 1, size=b)
    mask = np.arange(k)[None, :] < keep[:, None]
    idx = jnp.where(mask, idx, 0)
    val = jnp.where(mask, val, 0.0)
    w = _rand((d,), jnp.float32)
    out = ops.csr_dot(idx, val, w)
    want = ref.csr_dot_ref(idx, val, w)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_csr_dot_duplicate_features_accumulate():
    """A row listing the same feature twice contributes twice (CSR sum)."""
    idx = jnp.asarray([[3, 3, 0, 0]], jnp.int32)
    val = jnp.asarray([[1.5, 2.5, 0.0, 0.0]], jnp.float32)
    w = jnp.arange(8, dtype=jnp.float32)
    out = ops.csr_dot(idx, val, w)
    np.testing.assert_allclose(np.asarray(out), [4.0 * 3.0])


def test_csr_dot_empty_batch():
    out = ops.csr_dot(
        jnp.zeros((0, 8), jnp.int32), jnp.zeros((0, 8), jnp.float32),
        jnp.ones(16, jnp.float32),
    )
    assert out.shape == (0,)


def test_csr_dot_matches_dense_matvec():
    """Against a dense densification oracle (not just the gather ref)."""
    b, k, d = 12, 10, 96
    idx_np = np.stack([
        RNG.choice(d, size=k, replace=False) for _ in range(b)
    ]).astype(np.int32)
    val_np = RNG.normal(size=(b, k)).astype(np.float32)
    dense = np.zeros((b, d), np.float32)
    np.put_along_axis(dense, idx_np, val_np, axis=1)
    w = RNG.normal(size=d).astype(np.float32)
    out = ops.csr_dot(jnp.asarray(idx_np), jnp.asarray(val_np), jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(out), dense @ w, rtol=2e-5, atol=2e-5)


# --------------------------------------------------------- flash_attention


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize(
    "b,s,h,kh,d,bq,bk",
    [
        (1, 128, 2, 2, 64, 64, 64),
        (2, 256, 4, 2, 64, 128, 64),   # GQA
        (1, 256, 8, 1, 128, 64, 128),  # MQA
    ],
)
def test_flash_attention_sweep(b, s, h, kh, d, bq, bk, dtype, tol):
    q = _rand((b, s, h, d), dtype)
    k = _rand((b, s, kh, d), dtype)
    v = _rand((b, s, kh, d), dtype)
    out = ops.flash_attention(q, k, v, block_q=bq, block_k=bk)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


def test_flash_attention_non_causal():
    q = _rand((1, 128, 2, 64), jnp.float32)
    k = _rand((1, 128, 2, 64), jnp.float32)
    v = _rand((1, 128, 2, 64), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_flash_attention_matches_model_layer():
    """The Pallas kernel and the model's XLA path agree."""
    from repro.layers.attention import full_attention

    q = _rand((2, 128, 4, 64), jnp.float32)
    k = _rand((2, 128, 2, 64), jnp.float32)
    v = _rand((2, 128, 2, 64), jnp.float32)
    a = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    b = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-5, atol=3e-5)


# -------------------------------------------------------------- rglru_scan


@pytest.mark.parametrize(
    "b,t,w,bb,bt,bw",
    [(2, 128, 128, 2, 64, 128), (4, 256, 256, 2, 128, 128), (1, 64, 512, 1, 64, 256)],
)
def test_rglru_scan_sweep(b, t, w, bb, bt, bw):
    a = jnp.asarray(RNG.uniform(0.6, 0.999, size=(b, t, w)), jnp.float32)
    x = _rand((b, t, w), jnp.float32)
    h = ops.rglru_scan(a, x, block_b=bb, block_t=bt, block_w=bw)
    want = ref.rglru_scan_ref(a, x)
    np.testing.assert_allclose(np.asarray(h), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_rglru_scan_carry_across_blocks():
    """State must flow across time blocks: compare 1-block vs 4-block runs."""
    a = jnp.asarray(RNG.uniform(0.9, 0.999, size=(1, 256, 128)), jnp.float32)
    x = _rand((1, 256, 128), jnp.float32)
    h1 = ops.rglru_scan(a, x, block_t=256)
    h4 = ops.rglru_scan(a, x, block_t=64)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h4), rtol=1e-6, atol=1e-6)


def test_rglru_matches_layer_semantics():
    """Kernel recurrence == the associative_scan inside the RG-LRU layer."""
    import jax

    a = jnp.asarray(RNG.uniform(0.8, 0.99, size=(2, 64, 64)), jnp.float32)
    x = _rand((2, 64, 64), jnp.float32)

    def combine(l, r):
        a1, b1 = l
        a2, b2 = r
        return a1 * a2, a2 * b1 + b2

    _, h_assoc = jax.lax.associative_scan(combine, (a, x), axis=1)
    h_kernel = ops.rglru_scan(a, x, block_t=32)
    np.testing.assert_allclose(np.asarray(h_kernel), np.asarray(h_assoc), rtol=1e-5, atol=1e-5)


# -------------------------------------------------------------- flash_decode


@pytest.mark.parametrize(
    "b,t,h,kh,d,bk",
    [(2, 512, 4, 2, 64, 128), (1, 256, 8, 1, 128, 64), (2, 256, 4, 4, 64, 256)],
)
def test_flash_decode_sweep(b, t, h, kh, d, bk):
    q = _rand((b, h, d), jnp.float32)
    k = _rand((b, t, kh, d), jnp.float32)
    v = _rand((b, t, kh, d), jnp.float32)
    cur = jnp.asarray(RNG.integers(0, t, size=b), jnp.int32)
    out = ops.flash_decode(q, k, v, cur, block_k=bk)
    want = ref.flash_decode_ref(q, k, v, cur)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_flash_decode_respects_cache_length():
    """Entries beyond cur_index must not influence the output."""
    b, t, h, d = 1, 256, 2, 64
    q = _rand((b, h, d), jnp.float32)
    k = _rand((b, t, h, d), jnp.float32)
    v = _rand((b, t, h, d), jnp.float32)
    cur = jnp.asarray([100], jnp.int32)
    out1 = ops.flash_decode(q, k, v, cur, block_k=64)
    k2 = k.at[:, 101:].set(999.0)
    v2 = v.at[:, 101:].set(-999.0)
    out2 = ops.flash_decode(q, k2, v2, cur, block_k=64)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)


# ----------------------------------------------------- batch_gather_dma


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
@pytest.mark.parametrize(
    "n,d,b,block_d,rows_per_step",
    [(64, 256, 16, 128, 8), (128, 512, 5, 512, 8), (32, 128, 32, 128, 1),
     (64, 128, 7, 128, 16)],
)
def test_batch_gather_dma_bit_exact(n, d, b, block_d, rows_per_step, dtype):
    """The multi-row double-buffered DMA variant must match the reference
    gather bit-exactly (including ragged batch → padded grid)."""
    table = _rand((n, d), dtype) if dtype != jnp.int32 else jnp.asarray(
        RNG.integers(0, 100, size=(n, d)), jnp.int32
    )
    idx = jnp.asarray(RNG.integers(0, n, size=b), jnp.int32)
    out = ops.batch_gather_dma(
        table, idx, block_d=block_d, rows_per_step=rows_per_step
    )
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.batch_gather_ref(table, idx))
    )


@pytest.mark.parametrize("rows", [2, 4])
def test_batch_gather_dma_page_blocks(rows):
    table = _rand((128, 256), jnp.float32)
    idx = jnp.asarray(RNG.integers(0, 128 // rows, size=8), jnp.int32)
    out = ops.batch_gather_dma(table, idx, rows_per_block=rows, rows_per_step=4)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.batch_gather_ref(table, idx, rows))
    )


def test_batch_gather_dma_matches_single_row_variant():
    table = _rand((256, 512), jnp.bfloat16)
    idx = jnp.asarray(RNG.integers(0, 256, size=64), jnp.int32)
    a = ops.batch_gather(table, idx)
    b = ops.batch_gather_dma(table, idx, rows_per_step=8)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
