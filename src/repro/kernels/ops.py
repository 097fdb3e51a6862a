"""Public entry points for the kernels.

The Pallas kernels compile to Mosaic on a TPU backend.  On any other
backend they run their bodies in Pallas' interpreter (``interpret=True``),
which is how the tests check them against ``ref`` on a CPU.  The choice
is made at each call from ``jax.default_backend()``, never at import: on
a TPU a kernel never runs in the interpreter, whatever ``interpret`` says.

``csr_dot`` is not a Pallas kernel: its weight vector has one entry per
feature (29.9M for the kdd set), far beyond VMEM, so it is XLA's gather
and reduce.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.batch_gather import batch_gather as _batch_gather
from repro.kernels.batch_gather import batch_gather_dma as _batch_gather_dma
from repro.kernels.flash_attention import flash_attention as _flash_attention
from repro.kernels.rglru_scan import rglru_scan as _rglru_scan


def _interpret(interpret: bool | None) -> bool:
    """Interpret mode for this call: off on a TPU, else ``interpret``
    (default on, the only way a Mosaic kernel runs elsewhere)."""
    if jax.default_backend() == "tpu":
        return False
    return True if interpret is None else interpret


def batch_gather(table, indices, *, block_d: int = 512, rows_per_block: int = 1,
                 interpret: bool | None = None):
    return _batch_gather(
        table, indices, block_d=block_d, rows_per_block=rows_per_block,
        interpret=_interpret(interpret),
    )


def batch_gather_dma(table, indices, *, block_d: int = 512,
                     rows_per_block: int = 1, rows_per_step: int = 8,
                     interpret: bool | None = None):
    """Multi-row double-buffered gather (same semantics as batch_gather)."""
    return _batch_gather_dma(
        table, indices, block_d=block_d, rows_per_block=rows_per_block,
        rows_per_step=rows_per_step, interpret=_interpret(interpret),
    )


@jax.jit
def csr_dot(indices, values, w):
    """Padded-CSR inner products (sparse SVM hot path).

    indices: (B, K) int32 — feature ids, 0-padded
    values:  (B, K) f32   — nonzero values, 0.0-padded
    w:       (D,)   f32   — dense weight vector
    returns: (B,)   f32   — ``out[b] = Σ_k values[b,k]·w[indices[b,k]]``
    """
    gathered = w.astype(jnp.float32)[indices]
    return jnp.sum(values.astype(jnp.float32) * gathered, axis=-1)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None):
    return _flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=_interpret(interpret),
    )


def rglru_scan(a, x, *, block_b: int = 8, block_t: int = 128, block_w: int = 512,
               interpret: bool | None = None):
    return _rglru_scan(
        a, x, block_b=block_b, block_t=block_t, block_w=block_w,
        interpret=_interpret(interpret),
    )


def flash_decode(q, k_cache, v_cache, cur_index, *, block_k: int = 256,
                 interpret: bool | None = None):
    from repro.kernels.flash_decode import flash_decode as _fd

    return _fd(q, k_cache, v_cache, cur_index, block_k=block_k,
               interpret=_interpret(interpret))
