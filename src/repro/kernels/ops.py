"""jit'd public wrappers for the Pallas kernels.

On TPU the Mosaic kernels run natively; on any other backend they run in
interpret mode so the whole stack stays executable. ``use_pallas=False``
falls back to the pure-jnp reference (the path the XLA dry-run lowers).
"""
from __future__ import annotations


import jax

from repro.kernels import ref
from repro.kernels.cco_stats import cco_stats_pallas
from repro.kernels.flash_attention import flash_attention_pallas


def on_tpu() -> bool:
    """The one platform test behind every ``"pallas"`` kernel flag: the
    kernels are written for the TPU's Mosaic compiler, so ``"pallas"``
    compiles them on a TPU and runs them under the interpreter elsewhere."""
    return jax.default_backend() == "tpu"


def cco_stats(zf, zg, *, use_pallas: bool = True, block_n: int = 512,
              block_d: int = 256):
    """Fused five-statistics op (see kernels/cco_stats.py)."""
    if not use_pallas:
        return ref.cco_stats_ref(zf, zg)
    return cco_stats_pallas(zf, zg, block_n=block_n, block_d=block_d,
                            interpret=not on_tpu())


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    use_pallas: bool = True, block_q: int = 256,
                    block_kv: int = 512):
    """Blockwise GQA attention op (see kernels/flash_attention.py)."""
    if not use_pallas:
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  block_q=block_q, block_kv=block_kv,
                                  interpret=not on_tpu())
