"""Compile-only checks of every Pallas kernel, and of the sampler's image
views, against the TPU compiler.

Each test lowers and compiles a kernel at a real width for one chip of a
described v5e (``v5e:2x2``) — no chip is attached, nothing runs — and
asserts that the compiled program holds the Mosaic kernel
(``tpu_custom_call``). Interpret-mode tests cannot see what these catch:
Mosaic refusing a block layout, or the TPU compiler failing on a kernel
body (both happened to ``cco_stats`` at D=1024).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.data import augment
from repro.kernels.cco_stats import cco_stats_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.mips_topk import mips_topk_pallas
from repro.kernels.quantize import quant_dequant_pallas
from repro.kernels.segment_sum import segment_sum_pallas

F32 = jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with JAX's persistent compile
    cache off: an XLA:TPU program written to it here could not be read
    back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for ShapeDtypeStructs ``shapes`` ((shape, dtype)) on
    the described chip; return the compiled program's text."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


KERNELS = {
    # phase-1 statistics at the paper's head width, one round's 512 samples
    "cco_stats_cross_512x1024": (
        functools.partial(cco_stats_pallas, moments="cross"),
        [((512, 1024), F32), ((512, 1024), F32)]),
    "cco_stats_full_512x1024": (
        functools.partial(cco_stats_pallas, moments="full"),
        [((512, 1024), F32), ((512, 1024), F32)]),
    # the hierarchy fold at the benchmark's row width (d=64 stats rows)
    "segment_sum_4096x4352_e64": (
        lambda rows, ids, w: segment_sum_pallas(rows, ids, 64, w),
        [((4096, 4352), F32), ((4096,), jnp.int32), ((4096,), F32)]),
    # the quantized wire: per-client (1-D) and column-mapped (2-D) scales
    "quant_dequant_1d_scales": (
        lambda x, u, s: quant_dequant_pallas(x, u, s, 127.0),
        [((64, 65536), F32), ((64, 65536), F32), ((64,), F32)]),
    "quant_dequant_2d_scales": (
        lambda x, u, s: quant_dequant_pallas(x, u, s, 127.0),
        [((64, 65536), F32), ((64, 65536), F32), ((64, 65536), F32)]),
    # retrieval: one index and one shard of a 4-way sharded index
    "mips_topk_q256_n65536_d1024_k10": (
        functools.partial(mips_topk_pallas, k=10),
        [((256, 1024), F32), ((65536, 1024), F32)]),
    "mips_topk_shard_local": (
        lambda q, c, off: mips_topk_pallas(q, c, k=10, index_offset=off,
                                           n_total=65536),
        [((256, 1024), F32), ((16384, 1024), F32), ((), jnp.int32)]),
    "flash_attention_bf16_1x16x2048x128": (
        flash_attention_pallas,
        [((1, 16, 2048, 128), jnp.bfloat16)] * 3),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    text = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in text, f"{name}: no Mosaic kernel compiled"


def test_image_views_compile_without_loop_for_v5e(one_chip):
    """One round's 512 views (r14-k256-n2) compile for the TPU with no
    gather and no loop (a vmapped ``dynamic_slice`` crop compiles to a
    ``while`` of one ``dynamic-update-slice`` a sample there), and every
    selection product at HIGHEST, where a one-hot product is exact."""
    text = _compile(jax.vmap(augment.two_views_image), one_chip,
                    ((512, 2), jnp.uint32), ((512, 32, 32, 3), F32))
    for op in ("gather", "while", "dynamic-update-slice"):
        assert not re.search(rf"\s{op}\(", text), op
    products = re.findall(r"\s(?:convolution|dot)\(.*", text)
    assert products
    assert all("operand_precision={highest,highest}" in p for p in products)
