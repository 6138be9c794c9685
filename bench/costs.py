"""Operations and HBM bytes of the program's Pallas kernels, from the
shapes of one call: the numerators of their roofline shares.

Copied from ``benchmarks/costmodel.py`` (``cco_stats_cost``), returning
(flops, hbm_bytes).
"""
from __future__ import annotations

F32 = 4


def cco_stats_cost(n: int, d: int, *, second_moments: bool = False,
                   in_bytes: int = F32):
    """The one-pass statistics kernel (``kernels/cco_stats.py``) on
    zf, zg: (N, d). FLOPs: the (d, d) cross moment is a 2*N*d*d product
    (x3 with the within-view moments) plus ~6*N*d elementwise work.
    Bytes: both inputs read once, the statistics written once."""
    n_mats = 3 if second_moments else 1
    flops = n_mats * 2.0 * n * d * d + 6.0 * n * d
    hbm = 2.0 * n * d * in_bytes + (4 * d + n_mats * d * d) * F32
    return flops, hbm

