"""jit'd public wrappers around the Pallas kernels.

Backend dispatch lives in ``kernels.dispatch``: compiled Mosaic on TPU,
tiled XLA twins on CPU, interpret mode only on explicit request (parity
tests, ``REPRO_KERNEL_IMPL=pallas_interpret``).
"""
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd
from repro.kernels.pairdist import (neighbor_adjacency, neighbor_count,
                                    pairdist)

__all__ = ["flash_attention", "ssd", "pairdist", "neighbor_count",
           "neighbor_adjacency"]
