"""Work of a transform, counted from its shape and never from the HLO,
so that every implementation of the same transform is held to the same
bytes and operations.

A d-dimensional c2c transform split over P chips (slab or pencil) makes
d one-dimensional passes; each pass reads and writes the chip's local
array once, so each chip moves ``d * 2 * (N_total / P) * itemsize``
bytes of HBM traffic at the least. The operation count is the HPC
Challenge convention, ``5 N log2 N`` real operations for a complex
transform of N points, divided evenly over the chips.
"""

from __future__ import annotations

import math

ITEMSIZE = {"complex64": 8, "complex128": 16}


def hbm_bytes_per_chip(shape, dtype: str, chips: int) -> float:
    points = math.prod(shape)
    return len(shape) * 2.0 * points / chips * ITEMSIZE[dtype]


def flops_per_chip(shape, chips: int) -> float:
    points = math.prod(shape)
    return 5.0 * points * math.log2(points) / chips


def hbm_roofline_s(shape, dtype: str, chips: int, hbm_bytes_per_s: float) -> float:
    """The least time one chip's HBM allows for the transform's passes."""
    return hbm_bytes_per_chip(shape, dtype, chips) / hbm_bytes_per_s
