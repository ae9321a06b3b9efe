"""Operations and bytes a dropless expert layer needs, from shapes and
from what was really routed. The benchmark's own, like ``flops.py``: a PR
that changes the expert kernels cannot change what they are measured
against. One multiply-add = 2 operations."""

from __future__ import annotations


def expert_layer_cost(pairs: float, experts_read: float, hidden: int,
                      width: int, itemsize: int = 2) -> tuple[float, float]:
    """``(operations, bytes)`` of ONE expert layer's three grouped matmuls
    (gate and up ``hidden -> width``, down ``width -> hidden``) in one
    program that routes ``pairs`` token-expert pairs to ``experts_read``
    distinct experts.

    Bytes: each expert that has a row is read once, whole (three
    ``hidden x width`` matrices), and an expert without rows is not read;
    every pair's row is read (``hidden``) and its activation written
    (``width``) by the gate-up product, then read (``width``) and the
    result written (``hidden``) by the down product. Operations: three
    products of ``hidden x width`` a pair. The router, the gathers and the
    weighted sum are not part of it (they are ``moe_route``)."""
    ops = pairs * 3 * 2.0 * hidden * width
    bytes_ = (experts_read * 3.0 * hidden * width
              + pairs * 2.0 * (hidden + width)) * itemsize
    return ops, bytes_
