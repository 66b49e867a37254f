"""Block-size choice that Mosaic accepts.

A TPU block's last two dimensions must each be a multiple of the (8, 128)
tile, or equal to the whole array dimension.
"""
from __future__ import annotations


def fit_block(dim: int, block: int, align: int) -> int:
    """Largest multiple of ``align`` that is <= ``block`` and divides ``dim``;
    ``dim`` itself when it fits in one block or no such multiple exists."""
    if dim <= block:
        return dim
    for b in range(block - block % align, 0, -align):
        if dim % b == 0:
            return b
    return dim
