"""The benchmark's own GEAR table and CDC parameter set (copied from
``backuwup_tpu/ops/gear.py`` in PR 24; imports nothing of the program).

``GEAR[b] = fmix32(GEAR_SEED32 + b)`` with the murmur3 32-bit finalizer.
A configuration's file states every number of its ``CDCParams``; nothing
here has a default, so a change to the program's defaults cannot move the
reference with it.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

_M32 = 0xFFFFFFFF
GEAR_SEED32 = 0x6261636B  # "back"
GEAR_WINDOW = 32  # bytes of influence of the 32-bit rolling hash


def fmix32(h: int) -> int:
    """murmur3 finalizer: full-avalanche bijection on u32."""
    h &= _M32
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & _M32
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & _M32
    h ^= h >> 16
    return h


GEAR = np.array([fmix32(GEAR_SEED32 + b) for b in range(256)],
                dtype=np.uint32)


def _top_bits_mask(bits: int) -> int:
    if not 0 < bits < 32:
        raise ValueError("mask bits must be in (0, 32)")
    return (0xFFFFFFFF << (32 - bits)) & 0xFFFFFFFF


@dataclass(frozen=True)
class CDCParams:
    """Chunking parameters, as a configuration file states them."""

    min_size: int
    desired_size: int
    max_size: int
    mask_s_bits: int
    mask_l_bits: int

    def __post_init__(self) -> None:
        if not (0 < self.min_size <= self.desired_size <= self.max_size):
            raise ValueError("require 0 < min <= desired <= max")
        if self.mask_l_bits >= self.mask_s_bits:
            raise ValueError("mask_l must be looser (fewer bits) than mask_s")

    @property
    def mask_s(self) -> int:
        return _top_bits_mask(self.mask_s_bits)

    @property
    def mask_l(self) -> int:
        return _top_bits_mask(self.mask_l_bits)
