"""The benchmark's own copy of the numpy CDC oracle (copied from
``backuwup_tpu/ops/cdc_cpu.py`` in PR 24; imports nothing of the program).

CPU oracle for windowed Gear CDC (normative semantics in CDC_SPEC.md).

Replaces the reference's FastCDC hot loop (``dir_packer.rs:246-266``) with the
two-stage decomposition: per-position candidate discovery (vectorizable, the
TPU target) + sparse sequential cut selection (host).  The scalar
:func:`gear_hashes_scalar` path is the readability oracle; the numpy path is
bit-identical and fast enough for tests and mid-size corpora.
"""

from __future__ import annotations

import numpy as np

from .gear import GEAR, GEAR_WINDOW, CDCParams


def gear_hashes_scalar(data: bytes) -> np.ndarray:
    """h[i] = (h[i-1] << 1) + GEAR[b[i]] mod 2^32 — definitional loop."""
    out = np.empty(len(data), dtype=np.uint32)
    h = 0
    for i, b in enumerate(data):
        h = ((h << 1) + int(GEAR[b])) & 0xFFFFFFFF
        out[i] = h
    return out


def gear_hashes(data, prev_tail: bytes = b"") -> np.ndarray:
    """Vectorized per-position hashes.

    ``prev_tail`` supplies up to GEAR_WINDOW-1 bytes of left context (the halo
    when a long stream is processed block-wise); hashes are returned only for
    ``data`` positions, identical to hashing the concatenation.
    """
    tail = bytes(prev_tail)[-(GEAR_WINDOW - 1):] if prev_tail else b""
    buf = np.frombuffer(tail + bytes(data), dtype=np.uint8)
    g = GEAR[buf]
    n = len(buf)
    h = np.zeros(n, dtype=np.uint32)
    for k in range(GEAR_WINDOW):
        if k >= n:
            break
        # h[i] += GEAR[b[i-k]] << k
        h[k:] += g[:n - k] << np.uint32(k)
    return h[len(tail):]


def candidate_positions(data, params: CDCParams, prev_tail: bytes = b""):
    """Sorted positions where cand_s / cand_l hold (cand_s ⊆ cand_l)."""
    h = gear_hashes(data, prev_tail)
    cand_l = (h & np.uint32(params.mask_l)) == 0
    pos_l = np.nonzero(cand_l)[0]
    cand_s = (h[pos_l] & np.uint32(params.mask_s)) == 0
    pos_s = pos_l[cand_s]
    return pos_s, pos_l


def select_cuts(pos_s: np.ndarray, pos_l: np.ndarray, n: int,
                params: CDCParams) -> np.ndarray:
    """Resolve chunk end positions from candidate sets (CDC_SPEC.md rules).

    Returns the array of inclusive end positions; chunks are
    ``[0..e0], [e0+1..e1], ...`` and always end with ``n-1`` for n > 0.
    """
    if n == 0:
        return np.empty(0, dtype=np.int64)
    pos_s = np.asarray(pos_s, dtype=np.int64)
    pos_l = np.asarray(pos_l, dtype=np.int64)
    cuts = []
    s = 0
    while True:
        if n - s <= params.min_size:
            cuts.append(n - 1)
            break
        e = None
        # window 1: length in [min, desired) with the strict mask
        lo = s + params.min_size - 1
        hi = min(s + params.desired_size - 2, n - 2)  # e == n-1 is EOF anyway
        i = np.searchsorted(pos_s, lo, side="left")
        if i < len(pos_s) and pos_s[i] <= hi:
            e = int(pos_s[i])
        if e is None:
            # window 2: length in [desired, max) with the loose mask
            lo2 = s + params.desired_size - 1
            hi2 = min(s + params.max_size - 2, n - 2)
            j = np.searchsorted(pos_l, lo2, side="left")
            if j < len(pos_l) and pos_l[j] <= hi2:
                e = int(pos_l[j])
        if e is None:
            # forced cut at max, or EOF
            e = min(s + params.max_size - 1, n - 1)
        cuts.append(e)
        if e == n - 1:
            break
        s = e + 1
    return np.array(cuts, dtype=np.int64)


def cuts_to_chunks(ends) -> list:
    """Inclusive end positions -> [(offset, length), ...]."""
    out, s = [], 0
    for e in ends:
        out.append((s, int(e) - s + 1))
        s = int(e) + 1
    return out


def chunk_stream(data, params: CDCParams):
    """Chunk one stream; returns list of (offset, length)."""
    n = len(data)
    pos_s, pos_l = candidate_positions(data, params)
    return cuts_to_chunks(select_cuts(pos_s, pos_l, n, params))


def chunk_stream_scalar(data, params: CDCParams):
    """Definitional single loop over bytes — the ultimate oracle.

    O(n) python; use only on small inputs in tests.
    """
    n = len(data)
    out = []
    s = 0
    h = 0
    for i in range(n):
        h = ((h << 1) + int(GEAR[data[i]])) & 0xFFFFFFFF
        length = i - s + 1
        cut = False
        if i == n - 1:
            cut = True
        elif length >= params.min_size:
            if length < params.desired_size:
                cut = (h & params.mask_s) == 0
            elif length < params.max_size:
                cut = (h & params.mask_l) == 0
            else:
                cut = True
        if cut:
            out.append((s, length))
            s = i + 1
    return out
