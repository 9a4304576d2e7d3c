"""TPU execution backend for the windowed Gear CDC scan.

Replaces the reference's sequential FastCDC hot loop
(``client/src/backup/filesystem/dir_packer.rs:246-266``) with a data-parallel
decomposition designed for XLA/TPU:

* The per-position rolling hash ``h[i] = ((h[i-1] << 1) + GEAR[b[i]]) mod 2^32``
  is *exactly* equal to the 32-tap windowed sum
  ``h[i] = sum_{k=0}^{31} GEAR[b[i-k]] << k`` because shifts >= 32 vanish
  mod 2^32.  The window form has no sequential dependence, so the whole
  stream is hashed with 32 shifted vector adds — VPU work XLA fuses into a
  single pass over the bytes.
* The 256-entry gear-table lookup is executed on the **MXU**, not as a
  gather (TPU gathers serialize): bytes become a one-hot bf16 matrix that is
  multiplied against the table split into four 8-bit limbs.  0/1 and 0..255
  are exact in bf16 and the MXU accumulates in f32, so the product is the
  exact integer table value.
* Candidate cut-points (``h & mask == 0``) leave the device as a two-level
  sparse structure: bits are packed 32:1 into u32 words on the VPU, then
  :func:`_compact_words` hands over the (overwhelmingly zero) words that are
  not zero at a fixed capacity, so only a few KiB cross host<->HBM per
  segment.  It is not ``jnp.nonzero(size=...)``: that is a scatter of one
  update a word, which the TPU applies one after the other; the compaction
  scatters one update a 128-word block and gathers whole rows.
* Final cut selection (min/desired/max + two-mask normalization) runs on the
  host over the sparse candidates — the same code path as the CPU oracle
  (:func:`backuwup_tpu.ops.cdc_cpu.select_cuts`), so TPU and CPU chunking
  are bit-identical by construction.
* Long streams are processed in bounded segments with a 31-byte carried halo
  (sequence-parallel blockwise decomposition).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import defaults
from .cdc_cpu import cuts_to_chunks, select_cuts
from .cdc_cpu import gear_hashes as gear_hashes_np
from .gear import GEAR_WINDOW, CDCParams

_HALO = GEAR_WINDOW - 1  # 31 bytes of left context carry the full hash state


def _gear_values(b: jnp.ndarray) -> jnp.ndarray:
    """GEAR[b] computed per position: ``fmix32(GEAR_SEED32 + b)``.

    Seven fused elementwise u32 VPU ops — no gather (serializes on TPU)
    and no one-hot matmul (round 3's nibble-bilinear MXU form paid
    ~16 bytes of one-hot HBM traffic per stream byte and was the
    measured scan floor at ~215 ms/256 MiB; this form is pure
    fuseable arithmetic).  Bit-identical to ``GEAR[b]`` by
    construction (gear.make_gear_table evaluates the same formula).
    """
    from .gear import GEAR_SEED32
    h = b.astype(jnp.uint32) + jnp.uint32(GEAR_SEED32)
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> jnp.uint32(16))
    return h


def _hash_ext(ext: jnp.ndarray, halo_len: jnp.ndarray) -> jnp.ndarray:
    """Per-position hashes for ``ext[_HALO:]``, warmup-exact.

    ``ext`` is ``(_HALO + L,)`` uint8 — 31 bytes of left context followed by
    the segment.  ``halo_len`` (traced scalar, 0.._HALO) says how many of the
    context bytes really precede the stream position; taps reaching before
    the stream start are masked out, reproducing the oracle's short-window
    warmup at positions < 31.  Unrolled — use only on small/debug inputs
    (XLA materializes the 32 slice temporaries).
    """
    g = _gear_values(ext)
    L = ext.shape[0] - _HALO
    j = jnp.arange(L, dtype=jnp.int32)
    h = jnp.zeros(L, dtype=jnp.uint32)
    for k in range(GEAR_WINDOW):
        seg = g[_HALO - k:_HALO - k + L]
        if k > 0:
            seg = jnp.where(j >= jnp.int32(k) - halo_len.astype(jnp.int32),
                            seg, jnp.uint32(0))
        h = h + (seg << jnp.uint32(k))
    return h


def _hash_ext_fast(ext: jnp.ndarray) -> jnp.ndarray:
    """Per-position hashes for ``ext[_HALO:]``, production path.

    The 32-tap windowed sum is evaluated by **log-doubling** the linear
    recurrence: after pass ``t`` the running array holds
    ``a_t[i] = sum_{k < 2^t} GEAR[b[i-k]] << k``, so five shift-adds
    (``a <- a + (a >> shift 2^t positions) << 2^t``) replace 32 taps —
    ~8x less HBM traffic than a 32-iteration fori_loop.  Positions shifted
    in from beyond the left edge of ``ext`` read zero, which matches the
    zero-filled-halo warmup contract: at a stream start only h[0..30] are
    perturbed, positions that can never be selected as cuts because every
    cut-selection window starts at >= min_size - 1 > 31 (CDC_SPEC.md;
    min_size >= 64).  Candidate *sets* may therefore contain sub-min
    positions the CPU oracle lacks, but selected cuts are bit-identical.
    """
    assert GEAR_WINDOW == 32, "doubling ladder assumes a 32-byte window"
    a = _gear_values(ext)
    for t in range(5):
        s = 1 << t
        shifted = jnp.concatenate([jnp.zeros(s, dtype=a.dtype), a[:-s]])
        a = a + (shifted << jnp.uint32(s))
    return a[_HALO:]


def _pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """(L,) bool -> (L/32,) u32, little-endian bit order within each word."""
    w = bits.reshape(-1, 32).astype(jnp.uint32)
    return jnp.sum(w << jnp.arange(32, dtype=jnp.uint32)[None, :], axis=1,
                   dtype=jnp.uint32)


def _candidate_words(h, n_valid, mask_s, mask_l):
    """Packed candidate-bit words for both masks (loose ``l``, strict ``s``)."""
    L = h.shape[0]
    valid = jnp.arange(L, dtype=jnp.int32) < n_valid
    cand_l = ((h & mask_l) == 0) & valid
    cand_s = cand_l & ((h & mask_s) == 0)
    return _pack_bits(cand_l), _pack_bits(cand_s)


def _compact_words(words_l, words_s, k_cap: int):
    """The nonzero loose words of a slice, in order, at a fixed capacity.

    Returns ``(widx, wl, ws, nz_words)``: the indices of the nonzero words
    of ``words_l`` ascending (``-1`` from the true count on), the loose and
    strict words at those indices, and the true count, whatever ``k_cap``.

    ``jnp.nonzero(mask, size=k_cap)`` is a scatter-add of one update a
    *word*, and the v5e runs a scatter one update after the other: 36.6 ms
    a 128 MiB slice's 4,194,304 words at any density (PERF.md, PR 47).
    Here the only scatter has one update a 128-word *block*: the blocks'
    counts and their exclusive cumsum give each non-empty block its first
    output slot, its id is scattered there and spread over the block's
    slots by a running maximum, every slot fetches its block as a row (the
    chip gathers whole rows fast) and picks its word by rank along the 128
    lanes (a product on the MXU).  Every shape follows from ``k_cap`` and
    the word count, and no level has a capacity of its own below ``k_cap``.
    """
    n = words_l.shape[0]
    blk = 128
    while n % blk:
        blk //= 2
    nblk = n // blk
    wl2 = words_l.reshape(nblk, blk)
    cnt = jnp.sum(wl2 != 0, axis=1, dtype=jnp.int32)
    nz_words = jnp.sum(cnt)
    off = jnp.cumsum(cnt) - cnt
    # slots at or past k_cap fall off the end; 0 marks "no block starts here"
    first = jnp.zeros(k_cap, jnp.int32).at[
        jnp.where(cnt > 0, off, k_cap)].max(
            jnp.arange(1, nblk + 1, dtype=jnp.int32), mode="drop")
    slot = jnp.arange(k_cap, dtype=jnp.int32)
    bid = jnp.maximum(jax.lax.cummax(first) - 1, 0)
    rank = slot - jax.lax.cummax(jnp.where(first > 0, slot, 0))
    rows_l = wl2[bid]
    rows_nz = rows_l != 0
    # the count of nonzero lanes up to each lane, as a product with a
    # triangle of ones on the MXU: 0/1 are exact in bf16 and the sums, at
    # most 128, in f32.  (A cumsum along the lanes is 0.2-3.4 ms slower a
    # slice on the chip and takes its compiler 20-29 s at 8,192 rows;
    # doubling shifted adds 1.2 ms slower at 131,072: PERF.md, PR 47.)
    lane = jnp.arange(blk, dtype=jnp.int32)
    upto = jnp.dot(rows_nz.astype(jnp.bfloat16),
                   (lane[:, None] <= lane[None, :]).astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32).astype(jnp.int32)
    pick = rows_nz & (upto == (rank + 1)[:, None])
    filled = slot < nz_words
    widx = jnp.where(filled, bid * blk + jnp.argmax(pick, axis=1), -1)

    def picked(rows, words):
        got = jnp.sum(jnp.where(pick, rows, jnp.uint32(0)), axis=1,
                      dtype=jnp.uint32)
        return jnp.where(filled, got, words[0])

    return (widx, picked(rows_l, words_l),
            picked(words_s.reshape(nblk, blk)[bid], words_s), nz_words)


@functools.partial(jax.jit, static_argnames=("k_cap",))
def _scan_segment(ext, n_valid, mask_s, mask_l, *, k_cap: int):
    """Hash one padded segment, return sparse candidate words.

    Output: ``(widx, wl, ws, nz_words)`` — up to ``k_cap`` indices of nonzero
    candidate words (-1 padded), the loose/strict packed bits of each, and
    the true nonzero-word count for overflow detection.
    """
    with jax.named_scope("cdc_gear_hash"):
        h = _hash_ext_fast(ext)
        words_l, words_s = _candidate_words(h, n_valid, mask_s, mask_l)
    with jax.named_scope("cdc_word_compact"):
        return _compact_words(words_l, words_s, k_cap)


def _decode_words(widx, wl, ws, count, base_offset: int):
    """Sparse candidate words -> absolute (pos_l, is_s) numpy arrays."""
    widx = np.asarray(widx)[:count]
    wl = np.asarray(wl)[:count]
    ws = np.asarray(ws)[:count]
    keep = widx >= 0
    widx, wl, ws = widx[keep], wl[keep], ws[keep]
    if widx.size == 0:
        return (np.empty(0, dtype=np.int64), np.empty(0, dtype=bool))
    bits = np.arange(32, dtype=np.uint32)
    has_l = ((wl[:, None] >> bits[None, :]) & 1).astype(bool)
    has_s = ((ws[:, None] >> bits[None, :]) & 1).astype(bool)
    pos = (widx[:, None].astype(np.int64) * 32 + bits[None, :].astype(np.int64)
           + base_offset)
    return pos[has_l], has_s[has_l]


def gear_hashes_tpu(data, prev_tail: bytes = b"") -> np.ndarray:
    """Full per-position hash array on device; mirrors
    :func:`backuwup_tpu.ops.cdc_cpu.gear_hashes` (test/debug API)."""
    tail = bytes(prev_tail)[-_HALO:] if prev_tail else b""
    arr = np.frombuffer(bytes(data), dtype=np.uint8)
    ext = np.zeros(_HALO + len(arr), dtype=np.uint8)
    if tail:
        ext[_HALO - len(tail):_HALO] = np.frombuffer(tail, dtype=np.uint8)
    ext[_HALO:] = arr
    out = jax.jit(_hash_ext)(jnp.asarray(ext), jnp.int32(len(tail)))
    return np.asarray(out)


def _round_up(n: int, align: int) -> int:
    return -(-n // align) * align


def _segment_bucket(n: int) -> int:
    """Padded segment length: power-of-two bucket, >= 64 KiB, so a handful of
    compiled shapes cover every input size."""
    b = 64 * 1024
    while b < n:
        b *= 2
    return b


class TpuCdcScanner:
    """Stateless driver: chunk byte streams with the device doing the scan.

    Overflow of the sparse-word capacity (adversarial data only; real data
    yields ~1 candidate per 2^mask_l_bits bytes) falls back to the numpy
    oracle for the affected segment, preserving bit-identical output.
    """

    def __init__(self, params: Optional[CDCParams] = None,
                 segment_size: int = 128 * defaults.MiB,
                 cap_factor: int = 16):
        self.params = params or CDCParams()
        if self.params.min_size < GEAR_WINDOW:
            # _hash_ext_fast's zero-filled stream-start halo perturbs
            # h[0..30]; harmless only when no cut window reaches below 31.
            raise ValueError(
                f"TPU chunker requires min_size >= {GEAR_WINDOW}")
        self.segment_size = segment_size
        self.cap_factor = cap_factor

    def _k_cap(self, padded: int) -> int:
        expected = max(1, padded >> self.params.mask_l_bits)
        return max(512, _round_up(self.cap_factor * expected, 512))

    def candidate_positions(self, data, prev_tail: bytes = b""):
        """Sorted absolute (pos_s, pos_l) candidate arrays for ``data``."""
        params = self.params
        data = bytes(data)
        n = len(data)
        all_pos, all_s = [], []
        offset = 0
        tail = bytes(prev_tail)[-_HALO:] if prev_tail else b""
        while offset < n:
            seg = data[offset:offset + self.segment_size]
            padded = _segment_bucket(len(seg))
            ext = np.zeros(_HALO + padded, dtype=np.uint8)
            if tail:
                ext[_HALO - len(tail):_HALO] = np.frombuffer(tail, np.uint8)
            ext[_HALO:_HALO + len(seg)] = np.frombuffer(seg, np.uint8)
            k_cap = self._k_cap(padded)
            widx, wl, ws, nz_words = _scan_segment(
                jnp.asarray(ext), jnp.int32(len(seg)),
                jnp.uint32(params.mask_s), jnp.uint32(params.mask_l),
                k_cap=k_cap)
            if int(nz_words) > k_cap:  # capacity overflow: oracle rescan
                h = gear_hashes_np(seg, tail)
                cand_l = (h & np.uint32(params.mask_l)) == 0
                p = np.nonzero(cand_l)[0].astype(np.int64)
                s = (h[p] & np.uint32(params.mask_s)) == 0
                all_pos.append(p + offset)
                all_s.append(s)
            else:
                p, s = _decode_words(widx, wl, ws, k_cap, offset)
                all_pos.append(p)
                all_s.append(s)
            tail = seg[-_HALO:] if len(seg) >= _HALO else (tail + seg)[-_HALO:]
            offset += len(seg)
        if all_pos:
            pos_l = np.concatenate(all_pos)
            is_s = np.concatenate(all_s)
        else:
            pos_l = np.empty(0, dtype=np.int64)
            is_s = np.empty(0, dtype=bool)
        return pos_l[is_s], pos_l

    def chunk_stream(self, data):
        """Chunk one stream; list of (offset, length). Bit-identical to
        :func:`backuwup_tpu.ops.cdc_cpu.chunk_stream`."""
        n = len(data)
        pos_s, pos_l = self.candidate_positions(data)
        return cuts_to_chunks(select_cuts(pos_s, pos_l, n, self.params))


def _block_cum(pos, padded: int, bb: int):
    """Exclusive prefix counts of candidates per ``2^bb``-byte block.

    ``cum[b]`` = number of valid candidates (``pos < padded``; the
    compaction pads with sentinel ``padded``) at positions below
    ``b << bb``.  One scatter-add + one short cumsum, both over
    ``padded >> bb`` lanes — negligible next to even a single
    ``searchsorted`` over the candidate array.
    """
    nb = (padded >> bb) + 2
    valid = pos < padded
    cnt = jnp.zeros(nb, dtype=jnp.int32).at[
        jnp.where(valid, (pos >> bb).astype(jnp.int32), nb)
    ].add(1, mode="drop")
    return jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(cnt)[:-1]])


def _make_lookup(pos, cum, cap: int, padded: int, bb: int, probes: int = 6):
    """searchsorted-left on a sorted candidate array in TWO serialized
    gather rounds instead of ``log2(cap)``.

    ``jnp.searchsorted`` lowers to a binary search: ~15-17 *serialized*
    gather rounds over the candidate array, and ``_parallel_select``
    issues ~24 of them — the measured bulk of the 64 KiB-chunk select
    stage (PERF.md).  Here round 1 reads the block prefix table
    (:func:`_block_cum`) for a lower bound, round 2 probes the next
    ``probes+1`` candidates in parallel; sortedness makes the below-query
    prefix-run length the exact correction.  More than ``probes``
    candidates in one block (density far beyond the calibrated gear
    distribution; ``bb`` is sized to keep the expected run < 1/8) sets
    the overflow flag, which joins the row's existing oracle-fallback
    path — output stays bit-identical on every input either way.

    Queries beyond ``padded`` clamp: past-the-end results then differ
    from true searchsorted only in how far PAST the last valid candidate
    they land, which every call site masks (window checks compare the
    gathered position against an in-stream bound; gap-jump targets gather
    the same sentinel either way).
    """
    return functools.partial(_lookup, pos, cum, cap=cap, padded=padded,
                             bb=bb, probes=probes)


# jitted, so that the ~26 lookups of one ``_parallel_select`` are traced
# once a query shape and bound as calls: seven gathers each, they were
# more than half of what tracing a manifest program's scan + select cost
@functools.partial(jax.jit, static_argnames=("cap", "padded", "bb", "probes"))
def _lookup(pos, cum, q, *, cap: int, padded: int, bb: int, probes: int):
    qc = jnp.clip(q, 0, padded)
    idx0 = cum[jnp.minimum(qc >> bb, cum.shape[0] - 1)]
    adv = jnp.zeros_like(idx0)
    over = None
    for k in range(probes + 1):
        i = idx0 + k
        below = (i < cap) & (pos[jnp.minimum(i, cap - 1)] < qc)
        if k < probes:
            adv = adv + below.astype(jnp.int32)
        else:
            over = below
    return idx0 + adv, over


def _parallel_select(pos_l, pos_s, n, *, min_size: int, desired_size: int,
                     max_size: int, s_cap: int, l_cap: int, cut_cap: int,
                     padded: int, block_bits: int,
                     probe_iters: int = 6):
    """FastCDC cut selection in O(log) depth instead of a sequential loop.

    The greedy selection (``select_cuts``) is a chain walk: each cut is a
    function of the previous cut only.  The walk is parallelized with the
    classic pointer-jumping construction:

    * ``F(c)`` — the next *candidate* cut after a chunk ending at loose
      candidate ``c``, plus the count of forced (max-size) cuts emitted in
      between — is computed for EVERY candidate at once.  Forced runs are
      resolved in closed form: with no candidate in reach, the next start
      that could possibly cut jumps straight past the whole candidate-free
      gap (``steps = ceil((target-y)/max)``), so even an all-zeros stream
      (zero candidates) resolves in one probe.  ``probe_iters`` bounds the
      alignment retries; unresolved nodes flag the row for the oracle
      fallback (adversarial interval patterns only).
    * Doubling tables ``nxt_k = nxt_{k-1}[nxt_{k-1}]`` give the node and
      emitted-cut count ``2^k`` hops ahead.
    * Each output slot ``m`` independently walks the tables high-to-low
      (take a ``2^k``-hop block iff its emitted count stays <= ``m``),
      then reads its cut: a forced position (arithmetic) or the hop's
      candidate/terminal cut.

    Replaces a ``cut_cap``-iteration ``lax.while_loop`` whose per-step
    latency dominated small-chunk configs.  Bit-identical to
    :func:`backuwup_tpu.ops.cdc_cpu.select_cuts` (property-tested).
    """
    m = jnp.int32(min_size)
    d = jnp.int32(desired_size)
    M = jnp.int32(max_size)
    TERM = jnp.int32(l_cap)

    # Any-lane probe overflow is ORed into the row's unresolved flag, but
    # ONLY for lanes whose lookup result is actually consumed: sentinel-
    # clamped past-the-end queries and already-resolved/terminal lanes
    # always probe the stream's densest block, and counting them would
    # drop whole rows to the CPU oracle on locally dense (non-adversarial)
    # data even though every consumed lookup succeeded.
    look_ovf = []
    look_s = _make_lookup(pos_s, _block_cum(pos_s, padded, block_bits),
                          s_cap, padded, block_bits)
    look_l = _make_lookup(pos_l, _block_cum(pos_l, padded, block_bits),
                          l_cap, padded, block_bits)

    def ss_s(q, use=None):
        i, ov = look_s(q)
        look_ovf.append(jnp.any(ov if use is None else ov & use))
        return i

    def ss_l(q, use=None):
        i, ov = look_l(q)
        look_ovf.append(jnp.any(ov if use is None else ov & use))
        return i

    def step_from(x, use=None):
        """Candidate-window check for starts ``x``: (hit, cut position)."""
        lo1 = x + (m - 1)
        hi1 = jnp.minimum(x + (d - 2), n - 2)
        i = ss_s(lo1, use)
        e1 = pos_s[jnp.minimum(i, s_cap - 1)]
        ok1 = (i < s_cap) & (e1 <= hi1)
        lo2 = x + (d - 1)
        hi2 = jnp.minimum(x + (M - 2), n - 2)
        j = ss_l(lo2, use)
        e2 = pos_l[jnp.minimum(j, l_cap - 1)]
        ok2 = (j < l_cap) & (e2 <= hi2)
        return ok1 | ok2, jnp.where(ok1, e1, e2)

    def resolve(x0):
        """F for starts ``x0``: (kind TERM/node-pos, forced count,
        final cut pos, unresolved)."""
        y = x0
        jcnt = jnp.zeros_like(x0)
        done = jnp.zeros(x0.shape, dtype=bool)
        is_term = jnp.zeros(x0.shape, dtype=bool)
        final = jnp.full_like(x0, -1)
        for _ in range(probe_iters):
            short = (n - y) <= m  # short tail -> single final chunk
            # short lanes resolve to n-1 regardless of hit/e, so their
            # window lookups are dead; done lanes never consume again
            hit, e = step_from(y, use=~done & ~short)
            at_eof = y >= n - M   # forced cut would land at n-1
            now_term = short | (~hit & at_eof)
            resolved = ~done & (short | hit | at_eof)
            final = jnp.where(resolved,
                              jnp.where(short, n - 1,
                                        jnp.where(hit, e, n - 1)), final)
            is_term = jnp.where(resolved, now_term, is_term)
            # forced-EOF emits its n-1 cut as the hop's final cut, not as
            # one of the arithmetic forced cuts
            done = done | resolved
            # closed-form jump over the candidate-free gap: earliest start
            # that could see the next strict/loose candidate in-window
            # (consumed only by lanes still jumping, i.e. ~done post-update)
            qs = pos_s[jnp.minimum(ss_s(y + (m - 1), ~done), s_cap - 1)]
            ql = pos_l[jnp.minimum(ss_l(y + (d - 1), ~done), l_cap - 1)]
            target = jnp.minimum(jnp.minimum(qs - (d - 2), ql - (M - 2)),
                                 n - M)
            steps = jnp.maximum(
                (target - y + M - 1) // M, 1)
            y = jnp.where(done, y, y + steps * M)
            jcnt = jnp.where(done, jcnt, jcnt + steps)
        return is_term, jcnt, final, ~done

    # F for every candidate node (start = pos_l[c] + 1) and for START
    starts = jnp.concatenate([pos_l + 1, jnp.zeros(1, dtype=pos_l.dtype)])
    is_term, jcnt, final, unres = resolve(starts)
    node_final = final[:l_cap]
    node_term = is_term[:l_cap]
    node_j = jcnt[:l_cap]
    node_un = unres[:l_cap]
    # next node index: the final cut is itself a loose candidate unless
    # terminal (exact match by construction)
    # unresolved nodes carry final=-1 (garbage query) and already flag the
    # row via the unresolved chain, so they don't accumulate overflow here
    nxt0 = jnp.where(
        node_term, TERM,
        ss_l(node_final, ~node_term & ~node_un).astype(jnp.int32))
    emit0 = node_j + 1  # j forced cuts + 1 candidate/terminal cut
    # TERM self-loop emits nothing
    nxt0 = jnp.concatenate([nxt0, TERM[None]])
    emit0 = jnp.concatenate([emit0, jnp.zeros(1, jnp.int32)])
    un0 = jnp.concatenate([node_un, jnp.zeros(1, dtype=bool)])

    # 2^(levels-1) hops must cover the longest possible chain (cut_cap)
    levels = max(1, cut_cap.bit_length() + 1)
    nxts, emits, uns = [nxt0], [emit0], [un0]
    for _ in range(levels - 1):
        nk, ek, uk = nxts[-1], emits[-1], uns[-1]
        nxts.append(nk[nk])
        emits.append(ek + ek[nk])
        uns.append(uk | uk[nk])

    # hop 0: from START (virtual cut at -1, start 0)
    h0_term = is_term[l_cap]
    h0_j = jcnt[l_cap]
    h0_final = final[l_cap]
    h0_un = unres[l_cap]
    b1 = jnp.where(
        h0_term, TERM, ss_l(h0_final, ~h0_term & ~h0_un).astype(jnp.int32))
    h0_emit = h0_j + 1
    total = h0_emit + emits[-1][b1]
    row_unres = h0_un | uns[-1][b1]
    for ov in look_ovf:
        row_unres = row_unres | ov
    n_cuts = jnp.where(n > 0, total, 0)

    # per-slot table walk
    mslot = jnp.arange(cut_cap, dtype=jnp.int32)
    in_h0 = mslot < h0_emit
    # hop-0 cuts: forced k*M-1 for slot k-1, then the resolved final
    cut_h0 = jnp.where(mslot < h0_j, (mslot + 1) * M - 1, h0_final)
    mrel = mslot - h0_emit
    cur = jnp.full(cut_cap, 0, dtype=jnp.int32) + b1
    acc = jnp.zeros(cut_cap, dtype=jnp.int32)
    for k in range(levels - 1, -1, -1):
        cand_acc = acc + emits[k][cur]
        take = cand_acc <= mrel
        cur = jnp.where(take, nxts[k][cur], cur)
        acc = jnp.where(take, cand_acc, acc)
    # the hop from `cur` covers slot mrel: r-th of its fcount forced cuts,
    # or its final candidate/terminal cut
    r = mrel - acc
    cur_safe = jnp.minimum(cur, TERM)
    x_cur = pos_l[jnp.minimum(cur_safe, l_cap - 1)] + 1
    fcount = jnp.maximum(emit0[cur_safe] - 1, 0)
    final_cur = node_final[jnp.minimum(cur_safe, l_cap - 1)]
    cut_m = jnp.where(r < fcount, x_cur + (r + 1) * M - 1, final_cur)
    cuts = jnp.where(in_h0, cut_h0, cut_m)
    cuts = jnp.where(mslot < n_cuts, cuts, -1)
    return n_cuts, cuts, row_unres


@functools.partial(jax.jit, static_argnames=(
    "min_size", "desired_size", "max_size", "mask_s", "mask_l",
    "s_cap", "l_cap", "cut_cap", "fused"))
def scan_select_batch(ext_b: jnp.ndarray, nv_b: jnp.ndarray, *,
                      min_size: int, desired_size: int, max_size: int,
                      mask_s: int, mask_l: int,
                      s_cap: int, l_cap: int, cut_cap: int,
                      fused: bool = False) -> jnp.ndarray:
    """Fused gear scan + FastCDC cut selection, fully on device.

    ``(B, _HALO+P) u8 -> (B, 2+cut_cap) i32`` packed per row as
    ``[overflow, n_cuts, inclusive chunk end positions...]``.  This is the
    whole CDC front end in ONE dispatch: hashes via the doubling ladder,
    candidate compaction via fixed-capacity ``nonzero``, and the
    min/desired/max two-mask selection (bit-identical to
    :func:`backuwup_tpu.ops.cdc_cpu.select_cuts`) over the sparse
    candidates — so the only download a caller needs is the tiny packed
    cut list, instead of candidate words plus a host selection pass plus a
    chunk-meta re-upload.  ``overflow`` flags candidate counts beyond the
    sparse capacity (adversarial data); such rows must be re-chunked by
    the oracle.

    With ``fused=True`` the hash+mask+pack front end runs as the Mosaic
    strip kernel (:func:`backuwup_tpu.ops.scan_fused.fused_candidate_words`;
    PERF.md section 5 has its device seconds beside the XLA ladder's);
    callers gate on
    :func:`backuwup_tpu.ops.scan_fused.fused_scan_available`, which
    parity-checks the kernel against the XLA path on the live runtime.
    """
    P = ext_b.shape[1] - _HALO
    ms = jnp.uint32(mask_s)
    ml = jnp.uint32(mask_l)

    # word-level sparse capacity for the two-level compaction below;
    # nearly every candidate lands in its own 32-bit word on real data
    w_cap = max(512, min(l_cap, P // 32 if P >= 32 else 1))

    # block pyramid for the compaction: a direct fixed-capacity nonzero
    # over all P/32 words pays a full-length cumsum (~30+ ms on a 256 MiB
    # segment); reducing 128-word blocks to any-flags first shrinks the
    # expensive cumsums to (P/4096) + (b_cap*128) lanes.
    n_words = (P + 31) // 32
    blk = 128
    while blk > 1 and n_words % blk:
        blk //= 2
    nblk = n_words // blk
    b_cap = min(nblk, max(512, w_cap // 4))

    def compact_words(words_l, words_s):
        """Fixed-capacity (pos_l, is_s-derived pos_s) from packed
        candidate words via THREE-LEVEL compaction.

        A direct ``jnp.nonzero`` over the full position axis costs seconds
        on a 128 MiB segment (measured: the cumsum+scatter over 1.3e8
        lanes dominates the whole pipeline).  Candidate bits arrive packed
        32:1 into u32 words; word blocks reduce to any-flags whose
        ``nonzero`` is tiny, surviving blocks' words are gathered and
        compacted at ``w_cap``, and the final expansion works on
        ``w_cap*32`` lanes.  The strict mask's bits ride along through the
        SAME compaction (its candidates are a subset of the loose ones),
        so no full-axis cumsum or reduction remains.
        """
        wl2 = words_l.reshape(nblk, blk)
        ws2 = words_s.reshape(nblk, blk)
        any_b = jnp.any(wl2 != 0, axis=1)
        (bidx,) = jnp.nonzero(any_b, size=b_cap, fill_value=nblk)
        bsafe = jnp.clip(bidx, 0, nblk - 1)
        in_b = (bidx < nblk)[:, None]
        sub_l = jnp.where(in_b, wl2[bsafe], jnp.uint32(0)).reshape(-1)
        sub_s = jnp.where(in_b, ws2[bsafe], jnp.uint32(0)).reshape(-1)
        # word index (in the full array) of each gathered sub-word
        sub_widx = (bidx[:, None].astype(jnp.int32) * blk
                    + jnp.arange(blk, dtype=jnp.int32)[None, :]).reshape(-1)
        nzw = sub_l != 0
        sub_n = sub_l.shape[0]
        (wsel,) = jnp.nonzero(nzw, size=w_cap, fill_value=sub_n)
        wsafe = jnp.clip(wsel, 0, sub_n - 1)
        in_range = wsel < sub_n
        bits_l = jnp.where(in_range, sub_l[wsafe], jnp.uint32(0))
        bits_s = jnp.where(in_range, sub_s[wsafe], jnp.uint32(0))
        widx = jnp.where(in_range, sub_widx[wsafe], n_words)
        lane = jnp.arange(32, dtype=jnp.int32)[None, :]
        has_l = ((bits_l[:, None] >> lane.astype(jnp.uint32)) & 1) == 1
        has_s = ((bits_s[:, None] >> lane.astype(jnp.uint32)) & 1) == 1
        posmat = widx[:, None] * 32 + lane
        flat_l = has_l.reshape(-1)
        flat_s = has_s.reshape(-1)
        # no masking needed: sel below only gathers flat_l-true lanes, and
        # out-of-range gathers are overwritten with P by sel_ok
        flat_pos = posmat.reshape(-1)
        flat_n = flat_pos.shape[0]
        (sel,) = jnp.nonzero(flat_l, size=l_cap, fill_value=flat_n)
        sel_ok = sel < flat_n
        sel_safe = jnp.clip(sel, 0, flat_n - 1)
        pos_l = jnp.where(sel_ok, flat_pos[sel_safe], P).astype(jnp.int32)
        is_s = sel_ok & flat_s[sel_safe]
        (ssel,) = jnp.nonzero(is_s, size=s_cap, fill_value=l_cap)
        pos_s = jnp.where(ssel < l_cap,
                          pos_l[jnp.clip(ssel, 0, l_cap - 1)],
                          jnp.int32(P))
        overflow = ((jnp.sum(any_b.astype(jnp.int32)) > b_cap)
                    | (jnp.sum(nzw.astype(jnp.int32)) > w_cap)
                    | (jnp.sum(flat_l.astype(jnp.int32)) > l_cap)
                    | (jnp.sum(is_s.astype(jnp.int32)) > s_cap))
        return pos_l, pos_s, overflow

    # lookup-block size: expected loose-candidate count per block stays
    # <= 1/8 (density 2^-mask_l_bits), so the 6-probe correction never
    # overflows on distribution-typical data
    mask_l_bits = bin(mask_l).count("1")
    block_bits = max(5, min(11, mask_l_bits - 3))

    def one(n, words_l, words_s):
        pos_l, pos_s, ovf = compact_words(words_l, words_s)
        n_cuts, cuts, unres = _parallel_select(
            pos_l, pos_s, n, min_size=min_size, desired_size=desired_size,
            max_size=max_size, s_cap=s_cap, l_cap=l_cap, cut_cap=cut_cap,
            padded=P, block_bits=block_bits)
        overflow = (ovf | unres).astype(jnp.int32)
        return jnp.concatenate([overflow[None], n_cuts[None], cuts])

    nv_i = nv_b.astype(jnp.int32)
    if fused:
        from .scan_fused import fused_candidate_words
        wl_b, ws_b = fused_candidate_words(ext_b, nv_i,
                                           mask_s=mask_s, mask_l=mask_l)
    else:
        def words_one(ext, n):
            h = _hash_ext_fast(ext)
            return _candidate_words(h, n, ms, ml)

        wl_b, ws_b = jax.vmap(words_one)(ext_b, nv_i)

    return jax.vmap(one)(nv_i, wl_b, ws_b)
