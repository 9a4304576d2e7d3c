"""Flat leaf-pool BLAKE3: digest every chunk of a batch in ONE program.

A digest stage that lays chunks out as padded tiles a length class pays
per-class costs ~12 times per batch: a full-length ``nonzero``
compaction, a padded gather at the class span, an XLA word-prep pass, a
separate Pallas grid, and a scatter — dispatch and word-prep overhead,
not leaf compute.  The reference has no equivalent stage at all — it
hashes chunks one at a time on the CPU (``dir_packer.rs:285-311``); this
module is how the same work maps onto a TPU without the reference's
serial structure.

Design: decompose EVERY chunk into its 1 KiB BLAKE3 leaves and run one
flat pool of leaves through a single scan:

1. **Leaf plan, on device.**  A chunk of ``l`` bytes at offset ``o``
   owns ``ceil(l/1024)`` consecutive pool lanes; lane ``k`` covers bytes
   ``[o + 1024k, o + 1024k + min(1024, l - 1024k))`` with BLAKE3 chunk
   counter ``k``.  Ownership is materialized with one scatter of chunk
   ids at each chunk's first lane + a running max — no per-class
   compaction, no searchsorted.
2. **One leaf scan.**  The pool gathers once (1 KiB per lane), word-preps
   once, and runs ONE Pallas grid (the XLA leaf scan off the TPU) over
   all lanes.  Padding waste is the final partial leaf of each chunk —
   near-zero, where class tiles pad every chunk to its class span
   (~1.2-1.5x measured).  The leaf scan is ~94% of single-chunk BLAKE3 compute
   (16 blocks/leaf vs 1 merge per leaf pair), so this stage holds
   essentially all the FLOPs.
3. **Tiny tiered tree.**  Leaf chaining values (32 B/leaf — 32x smaller
   than payload) are gathered per chunk into 2-3 geometric leaf-count
   tiers and pair-merged by :func:`blake3_tpu.tree_reduce_groups`; tier
   padding costs ~1/16 of leaf work at worst, so coarse tiers are fine
   where payload-level class tiles were not.  Tier capacities cascade
   upward (excess hands to the next tier; only terminus overflow aborts
   to the host-tiled path, bit-exact either way).

Digests are bit-identical to :mod:`backuwup_tpu.ops.blake3_cpu` (the
spec oracle) — property-tested in interpret mode and checked on the live
runtime where a ``DevicePipeline`` is made (:func:`_pool_digest_probe`).

Mesh usage (``manifest_device.scan_digest_batch_pool_mesh``): each shard
runs its own pool over its row slice with PER-SHARD ``leaf_cap``/``tiers``
sized for ``B/D`` rows, so the ``(1,)`` overflow flag widens to one flag
per shard and adversarial data re-runs only that shard's rows.  Two
accumulator invariants the dedup handoff leans on: (a) ``acc`` is
zero-initialized and only cascade-placed chunks scatter into it, so
unplaced/invalid lanes stay all-zero — exactly the probe kernel's
padding-query convention; (b) when the leaf pool itself overflows
(``pool_short > 0``) the affected chunks still cascade-place but carry
WRONG digests — the shard's overflow flag forces the host-tiled re-run
for its manifests, and any wrong keys the handoff inserted are inert
junk (2^-128 collision odds against real BLAKE3 prefixes).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .blake3_cpu import (
    BLOCK_LEN,
    CHUNK_END,
    CHUNK_LEN,
    CHUNK_START,
    MAX_LEAVES_PER_CHUNK,
    ROOT,
)
from .blake3_tpu import (
    _IV_NP,
    _bytes_to_words,
    _compress_cols,
    _leaf_scan_pallas,
    _vary_like,
    tree_reduce_groups,
)


def _leaf_scan_xla_flat(words_flat: jnp.ndarray, nb: jnp.ndarray,
                        lbl: jnp.ndarray, counter: jnp.ndarray):
    """Flat-lane XLA leaf scan: (lanes, 16, 16) u32 -> (lanes, 8) cv +
    (lanes, 8) penultimate cv (state before the last block's compression,
    for the single-leaf ROOT recompute).  The pool's leaf scan off the TPU
    and the Pallas kernel's reference; masking mirrors ``digest_padded``'s
    leaf loop.
    """
    lanes = words_flat.shape[0]
    zeros = jnp.zeros(lanes, dtype=jnp.uint32)
    iv_cols = [jnp.broadcast_to(jnp.uint32(_IV_NP[i]), (lanes,)) + zeros
               for i in range(8)]
    counter = counter.astype(jnp.uint32)

    def body(blk, carry):
        cv, cv_pre = carry
        mslab = jax.lax.dynamic_index_in_dim(words_flat, blk, axis=1,
                                             keepdims=False)  # (lanes, 16)
        m = [mslab[:, w] for w in range(16)]
        active = blk < nb
        is_last = blk == nb - 1
        flags = jnp.where(blk == 0, jnp.uint32(CHUNK_START), jnp.uint32(0))
        flags = jnp.where(is_last, flags | jnp.uint32(CHUNK_END), flags)
        blen = jnp.where(is_last, lbl, jnp.uint32(BLOCK_LEN))
        cv_pre = [jnp.where(is_last, c, p) for c, p in zip(cv, cv_pre)]
        out = _compress_cols(cv, m, counter, zeros, blen, flags)
        cv = [jnp.where(active, o, c) for o, c in zip(out, cv)]
        return cv, cv_pre

    cv, cv_pre = jax.lax.fori_loop(
        0, MAX_LEAVES_PER_CHUNK, body,
        _vary_like((iv_cols, list(iv_cols)), nb, lbl, counter))
    return jnp.stack(cv, axis=1), jnp.stack(cv_pre, axis=1)


def _leaf_rows(flat: jnp.ndarray, off: jnp.ndarray) -> jnp.ndarray:
    """The pool's gather: ``(lanes, 1024) u8``, lane ``j`` holding
    ``flat[off[j] : off[j] + 1024]``.

    A lane's 1 KiB at an arbitrary byte offset lies in rows ``q`` and
    ``q + 1`` of the stream seen as whole 1 KiB rows, ``s = off % 1024``
    bytes in.  Whole rows are what the v5e gathers fast, so: two row
    gathers, then the shift by ``s`` as ten conditional shifts by a power
    of two along the row, the row pair narrowing by what each stage can
    no longer need.  Asked for as ``lanes`` byte slices at byte offsets
    (``vmap(dynamic_slice)``) the v5e runs a loop of
    ``lanes`` steps, 1.8 us each: 0.300 s at the long file's 164,864
    lanes where this form takes 0.029 s (chip run, PR 42; the other
    forms tried are in ``scripts/probe_pool_gather.py``).

    Slack: the row view is cut at a whole row, so ``flat`` must reach at
    least ``CHUNK_LEN`` bytes past the last chunk's end for the cut to
    keep every row that holds a chunk's byte.  The index of row ``q + 1``
    is held inside the view: where that changes it, ``q`` is the view's
    last row, the lane's bytes end in it, and the caller's mask drops
    what the stand-in row put behind them.
    """
    n_rows = flat.shape[0] // CHUNK_LEN
    rows = flat[:n_rows * CHUNK_LEN].reshape(n_rows, CHUNK_LEN)
    q = off // CHUNK_LEN
    s = (off % CHUNK_LEN)[:, None]
    x = jnp.concatenate(
        [rows[q], rows[jnp.minimum(q + 1, n_rows - 1)]], axis=1)
    for bit in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1):
        x = jnp.where((s & bit) != 0, x[:, bit:], x[:, :x.shape[1] - bit])
    return x[:, :CHUNK_LEN]


@functools.lru_cache(maxsize=32)
def tier_spans(max_leaves: int, n_tiers: int = 3) -> Tuple[int, ...]:
    """Geometric leaf-count tier grid ending at ``max_leaves``.

    Tree padding costs ≤ span/actual of ~1/16 of leaf compute, so a
    2x-geometric grid (vs the payload path's ~12 linear classes) keeps
    total tree overcompute a few percent while cutting the number of
    tree tiles to 2-3.
    """
    spans = [max_leaves]
    while len(spans) < n_tiers and spans[-1] > 8:
        spans.append(max(8, -(-spans[-1] // 2 // 8) * 8))
    return tuple(reversed([s for i, s in enumerate(spans)
                           if i == 0 or s < spans[i - 1]]))


def leaf_capacity(total_padded_bytes: int, max_chunks: int) -> int:
    """Structural upper bound on pool lanes: every payload byte plus at
    most one partial leaf per chunk.  No distribution calibration — the
    pool's lanes, unlike its tiers, cannot overflow on adversarial data."""
    cap = total_padded_bytes // CHUNK_LEN + max_chunks
    return -(-cap // 512) * 512


@functools.partial(jax.jit, static_argnames=(
    "leaf_cap", "tiers", "pallas", "interpret"))
def pool_digest(flat: jnp.ndarray, offs: jnp.ndarray, lens: jnp.ndarray, *,
                leaf_cap: int, tiers: Tuple[Tuple[int, int], ...],
                pallas: bool = False, interpret: bool = False):
    """Digest ``C`` chunks carved from one resident byte pool.

    ``flat``: (N,) u8 with >= CHUNK_LEN slack bytes after the last chunk
    (the gather reads the stream as whole 1 KiB rows, cut at a whole row:
    :func:`_leaf_rows`); ``offs``/``lens``: (C,) i32
    absolute byte offsets / lengths (len <= 0 marks an unused slot).
    ``tiers``: ((leaf_span, chunk_capacity), ...) ascending by span; the
    last span must be >= the largest possible leaf count.

    Returns ``((C, 8) u32 root chaining values, (1,) i32 overflow)``;
    overflow counts chunks the tier cascade could not place plus any
    pool-lane shortfall (caller falls back, output stays bit-exact).
    """
    C = offs.shape[0]
    offs = offs.astype(jnp.int32)
    lens = lens.astype(jnp.int32)
    valid = lens > 0
    lv = jnp.where(valid, -(-lens // CHUNK_LEN), 0)  # leaves per chunk
    base = jnp.cumsum(lv) - lv  # exclusive prefix
    total = base[-1] + lv[-1]
    pool_short = jnp.maximum(total - leaf_cap, 0)

    # --- ownership fill: one scatter + running max -------------------------
    start_idx = jnp.where(valid, jnp.minimum(base, leaf_cap - 1), leaf_cap)
    marker = jnp.full(leaf_cap, -1, dtype=jnp.int32)
    marker = marker.at[start_idx].max(jnp.arange(C, dtype=jnp.int32),
                                      mode="drop")
    owner = jax.lax.associative_scan(jnp.maximum, marker)  # (leaf_cap,)
    oc = jnp.clip(owner, 0, C - 1)
    lane = jnp.arange(leaf_cap, dtype=jnp.int32)
    k = lane - base[oc]
    active = (owner >= 0) & (k < lv[oc])
    nbytes = jnp.where(active,
                       jnp.clip(lens[oc] - k * CHUNK_LEN, 0, CHUNK_LEN), 0)

    # --- one gather + one word-prep + ONE leaf scan ------------------------
    off = jnp.where(active, offs[oc] + k * CHUNK_LEN, 0)

    data = _leaf_rows(flat, off)  # (leaf_cap, 1024)
    data = jnp.where(
        jnp.arange(CHUNK_LEN, dtype=jnp.int32)[None, :] < nbytes[:, None],
        data, jnp.uint8(0))
    words = _bytes_to_words(
        data.reshape(leaf_cap, MAX_LEAVES_PER_CHUNK, BLOCK_LEN))
    nb = jnp.maximum(1, -(-nbytes // BLOCK_LEN))
    lbl = (nbytes - (nb - 1) * BLOCK_LEN).astype(jnp.uint32)
    kc = jnp.maximum(k, 0)
    if pallas:
        cvp_mat, cvpre_mat = _leaf_scan_pallas(words, nb, lbl, kc,
                                               interpret=interpret)
    else:
        cvp_mat, cvpre_mat = _leaf_scan_xla_flat(words, nb, lbl,
                                                 kc.astype(jnp.uint32))
    # slack rows so fixed-span tier gathers never clamp
    top_span = tiers[-1][0]
    cv_pool = jnp.pad(cvp_mat, ((0, top_span), (0, 0)))

    # --- tiered tree reduction over leaf CVs -------------------------------
    cls = jnp.zeros(C, dtype=jnp.int32)
    for span, _cap in tiers[:-1]:
        cls = cls + (lv > span).astype(jnp.int32)
    carry = jnp.zeros(C, dtype=bool)
    placed = []  # a tier with room: (span, slots' chunk ids, base lanes, counts)
    for i, (span, cap) in enumerate(tiers):
        if cap == 0:
            carry = carry | (valid & (cls == i))
            continue
        mine = valid & ((cls == i) | carry)
        rank = jnp.cumsum(mine.astype(jnp.int32)) - 1
        take = mine & (rank < cap)
        carry = mine & ~take
        (idx,) = jnp.nonzero(take, size=cap, fill_value=C)
        safe = jnp.clip(idx, 0, C - 1)
        got = idx < C
        placed.append((span, idx,
                       jnp.where(got, jnp.minimum(base[safe], leaf_cap - 1), 0),
                       jnp.where(got, lv[safe], 1)))
    acc = jnp.zeros((C, 8), dtype=jnp.uint32)
    if placed:
        # single-leaf chunks: recompress leaf 0's final block with ROOT,
        # every tier's slots in one compression
        b = jnp.concatenate([p[2] for p in placed])
        cnt = jnp.concatenate([p[3] for p in placed])
        nb0 = nb[b]
        m0 = jnp.take_along_axis(
            words[b], (nb0 - 1)[:, None, None], axis=1)[:, 0]  # (slots, 16)
        flags0 = (jnp.where(nb0 == 1, jnp.uint32(CHUNK_START), jnp.uint32(0))
                  | jnp.uint32(CHUNK_END) | jnp.uint32(ROOT))
        zb = jnp.zeros_like(lbl[b])
        root_single = _compress_cols(
            [cvpre_mat[b, ci] for ci in range(8)],
            [m0[:, w] for w in range(16)], zb, zb, lbl[b], flags0)
        root_seed = [jnp.where(cnt == 1, rs, jnp.uint32(0))
                     for rs in root_single]
        groups, at = [], 0
        for span, idx, b_t, cnt_t in placed:
            leaf_mat = jax.vmap(lambda bb, span=span: jax.lax.dynamic_slice(
                cv_pool, (bb, 0), (span, 8)))(b_t)  # (cap, span, 8)
            n = idx.shape[0]
            groups.append(([leaf_mat[:, :, ci] for ci in range(8)], cnt_t,
                           [rs[at:at + n] for rs in root_seed]))
            at += n
        # the tiers' trees in the levels of the widest (tree_reduce_groups)
        for (_span, idx, _b, _cnt), out_tile in zip(
                placed, tree_reduce_groups(groups)):
            # fill slots keep idx == C: out of range -> dropped (clipping
            # to C-1 would duplicate-write a real chunk's row, undefined
            # order)
            acc = acc.at[idx].set(out_tile, mode="drop")
    ovf = (jnp.sum(carry.astype(jnp.int32)) + pool_short)[None]
    return acc, ovf


@functools.lru_cache(maxsize=2)
def _pool_digest_probe(pallas: bool) -> None:
    """Run the compiled leaf-pool path against the HOST spec oracle on
    the live runtime, once a form (where a ``DevicePipeline`` is made);
    raises on a lowering failure (the compiler's own error) or on the
    first digest that differs, on any platform: the pool is the batched
    route's only digest, and a fault there must not turn into a slower
    green run."""
    from .blake3_cpu import blake3_hash
    rng = np.random.default_rng(7)
    flat = rng.integers(0, 256, 256 * 1024, dtype=np.uint8)
    lens = [1, 63, 64, 65, 1023, 1024, 1025, 4096, 70_000, 100_000]
    offs, cur = [], 0
    for l in lens:
        offs.append(cur)
        cur += l
    C = 16
    offs_a = np.zeros(C, np.int32)
    lens_a = np.zeros(C, np.int32)
    offs_a[:len(lens)] = offs
    lens_a[:len(lens)] = lens
    spans = tier_spans(128)
    acc, ovf = pool_digest(
        jnp.asarray(np.concatenate([flat, np.zeros(CHUNK_LEN, np.uint8)])),
        jnp.asarray(offs_a), jnp.asarray(lens_a),
        leaf_cap=leaf_capacity(cur, C),
        tiers=tuple((s, 8) for s in spans), pallas=pallas)
    acc = np.asarray(acc)
    if int(np.asarray(ovf)[0]) != 0:
        raise RuntimeError("leaf-pool digest probe overflowed its tiers")
    for i, l in enumerate(lens):
        want = blake3_hash(flat[offs[i]:offs[i] + l].tobytes())
        if want != np.ascontiguousarray(acc[i].astype("<u4")).tobytes():
            raise RuntimeError(
                f"leaf-pool digest (pallas={pallas}) disagrees with the "
                f"BLAKE3 oracle on a {l}-byte chunk")


@functools.lru_cache(maxsize=64)
def tier_caps(spans: Tuple[int, ...], fracs_by_leaves, expect_total: float,
              n_extra: int) -> Tuple[Tuple[int, int], ...]:
    """Capacity per tier from a (leaf-count -> fraction) histogram.

    ``fracs_by_leaves``: tuple of (max_leaves_of_bin, fraction) pairs —
    hashable so the plan caches per (params, shape).  Expectation +
    0.75 sigma (binomial); the terminus carries the real
    slack plus ``n_extra`` (short per-row tails land in tier 0).
    """
    out = []
    for i, span in enumerate(spans):
        lo = spans[i - 1] if i else 0
        frac = sum(f for ml, f in fracs_by_leaves if lo < ml <= span)
        mu = expect_total * frac
        sigma = (max(mu, 0.0) * max(1.0 - frac, 0.0)) ** 0.5
        want = mu + 0.75 * sigma + 1 + (n_extra if i == 0 else 0)
        if i == len(spans) - 1:
            want += 8 + 0.02 * expect_total
        out.append((span, -(-int(want) // 4) * 4))
    return tuple(out)
