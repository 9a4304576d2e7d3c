"""Batched BLAKE3 on TPU: the fingerprint stage of the dedup pipeline.

The reference hashes every chunk and tree blob with the SIMD ``blake3`` crate
(``client/src/backup/filesystem/dir_packer.rs:286,321,353``), one chunk at a
time.  Here many independent inputs are digested in one device program:

* Each input is padded to ``L`` 1 KiB leaf chunks; a batch is ``(B, L*1024)``
  u8.  The compression function is vectorized over ``B*L`` lanes as pure u32
  VPU arithmetic (rotates = shift pairs), with the 7 rounds and the message
  permutation schedule unrolled at trace time.
* The leaf scan walks the 16 blocks of every chunk in lock-step; per-lane
  masks (block counts, last-block lengths, CHUNK_START/END/ROOT flags)
  make digests exact for every input length, including 0.
* The binary tree reduction pair-merges chaining values level by level;
  an unpaired rightmost node rides up unchanged, which reproduces BLAKE3's
  largest-power-of-two-left split exactly (see blake3_cpu.py docstring).
* Structure and masking mirror :class:`backuwup_tpu.ops.blake3_cpu.Blake3Numpy`
  line for line, and digests are bit-identical to the scalar spec
  implementation — self-consistent dedup requires nothing less.

Batching policy lives in :func:`bucketed_batches`: variable-size CDC chunks
(256 KiB..3 MiB for default params) are grouped into a handful of (B, L)
compiled shapes (``defaults.BLAKE3_LEAF_BUCKETS``) to bound both padding
waste and XLA recompiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import defaults
from ..obs import profile as obs_profile
from .blake3_cpu import (
    BLOCK_LEN,
    CHUNK_END,
    CHUNK_LEN,
    CHUNK_START,
    G_SCHEDULE,
    IV,
    MAX_LEAVES_PER_CHUNK,
    MSG_PERMUTATION,
    PARENT,
    ROOT,
)

_IV_NP = np.array(IV, dtype=np.uint32)


# The compression is written in ``jax.lax`` primitives, not operators: an
# operator on a tracer goes through ``jax.numpy``'s dispatch (~0.1-0.2 ms a
# call where the primitive binds in ~0.02-0.05 ms), and a manifest program
# traces ~8,000 of them in its compressions.  The jaxpr is the same.
_add, _xor, _or = jax.lax.add, jax.lax.bitwise_xor, jax.lax.bitwise_or


def _rotr(x, n: int):
    return _or(jax.lax.shift_right_logical(x, np.uint32(n)),
               jax.lax.shift_left(x, np.uint32(32 - n)))


def _vma_of(*xs) -> frozenset:
    """Union of the arrays' varying manual mesh axes (``jax.shard_map``);
    empty outside ``shard_map``."""
    return frozenset().union(*(jax.typeof(x).vma for x in xs))


def _vary_like(tree, *refs):
    """Give every array of ``tree`` the union of the varying manual axes
    of ``tree`` and ``refs``.

    Under ``jax.shard_map`` (``check_vma=True``) a loop carry must keep
    one type: a constant column (an IV word, a zero) is unvarying while
    anything computed from a shard's rows varies over the mesh axis, and
    the loop refuses the mix.  A constant joins the others by adding a
    zero derived from an array that already varies (``x & 0``, which XLA
    and Mosaic both fold away) — ``jax.lax.pcast`` would say the same but
    has no Pallas TPU lowering, and these loops run inside the leaf
    kernel too.  All arrays share one lane shape.  Outside ``shard_map``
    every set is empty and this is the identity.
    """
    leaves, treedef = jax.tree.flatten(tree)
    everyone = (*refs, *leaves)
    vma = _vma_of(*everyone)
    if not vma:
        return tree
    donor = next(x for x in everyone if jax.typeof(x).vma == vma)
    zero = donor & jnp.zeros((), donor.dtype)
    return jax.tree.unflatten(treedef, [
        x if jax.typeof(x).vma == vma else x + zero.astype(x.dtype)
        for x in leaves])


def _compress_cols(cv, m, counter_lo, counter_hi, block_len, flags):
    """One BLAKE3 compression, vectorized over lanes.

    ``cv``: list of 8 u32 arrays; ``m``: list of 16 u32 arrays; the scalars
    are u32 arrays of the same lane shape.  Columns stay as separate SSA
    values so XLA fuses the whole round structure without scatter ops.
    Returns the 8 output chaining-value columns.
    """
    iv = [jnp.broadcast_to(jnp.uint32(_IV_NP[i]), counter_lo.shape)
          for i in range(4)]
    state = [c + jnp.uint32(0) for c in cv] + iv + [counter_lo, counter_hi,
                                                    block_len, flags]
    m = [w + jnp.uint32(0) for w in m]
    state, m = _vary_like((state, m))  # one carry type under shard_map

    def round_body(_, carry):
        state, m = list(carry[0]), list(carry[1])
        for i, (a, b, c, d) in enumerate(G_SCHEDULE):
            mx, my = m[2 * i], m[2 * i + 1]
            state[a] = _add(_add(state[a], state[b]), mx)
            state[d] = _rotr(_xor(state[d], state[a]), 16)
            state[c] = _add(state[c], state[d])
            state[b] = _rotr(_xor(state[b], state[c]), 12)
            state[a] = _add(_add(state[a], state[b]), my)
            state[d] = _rotr(_xor(state[d], state[a]), 8)
            state[c] = _add(state[c], state[d])
            state[b] = _rotr(_xor(state[b], state[c]), 7)
        # permuting after the final round too is harmless (m is dropped);
        # keeping it unconditional lets the 7 rounds share one loop body
        return tuple(state), tuple(m[p] for p in MSG_PERMUTATION)

    state, _ = jax.lax.fori_loop(0, 7, round_body, (tuple(state), tuple(m)))
    return [_xor(state[i], state[i + 8]) for i in range(8)]


def _bytes_to_words(buf: jnp.ndarray) -> jnp.ndarray:
    """(..., 4k) u8 -> (..., k) u32 little-endian."""
    b = buf.reshape(*buf.shape[:-1], -1, 4).astype(jnp.uint32)
    return (b[..., 0] | (b[..., 1] << jnp.uint32(8))
            | (b[..., 2] << jnp.uint32(16)) | (b[..., 3] << jnp.uint32(24)))


@functools.partial(jax.jit, static_argnames=("L", "pallas",
                                             "pallas_interpret"))
def digest_padded(buf: jnp.ndarray, lens: jnp.ndarray, *, L: int,
                  pallas: bool = False,
                  pallas_interpret: bool = False) -> jnp.ndarray:
    """Digest a zero-padded batch.

    ``buf``: (B, L*1024) u8; ``lens``: (B,) true byte lengths (i32).
    Returns (B, 8) u32 root chaining values (little-endian digest words).

    The 16-block leaf scan runs as a ``fori_loop`` (compile-time: one
    compression in the graph, not 16); the single-chunk ROOT variant is
    produced by stashing the last block's inputs during the scan and
    recompressing once over B lanes afterwards, instead of running a second
    full scan.  The wide tree levels are unrolled, the narrow ones share
    one loop (:func:`tree_reduce_groups`), and the PARENT|ROOT compression
    is computed only for pair 0, the only pair that can ever finalize the
    root.

    ``pallas=True`` swaps the leaf scan for the VMEM-resident Mosaic
    kernel (bit-identical; callers gate on
    :func:`pallas_digest_available`, which parity-checks on the live
    runtime).  The tree reduction stays in XLA — it touches 1/16 of the
    leaf traffic.
    """
    B = buf.shape[0]
    # tolerate junk beyond each row's true length (e.g. buffers gathered
    # from a resident stream): BLAKE3 pads partial blocks with zeros
    lens = lens.astype(jnp.int32)
    buf = jnp.where(
        jnp.arange(buf.shape[1], dtype=jnp.int32)[None, :] < lens[:, None],
        buf, jnp.uint8(0))
    words = _bytes_to_words(buf.reshape(B, L, MAX_LEAVES_PER_CHUNK, BLOCK_LEN))
    lanes = B * L
    words_flat = words.reshape(lanes, MAX_LEAVES_PER_CHUNK, 16)
    n_chunks = jnp.maximum(1, -(-lens // CHUNK_LEN))  # (B,)
    chunk_idx = jnp.arange(L, dtype=jnp.int32)
    chunk_bytes = jnp.clip(lens[:, None] - chunk_idx[None, :] * CHUNK_LEN,
                           0, CHUNK_LEN)  # (B, L)
    n_blocks = jnp.maximum(1, -(-chunk_bytes // BLOCK_LEN))
    last_block_len = (chunk_bytes - (n_blocks - 1) * BLOCK_LEN).astype(jnp.uint32)
    is_single = (n_chunks == 1)

    # --- leaf scan: fori_loop over the 16 blocks, lanes = (B*L,) -----------
    counter_lo = jnp.broadcast_to(chunk_idx[None, :].astype(jnp.uint32),
                                  (B, L)).reshape(-1)
    counter_hi = jnp.zeros(lanes, dtype=jnp.uint32)
    nb = n_blocks.reshape(-1)
    lbl = last_block_len.reshape(-1)
    zeros = jnp.zeros(lanes, dtype=jnp.uint32)

    if pallas:
        with jax.named_scope("blake3_leaf_scan"):
            cv_mat, cvp_mat = _leaf_scan_pallas(
                words_flat, nb, lbl, counter_lo, interpret=pallas_interpret)
        leaf_cv = [cv_mat[:, i].reshape(B, L) for i in range(8)]
        # single-chunk ROOT recompute from the penultimate CV + the last
        # block of chunk 0, rebuilt here (B lanes — negligible)
        nb0 = n_blocks[:, 0]
        m0 = jnp.take_along_axis(
            words[:, 0], (nb0 - 1)[:, None, None], axis=1)[:, 0]  # (B, 16)
        lane0 = jnp.arange(B, dtype=jnp.int32) * L
        blen0 = last_block_len[:, 0]
        flags0 = (jnp.where(nb0 == 1, jnp.uint32(CHUNK_START), jnp.uint32(0))
                  | jnp.uint32(CHUNK_END))
        root_single = _compress_cols(
            [cvp_mat[lane0, i] for i in range(8)],
            [m0[:, w] for w in range(16)],
            jnp.zeros(B, dtype=jnp.uint32), jnp.zeros(B, dtype=jnp.uint32),
            blen0, flags0 | jnp.uint32(ROOT))
    else:
        iv_cols = [jnp.broadcast_to(jnp.uint32(_IV_NP[i]), (lanes,)) + zeros
                   for i in range(8)]

        def leaf_body(blk, carry):
            cv, cv_last_in, m_last, blen_last, flags_last = carry
            mslab = jax.lax.dynamic_index_in_dim(words_flat, blk, axis=1,
                                                 keepdims=False)  # (lanes, 16)
            m = [mslab[:, w] for w in range(16)]
            active = blk < nb
            is_last = blk == nb - 1
            flags = jnp.where(blk == 0, jnp.uint32(CHUNK_START),
                              jnp.uint32(0))
            flags = jnp.where(is_last, flags | jnp.uint32(CHUNK_END), flags)
            blen = jnp.where(is_last, lbl, jnp.uint32(BLOCK_LEN))
            # stash the *inputs* of each chunk's final compression for the
            # single-chunk ROOT recompute after the loop
            cv_last_in = [jnp.where(is_last, c, s)
                          for c, s in zip(cv, cv_last_in)]
            m_last = [jnp.where(is_last, mw, sw)
                      for mw, sw in zip(m, m_last)]
            blen_last = jnp.where(is_last, blen, blen_last)
            flags_last = jnp.where(is_last, flags, flags_last)
            out = _compress_cols(cv, m, counter_lo, counter_hi, blen, flags)
            cv = [jnp.where(active, o, c) for o, c in zip(out, cv)]
            return cv, cv_last_in, m_last, blen_last, flags_last

        init = _vary_like(
            (iv_cols, list(iv_cols), [zeros] * 16, zeros, zeros),
            nb, lbl, counter_lo)
        # the scope names the loop's operations in a device trace
        with jax.named_scope("blake3_leaf_scan"):
            cv, cv_last_in, m_last, blen_last, flags_last = \
                jax.lax.fori_loop(0, MAX_LEAVES_PER_CHUNK, leaf_body, init)
        leaf_cv = [c.reshape(B, L) for c in cv]

        # single-chunk roots: recompress chunk 0's final block, ROOT set
        def chunk0(col):
            return col.reshape(B, L)[:, 0]

        root_single = _compress_cols(
            [chunk0(c) for c in cv_last_in], [chunk0(mw) for mw in m_last],
            jnp.zeros(B, dtype=jnp.uint32), jnp.zeros(B, dtype=jnp.uint32),
            chunk0(blen_last), chunk0(flags_last) | jnp.uint32(ROOT))

    # --- tree reduction: pair-merge, unpaired node rides up ----------------
    root_cv = [jnp.where(is_single, rs, jnp.uint32(0))
               for rs in root_single]
    return tree_reduce_cvs(leaf_cv, n_chunks, root_cv)


# A tier's levels from 1/64 of its widest span down run as ONE loop over
# a fixed width (at most six levels stay unrolled).  A level in the graph
# is a compression of its own to trace, lower and compile (~0.45 s of
# compile each on a v5e's compiler), and the narrow levels are most of
# them and next to none of the work: the loop recomputes at its full
# width what the unrolled form would halve, ~6 % more tree lanes.
_TAIL_SHARE = 64


def _merge_level(cvs, counts, root_cv):
    """One tree level over 8 (B, cur) columns: pairs (2i, 2i+1) merge
    into slot i where both exist in the row, an unpaired node rides up.
    Returns the (B, ceil(cur / 2)) columns, the new counts and roots."""
    B, cur = cvs[0].shape
    Pn = cur // 2
    left = [c[:, 0:2 * Pn:2] for c in cvs]   # (B, Pn)
    right = [c[:, 1:2 * Pn:2] for c in cvs]
    # one compression a level: the B * Pn pair merges and, behind
    # them, pair 0 of every row again under PARENT | ROOT (the root
    # merge, count 2 -> 1, always happens at pair 0).  A compression
    # of their own for those B lanes doubled the loops a digest
    # program carries, and with them what a first backup traces and
    # compiles.
    lanes_p = B * Pn
    m = [jnp.concatenate([x.reshape(-1), x[:, 0]]) for x in left + right]
    zero = jnp.zeros(lanes_p + B, dtype=jnp.uint32)
    ivc = [jnp.broadcast_to(jnp.uint32(_IV_NP[i]), (lanes_p + B,))
           for i in range(8)]
    bl = jnp.full(lanes_p + B, BLOCK_LEN, dtype=jnp.uint32)
    flags = jnp.concatenate([
        jnp.full(lanes_p, PARENT, dtype=jnp.uint32),
        jnp.full(B, PARENT | ROOT, dtype=jnp.uint32)])
    both = _compress_cols(ivc, m, zero, zero, bl, flags)
    merged = [x[:lanes_p].reshape(B, Pn) for x in both]
    merged_root0 = [x[lanes_p:] for x in both]
    pair_idx = jnp.arange(Pn, dtype=jnp.int32)
    pair_merges = (2 * pair_idx[None, :] + 1) < counts[:, None]  # (B, Pn)
    nxt = []
    for ci in range(8):
        col = jnp.where(pair_merges, merged[ci], left[ci])
        if cur % 2:
            col = jnp.concatenate([col, cvs[ci][:, -1:]], axis=1)
        nxt.append(col)
    is_root_merge = (counts == 2)
    root_cv = [jnp.where(is_root_merge, mr0, rc)
               for mr0, rc in zip(merged_root0, root_cv)]
    counts = jnp.where(counts > 1, (counts + 1) // 2, counts)
    return nxt, counts, root_cv


def _merge_tail(cvs, counts, root_cv):
    """Every remaining level of 8 (B, cur) columns in one loop: the
    columns are padded to a power of two and each pass merges the whole
    width (slots past a row's count hold junk that no later pass reads:
    a pair merges only under its row's count)."""
    cur = cvs[0].shape[1]
    levels = max(1, (cur - 1).bit_length())
    width = 1 << levels
    cvs = [jnp.pad(c, ((0, 0), (0, width - cur))) for c in cvs]

    def body(_, carry):
        nxt, counts, root_cv = _merge_level(*carry)
        return ([jnp.concatenate([c, jnp.zeros_like(c)], axis=1)
                 for c in nxt], counts, root_cv)

    _, _, root_cv = jax.lax.fori_loop(
        0, levels, body, _vary_like((cvs, counts, list(root_cv))))
    return root_cv


@jax.named_scope("blake3_tree_reduce")
def tree_reduce_groups(groups):
    """BLAKE3 tree reductions of several groups of inputs in the levels
    of the widest: ``groups`` is a list of ``(leaf_cv, counts, root_cv)``
    as :func:`tree_reduce_cvs` takes them, of any row counts and spans.
    The widest group is merged level by level; a narrower one joins its
    rows when the width has come down to its span (rows are independent:
    every mask is the row's own count), so the tiers of a leaf pool cost
    the levels of one tier.  Returns one (B_i, 8) array a group, in the
    order given.
    """
    order = sorted(range(len(groups)),
                   key=lambda i: -groups[i][0][0].shape[1])
    waiting = [groups[i] for i in order]
    rows = [g[1].shape[0] for g in waiting]
    cvs, counts, root_cv = (list(waiting[0][0]), waiting[0][1],
                            list(waiting[0][2]))
    waiting = waiting[1:]
    tail = cvs[0].shape[1] // _TAIL_SHARE
    while True:
        cur = cvs[0].shape[1]
        while waiting and waiting[0][0][0].shape[1] >= cur:
            g_cvs, g_counts, g_root = waiting.pop(0)
            span = g_cvs[0].shape[1]
            cvs = [jnp.concatenate(
                [jnp.pad(a, ((0, 0), (0, span - cur))), b])
                for a, b in zip(cvs, g_cvs)]
            counts = jnp.concatenate([counts, g_counts])
            root_cv = [jnp.concatenate([a, b])
                       for a, b in zip(root_cv, g_root)]
            cur = span
        if cur == 1:
            break
        if not waiting and 2 < cur <= tail:
            root_cv = _merge_tail(cvs, counts, root_cv)
            break
        cvs, counts, root_cv = _merge_level(cvs, counts, root_cv)
    out = jnp.stack(root_cv, axis=1)  # (sum of rows, 8) u32
    parts, at = [None] * len(groups), 0
    for i, n in zip(order, rows):
        parts[i] = out[at:at + n]
        at += n
    return parts


def tree_reduce_cvs(leaf_cv, counts, root_cv):
    """BLAKE3 tree reduction over per-input leaf chaining values.

    ``leaf_cv``: list of 8 (B, L) u32 columns; ``counts``: (B,) true leaf
    counts (>=1); ``root_cv``: list of 8 (B,) columns pre-seeded with the
    single-leaf roots (used where counts == 1).  Pair-merges level by
    level; an unpaired rightmost node rides up unchanged, reproducing
    BLAKE3's largest-power-of-two-left split exactly.  Returns (B, 8).
    """
    return tree_reduce_groups([(leaf_cv, counts, root_cv)])[0]


# ---------------------------------------------------------------------------
# Pallas leaf kernel: the 16-block leaf scan entirely in VMEM.
#
# The XLA leaf scan materializes every intermediate state column in HBM
# (112 G-steps x 6 ops x 4 B per lane per block ~= 26 GB of traffic for a
# 256 MiB batch — measured ~62 ms, HBM-bound at ~8 GiB/s of payload).
# Here each grid step stages 1024 leaves (1 MiB of message words) into
# VMEM, runs all 16 compressions with the state resident, and writes back
# only the output + penultimate chaining values (64 KiB) — payload read
# once, ~10x less traffic.
# ---------------------------------------------------------------------------

_LEAF_LANES = 4096  # leaves per grid step: (32, 128) vector shape
_LROWS = _LEAF_LANES // 128


def _leaf_scan_kernel(nb_ref, lbl_ref, cidx_ref, w_ref, cv_ref, cvp_ref):
    """One grid step: (256, 1024) u32 word-major leaf messages ->
    (64, 128) output CVs + penultimate CVs (single-chunk ROOT recompute).

    State words live as (8, 128) tiles covering the step's 1024 lanes;
    the whole 16-block scan runs without touching HBM.  Mirrors the
    masking of :func:`digest_padded`'s leaf loop exactly.
    """
    nb = nb_ref[0]          # (R, 128) i32: blocks per lane
    lbl = lbl_ref[0]        # (R, 128) u32: last-block length
    counter = cidx_ref[0].astype(jnp.uint32)  # (R, 128): chunk index in row
    zero = jnp.zeros((_LROWS, 128), dtype=jnp.uint32)
    iv_cols = [jnp.broadcast_to(jnp.uint32(_IV_NP[i]), (_LROWS, 128)) + zero
               for i in range(8)]

    def body(blk, carry):
        cv, cv_pre = carry
        # words arrive pre-tiled as (256, R, 128): word bw of the step's
        # lanes IS an (R, 128) tile (a flat row would relayout across
        # lanes on every read); R=32 rows give each vector op 4096 lanes,
        # hiding the G chain's op latency (R=8 measured 2x slower)
        m = [w_ref[0, blk * 16 + w] for w in range(16)]
        active = blk < nb
        is_last = blk == nb - 1
        flags = jnp.where(blk == 0, jnp.uint32(CHUNK_START), jnp.uint32(0))
        flags = jnp.where(is_last, flags | jnp.uint32(CHUNK_END), flags)
        blen = jnp.where(is_last, lbl, jnp.uint32(BLOCK_LEN))
        cv_pre = [jnp.where(is_last, c, p) for c, p in zip(cv, cv_pre)]
        out = _compress_cols(cv, m, counter, zero, blen, flags)
        cv = [jnp.where(active, o, c) for o, c in zip(out, cv)]
        return cv, cv_pre

    cv, cv_pre = jax.lax.fori_loop(
        0, MAX_LEAVES_PER_CHUNK, body,
        _vary_like((iv_cols, list(iv_cols)), nb, lbl, counter))
    for i in range(8):
        cv_ref[0, i * _LROWS:(i + 1) * _LROWS, :] = cv[i]
        cvp_ref[0, i * _LROWS:(i + 1) * _LROWS, :] = cv_pre[i]


@functools.lru_cache(maxsize=1)
def pallas_digest_available() -> bool:
    """True when the Pallas leaf kernel is selected: on a TPU, after it
    lowered and matched the XLA path bit for bit.

    On the TPU a kernel that does not lower, or does not match, raises —
    with the compiler's own message — instead of handing the work to a
    slower path behind a green run.  Off the TPU (the CPU test
    configuration) False: the XLA leaf scan is the only form there.
    """
    if jax.devices()[0].platform != "tpu":
        return False
    rng = np.random.default_rng(3)
    # B*L = 12288 lanes = 3 grid steps (> _LEAF_LANES): the probe must
    # exercise the multi-grid-step index map on the live runtime — a
    # g>1-specific mis-lowering would otherwise pass a g=1 probe and
    # silently corrupt digests in production tiles.
    B = 1536
    buf = rng.integers(0, 256, (B, 8 * CHUNK_LEN), dtype=np.uint8)
    lens = np.resize(
        np.array([0, 1, 64, 65, 1024, 1025, 4000, 8192], np.int32), B)
    a = np.asarray(digest_padded(jnp.asarray(buf), jnp.asarray(lens),
                                 L=8, pallas=False))
    b = np.asarray(digest_padded(jnp.asarray(buf), jnp.asarray(lens),
                                 L=8, pallas=True))
    assert B * 8 > _LEAF_LANES  # keep the probe multi-step if consts move
    if not (a == b).all():
        raise RuntimeError(
            "Pallas BLAKE3 leaf kernel disagrees with the XLA leaf scan on "
            f"{int((a != b).any(axis=1).sum())} of {B} probe digests")
    return True


def _leaf_scan_pallas(words: jnp.ndarray, n_blocks: jnp.ndarray,
                      last_len: jnp.ndarray, chunk_idx: jnp.ndarray,
                      interpret: bool = False):
    """(lanes, 16, 16) u32 leaf words -> (lanes, 8) cv, (lanes, 8) cv_pre.

    ``interpret=True`` runs the kernel body in the pallas interpreter
    (CPU tests prove the logic; the Mosaic lowering itself is proven by
    :func:`pallas_digest_available`'s runtime parity gate).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lanes = words.shape[0]
    g = -(-lanes // _LEAF_LANES)
    pad = g * _LEAF_LANES - lanes

    def pad_to(x, fill=0):
        if pad:
            x = jnp.concatenate(
                [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)])
        return x

    # word-major per grid step, each word an (R, 128) lane tile:
    # (g, 256, R, 128), dim 1 = block*16 + word
    wt = pad_to(words.reshape(lanes, 256)).reshape(
        g, _LROWS, 128, 256).transpose(0, 3, 1, 2)
    nb = pad_to(n_blocks.astype(jnp.int32)).reshape(g, _LROWS, 128)
    lbl = pad_to(last_len.astype(jnp.uint32)).reshape(g, _LROWS, 128)
    cidx = pad_to(chunk_idx.astype(jnp.int32)).reshape(g, _LROWS, 128)
    cv, cvp = pl.pallas_call(
        _leaf_scan_kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((1, _LROWS, 128), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _LROWS, 128), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _LROWS, 128), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 256, _LROWS, 128), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 8 * _LROWS, 128), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8 * _LROWS, 128), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        # under shard_map the outputs vary over the inputs' mesh axes
        out_shape=[jax.ShapeDtypeStruct(
            (g, 8 * _LROWS, 128), jnp.uint32,
            vma=_vma_of(nb, lbl, cidx, wt))] * 2,
        interpret=interpret,
        name="blake3_leaf_scan",
    )(nb, lbl, cidx, wt)
    # (g, 8 words, R, 128) -> (lanes, 8)
    def unpack(x):
        x = x.reshape(g, 8, _LROWS, 128).transpose(0, 2, 3, 1)
        return x.reshape(g * _LEAF_LANES, 8)[:lanes]

    return unpack(cv), unpack(cvp)


def _root_cv_to_digests(root_cv: np.ndarray) -> list:
    out = np.ascontiguousarray(root_cv.astype("<u4")).tobytes()
    return [out[i * 32:(i + 1) * 32] for i in range(root_cv.shape[0])]


def _leaf_bucket(n_bytes: int) -> int:
    """Smallest configured (B, L) leaf bucket holding ``n_bytes``."""
    n_chunks = max(1, -(-n_bytes // CHUNK_LEN))
    for b in defaults.BLAKE3_LEAF_BUCKETS:
        if n_chunks <= b:
            return b
    return n_chunks  # oversized input: exact-size compile


def _batch_bucket(n: int) -> int:
    """Batch sizes are padded to powers of two (>=8) to bound recompiles."""
    b = 8
    while b < n:
        b *= 2
    return b


def bucketed_batches(datas):
    """Group inputs by leaf bucket; yields (indices, buf, lens, L)."""
    groups = {}
    for i, d in enumerate(datas):
        groups.setdefault(_leaf_bucket(len(d)), []).append(i)
    for L, idxs in sorted(groups.items()):
        B = _batch_bucket(len(idxs))
        buf = np.zeros((B, L * CHUNK_LEN), dtype=np.uint8)
        lens = np.zeros(B, dtype=np.int32)
        for row, i in enumerate(idxs):
            d = datas[i]
            buf[row, :len(d)] = np.frombuffer(d, dtype=np.uint8)
            lens[row] = len(d)
        yield idxs, buf, lens, L


def blake3_many_tpu(datas) -> list:
    """Batched digests on the device; bit-exact vs
    :func:`backuwup_tpu.ops.blake3_cpu.blake3_hash`."""
    datas = list(datas)
    out = [None] * len(datas)
    for idxs, buf, lens, L in bucketed_batches(datas):
        obs_profile.device_upload(buf.nbytes + lens.nbytes)
        root = digest_padded(jnp.asarray(buf), jnp.asarray(lens), L=L)
        obs_profile.device_wait()
        root = np.asarray(root)
        digests = _root_cv_to_digests(root)
        for row, i in enumerate(idxs):
            out[i] = digests[row]
    return out
