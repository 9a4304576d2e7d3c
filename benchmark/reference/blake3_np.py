"""The benchmark's own copy of the host BLAKE3 (copied from
``backuwup_tpu/ops/blake3_cpu.py`` in PR 24; imports nothing of the program).

BLAKE3 on the host: pure-Python spec reference + numpy batch engine.

The reference fingerprints every chunk and tree blob with BLAKE3
(``client/src/backup/filesystem/dir_packer.rs:286,321,353``) via the SIMD
``blake3`` crate.  Here BLAKE3 is implemented from the public specification
(hash mode only, 32-byte digests):

* :func:`blake3_hash` — scalar pure-Python implementation, the readability
  oracle; used for tiny inputs and tests.
* :class:`Blake3Numpy` — batch engine vectorized over many inputs at once
  with numpy uint32 arrays.  Its masked leaf-scan + pair-merge tree reduction
  is the exact algorithm the TPU kernel (:mod:`.blake3_tpu`) uses, so the two
  are structurally parallel and must agree bit-for-bit.

Tree topology note: BLAKE3 splits the leaves of a subtree so the left side
holds the largest power of two ≤ n leaves.  Bottom-up pair-merging where an
unpaired rightmost node rides up unchanged produces exactly that topology,
which is what both batch engines implement.
"""

from __future__ import annotations

import struct

import numpy as np

M32 = 0xFFFFFFFF
IV = (0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
      0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19)
MSG_PERMUTATION = (2, 6, 3, 10, 7, 0, 4, 13, 1, 11, 12, 5, 9, 14, 15, 8)
BLOCK_LEN = 64
CHUNK_LEN = 1024
MAX_LEAVES_PER_CHUNK = 16  # 64-byte blocks per 1 KiB chunk

CHUNK_START = 1 << 0
CHUNK_END = 1 << 1
PARENT = 1 << 2
ROOT = 1 << 3

# Column/diagonal mixing schedule: (a, b, c, d) state indices for the 8 G
# applications of one round, in order; message words 2i, 2i+1 feed G number i.
G_SCHEDULE = (
    (0, 4, 8, 12), (1, 5, 9, 13), (2, 6, 10, 14), (3, 7, 11, 15),
    (0, 5, 10, 15), (1, 6, 11, 12), (2, 7, 8, 13), (3, 4, 9, 14),
)


def _rotr(x: int, n: int) -> int:
    return ((x >> n) | (x << (32 - n))) & M32


def compress(cv, block_words, counter, block_len, flags):
    """One BLAKE3 compression; returns the full 16-word output state."""
    state = list(cv) + [IV[0], IV[1], IV[2], IV[3],
                        counter & M32, (counter >> 32) & M32, block_len, flags]
    m = list(block_words)
    for r in range(7):
        for i, (a, b, c, d) in enumerate(G_SCHEDULE):
            mx, my = m[2 * i], m[2 * i + 1]
            state[a] = (state[a] + state[b] + mx) & M32
            state[d] = _rotr(state[d] ^ state[a], 16)
            state[c] = (state[c] + state[d]) & M32
            state[b] = _rotr(state[b] ^ state[c], 12)
            state[a] = (state[a] + state[b] + my) & M32
            state[d] = _rotr(state[d] ^ state[a], 8)
            state[c] = (state[c] + state[d]) & M32
            state[b] = _rotr(state[b] ^ state[c], 7)
        if r < 6:
            m = [m[p] for p in MSG_PERMUTATION]
    out = [(state[i] ^ state[i + 8]) & M32 for i in range(8)]
    out += [(state[i + 8] ^ cv[i]) & M32 for i in range(8)]
    return out


def _block_words(block: bytes):
    block = block + b"\x00" * (BLOCK_LEN - len(block))
    return struct.unpack("<16I", block)


def _chunk_cv(data: bytes, counter: int, root: bool):
    """Chaining value of one ≤1024-byte chunk (ROOT flagged if requested)."""
    cv = IV
    n_blocks = max(1, (len(data) + BLOCK_LEN - 1) // BLOCK_LEN)
    for i in range(n_blocks):
        block = data[i * BLOCK_LEN:(i + 1) * BLOCK_LEN]
        flags = 0
        if i == 0:
            flags |= CHUNK_START
        if i == n_blocks - 1:
            flags |= CHUNK_END
            if root:
                flags |= ROOT
        out = compress(cv, _block_words(block), counter,
                       len(block) if data else 0, flags)
        cv = out[:8]
    return cv


def _parent_cv(left, right, root: bool):
    out = compress(IV, tuple(left) + tuple(right), 0, BLOCK_LEN,
                   PARENT | (ROOT if root else 0))
    return out[:8]


def blake3_hash(data: bytes) -> bytes:
    """32-byte BLAKE3 digest (hash mode), scalar reference implementation."""
    n_chunks = max(1, (len(data) + CHUNK_LEN - 1) // CHUNK_LEN)
    if n_chunks == 1:
        return struct.pack("<8I", *_chunk_cv(data, 0, root=True))
    cvs = [_chunk_cv(data[i * CHUNK_LEN:(i + 1) * CHUNK_LEN], i, root=False)
           for i in range(n_chunks)]
    while len(cvs) > 2:
        nxt = [_parent_cv(cvs[i], cvs[i + 1], root=False)
               for i in range(0, len(cvs) - 1, 2)]
        if len(cvs) % 2:
            nxt.append(cvs[-1])
        cvs = nxt
    return struct.pack("<8I", *_parent_cv(cvs[0], cvs[1], root=True))


# --------------------------------------------------------------------------
# numpy batch engine
# --------------------------------------------------------------------------

_IV_NP = np.array(IV, dtype=np.uint32)
_PERM_NP = np.array(MSG_PERMUTATION, dtype=np.int64)


def _rotr_np(x, n):
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


def compress_np(cv, m, counter_lo, counter_hi, block_len, flags):
    """Vectorized compression over a leading batch axis.

    cv: (B, 8) u32; m: (B, 16) u32; counter_lo/hi, block_len, flags: (B,) u32.
    Returns the (B, 8) output chaining value.
    """
    B = cv.shape[0]
    v = np.empty((B, 16), dtype=np.uint32)
    v[:, :8] = cv
    v[:, 8:12] = _IV_NP[:4]
    v[:, 12] = counter_lo
    v[:, 13] = counter_hi
    v[:, 14] = block_len
    v[:, 15] = flags
    m = np.asarray(m, dtype=np.uint32)
    with np.errstate(over="ignore"):
        for r in range(7):
            for i, (a, b, c, d) in enumerate(G_SCHEDULE):
                mx, my = m[:, 2 * i], m[:, 2 * i + 1]
                v[:, a] += v[:, b] + mx
                v[:, d] = _rotr_np(v[:, d] ^ v[:, a], 16)
                v[:, c] += v[:, d]
                v[:, b] = _rotr_np(v[:, b] ^ v[:, c], 12)
                v[:, a] += v[:, b] + my
                v[:, d] = _rotr_np(v[:, d] ^ v[:, a], 8)
                v[:, c] += v[:, d]
                v[:, b] = _rotr_np(v[:, b] ^ v[:, c], 7)
            if r < 6:
                m = m[:, _PERM_NP]
    return v[:, :8] ^ v[:, 8:]


class Blake3Numpy:
    """Batched BLAKE3 over many independent byte strings.

    All inputs of a batch are padded to the same number of 1 KiB chunks; per
    input, invalid chunks/blocks are masked out of the scan/merge so digests
    are exact for every length, including 0.
    """

    def digest_batch(self, datas) -> list:
        if not datas:
            return []
        lens = np.array([len(d) for d in datas], dtype=np.int64)
        B = len(datas)
        n_chunks = np.maximum(1, -(-lens // CHUNK_LEN))  # ceil, min 1
        L = int(n_chunks.max())
        # Byte tensor (B, L*1024), zero padded.
        buf = np.zeros((B, L * CHUNK_LEN), dtype=np.uint8)
        for i, d in enumerate(datas):
            buf[i, :len(d)] = np.frombuffer(bytes(d), dtype=np.uint8)
        return self._digest_padded(buf, lens, L)

    def _digest_padded(self, buf: np.ndarray, lens: np.ndarray, L: int) -> list:
        """buf: (B, L*1024) u8 zero-padded; lens: true byte lengths."""
        B = buf.shape[0]
        words = buf.reshape(B, L, MAX_LEAVES_PER_CHUNK, BLOCK_LEN) \
                   .view(np.uint32).reshape(B, L, MAX_LEAVES_PER_CHUNK, 16)
        # Per-chunk block counts / last-block lengths.
        n_chunks = np.maximum(1, -(-lens // CHUNK_LEN))
        chunk_idx = np.arange(L)
        chunk_valid = chunk_idx[None, :] < n_chunks[:, None]  # (B, L)
        # Bytes in each chunk (0..1024); final chunk may be partial, and a
        # zero-length input still has one (empty) chunk.
        chunk_bytes = np.clip(lens[:, None] - chunk_idx[None, :] * CHUNK_LEN,
                              0, CHUNK_LEN)
        n_blocks = np.maximum(1, -(-chunk_bytes // BLOCK_LEN))  # (B, L)
        last_block_len = (chunk_bytes - (n_blocks - 1) * BLOCK_LEN).astype(np.uint32)

        is_single_chunk = (n_chunks == 1)

        # --- leaf scan: 16 sequential blocks, batched over (B, L) ----------
        cv = np.broadcast_to(_IV_NP, (B * L, 8)).copy()
        cv_root = cv.copy()  # variant with ROOT on the last block (single-chunk roots)
        counter_lo = np.broadcast_to(chunk_idx[None, :].astype(np.uint32),
                                     (B, L)).reshape(-1)
        counter_hi = np.zeros(B * L, dtype=np.uint32)
        nb = n_blocks.reshape(-1)
        lbl = last_block_len.reshape(-1)
        for blk in range(MAX_LEAVES_PER_CHUNK):
            m = words[:, :, blk, :].reshape(B * L, 16)
            active = blk < nb
            is_last = blk == nb - 1
            flags = np.where(blk == 0, CHUNK_START, 0).astype(np.uint32)
            flags = np.where(is_last, flags | CHUNK_END, flags)
            blen = np.where(is_last, lbl, BLOCK_LEN).astype(np.uint32)
            out = compress_np(cv, m, counter_lo, counter_hi, blen, flags)
            cv = np.where(active[:, None], out, cv)
            out_r = compress_np(cv_root, m, counter_lo, counter_hi, blen,
                                np.where(is_last, flags | ROOT, flags).astype(np.uint32))
            cv_root = np.where(active[:, None], out_r, cv_root)
        leaf_cv = cv.reshape(B, L, 8)
        leaf_cv_root = cv_root.reshape(B, L, 8)

        # --- tree reduction: pair-merge, odd node rides up -----------------
        root_cv = np.where(is_single_chunk[:, None], leaf_cv_root[:, 0], 0)
        cvs = leaf_cv
        counts = n_chunks.copy()
        while cvs.shape[1] > 1:
            P = cvs.shape[1] // 2
            left = cvs[:, 0:2 * P:2]  # (B, P, 8)
            right = cvs[:, 1:2 * P:2]
            m = np.concatenate([left, right], axis=-1).reshape(B * P, 16)
            zeros = np.zeros(B * P, dtype=np.uint32)
            merged = compress_np(
                np.broadcast_to(_IV_NP, (B * P, 8)).copy(), m, zeros, zeros,
                np.full(B * P, BLOCK_LEN, dtype=np.uint32),
                np.full(B * P, PARENT, dtype=np.uint32)).reshape(B, P, 8)
            merged_root = compress_np(
                np.broadcast_to(_IV_NP, (B * P, 8)).copy(), m, zeros, zeros,
                np.full(B * P, BLOCK_LEN, dtype=np.uint32),
                np.full(B * P, PARENT | ROOT, dtype=np.uint32)).reshape(B, P, 8)
            # pair j merges iff 2j+1 < count; unpaired node rides up.
            pair_idx = np.arange(P)
            pair_merges = (2 * pair_idx[None, :] + 1) < counts[:, None]  # (B, P)
            nxt_len = (cvs.shape[1] + 1) // 2
            nxt = np.zeros((B, nxt_len, 8), dtype=np.uint32)
            nxt[:, :P] = np.where(pair_merges[:, :, None], merged, left)
            # odd leftover at the old level rides up into the last slot
            if cvs.shape[1] % 2:
                nxt[:, -1] = cvs[:, -1]
            else:
                # even storage width: a ride-up only happens per-item when
                # count is odd and its last valid node sits at index count-1;
                # np.where above already kept `left` for non-merging pairs,
                # which is exactly the ride-up when count-1 is even.
                pass
            # the root is produced by the merge that takes count 2 -> 1
            is_root_merge = (counts == 2)
            root_cv = np.where(is_root_merge[:, None], merged_root[:, 0], root_cv)
            cvs = nxt
            counts = np.where(counts > 1, (counts + 1) // 2, counts)

        out_bytes = root_cv.astype("<u4").tobytes()
        return [out_bytes[i * 32:(i + 1) * 32] for i in range(B)]


_BATCH = Blake3Numpy()


def blake3_many(datas) -> list:
    """Batched digests via the numpy engine (bit-exact vs :func:`blake3_hash`)."""
    return _BATCH.digest_batch(datas)
