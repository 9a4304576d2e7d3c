/* Native single-thread dedup pipeline: windowed-gear CDC + BLAKE3.
 *
 * This is the honest CPU baseline the device pipeline is measured against
 * (BASELINE.md: ">=10x CPU single-thread chunk+hash throughput"), playing
 * the role the SIMD `fastcdc` + `blake3` crates play in the reference
 * client (dir_packer.rs:246-311).  Semantics are normative per
 * backuwup_tpu/ops/CDC_SPEC.md and bit-identical to ops/cdc_cpu.py /
 * ops/blake3_cpu.py; parity is asserted by tests/test_native.py.
 *
 * BLAKE3 is implemented from the public specification (IV, message
 * permutation, flag values, tree structure); no third-party code.
 */

#include <stddef.h>
#include <stdint.h>
#include <string.h>

/* ---------------------------------------------------------------- gear -- */

#define GEAR_WINDOW 32
static uint32_t GEAR[256];
static int gear_ready = 0;

/* GEAR[b] = fmix32(GEAR_SEED32 + b), spec v2 (ops/gear.py). */
static void gear_init(void) {
    if (gear_ready) return;
    for (int i = 0; i < 256; i++) {
        uint32_t h = 0x6261636BU + (uint32_t)i;
        h ^= h >> 16;
        h *= 0x85EBCA6BU;
        h ^= h >> 13;
        h *= 0xC2B2AE35U;
        h ^= h >> 16;
        GEAR[i] = h;
    }
    gear_ready = 1;
}

/* Next inclusive cut end for the chunk starting at s (select_cuts rules:
 * window 1 = [s+min-1, s+desired-2] under mask_s, window 2 =
 * [s+desired-1, s+max-2] under mask_l, both capped at n-2; else forced at
 * s+max-1 or EOF).  The rolling hash h[i] depends only on bytes
 * [i-31, i], so the scan warms up over the 31 bytes before the first
 * eligible position instead of hashing the skipped min-size prefix. */
static size_t next_cut(const uint8_t *data, size_t n, size_t s,
                       uint64_t min_size, uint64_t desired, uint64_t max_size,
                       uint32_t mask_s, uint32_t mask_l) {
    if (n - s <= min_size) return n - 1;
    size_t start = s + min_size - 1; /* first eligible end position */
    uint32_t h = 0;
    size_t warm = start >= GEAR_WINDOW - 1 ? start - (GEAR_WINDOW - 1) : 0;
    for (size_t i = warm; i < start; i++)
        h = (h << 1) + GEAR[data[i]];
    size_t hi1 = s + desired - 2;
    if (hi1 > n - 2) hi1 = n - 2;
    size_t hi2 = s + max_size - 2;
    if (hi2 > n - 2) hi2 = n - 2;
    for (size_t i = start; i <= hi2; i++) {
        h = (h << 1) + GEAR[data[i]];
        if (i <= hi1) {
            if ((h & mask_s) == 0) return i;
        } else {
            if ((h & mask_l) == 0) return i;
        }
    }
    size_t forced = s + max_size - 1;
    return forced < n - 1 ? forced : n - 1;
}

/* -------------------------------------------------------------- blake3 -- */

#define CHUNK_LEN 1024
#define BLOCK_LEN 64
#define FLAG_CHUNK_START 1u
#define FLAG_CHUNK_END 2u
#define FLAG_PARENT 4u
#define FLAG_ROOT 8u

static const uint32_t B3_IV[8] = {
    0x6A09E667u, 0xBB67AE85u, 0x3C6EF372u, 0xA54FF53Au,
    0x510E527Fu, 0x9B05688Cu, 0x1F83D9ABu, 0x5BE0CD19u};

static const uint8_t B3_PERM[16] = {2, 6,  3, 10, 7, 0,  4, 13,
                                    1, 11, 12, 5, 9, 14, 15, 8};

static inline uint32_t rotr32(uint32_t x, int n) {
    return (x >> n) | (x << (32 - n));
}

#define G(a, b, c, d, mx, my)                \
    do {                                     \
        st[a] = st[a] + st[b] + (mx);        \
        st[d] = rotr32(st[d] ^ st[a], 16);   \
        st[c] = st[c] + st[d];               \
        st[b] = rotr32(st[b] ^ st[c], 12);   \
        st[a] = st[a] + st[b] + (my);        \
        st[d] = rotr32(st[d] ^ st[a], 8);    \
        st[c] = st[c] + st[d];               \
        st[b] = rotr32(st[b] ^ st[c], 7);    \
    } while (0)

static void compress(const uint32_t cv[8], const uint32_t block[16],
                     uint64_t counter, uint32_t block_len, uint32_t flags,
                     uint32_t out[8]) {
    uint32_t st[16];
    uint32_t m[16];
    memcpy(m, block, sizeof(m));
    memcpy(st, cv, 8 * sizeof(uint32_t));
    memcpy(st + 8, B3_IV, 4 * sizeof(uint32_t));
    st[12] = (uint32_t)counter;
    st[13] = (uint32_t)(counter >> 32);
    st[14] = block_len;
    st[15] = flags;
    for (int r = 0;; r++) {
        G(0, 4, 8, 12, m[0], m[1]);
        G(1, 5, 9, 13, m[2], m[3]);
        G(2, 6, 10, 14, m[4], m[5]);
        G(3, 7, 11, 15, m[6], m[7]);
        G(0, 5, 10, 15, m[8], m[9]);
        G(1, 6, 11, 12, m[10], m[11]);
        G(2, 7, 8, 13, m[12], m[13]);
        G(3, 4, 9, 14, m[14], m[15]);
        if (r == 6) break;
        uint32_t p[16];
        for (int i = 0; i < 16; i++) p[i] = m[B3_PERM[i]];
        memcpy(m, p, sizeof(m));
    }
    for (int i = 0; i < 8; i++) out[i] = st[i] ^ st[i + 8];
}

static void load_block(const uint8_t *p, size_t len, uint32_t block[16]) {
    uint8_t buf[BLOCK_LEN];
    const uint8_t *src = p;
    if (len < BLOCK_LEN) {
        memset(buf, 0, sizeof(buf));
        memcpy(buf, p, len);
        src = buf;
    }
    for (int i = 0; i < 16; i++)
        block[i] = (uint32_t)src[4 * i] | ((uint32_t)src[4 * i + 1] << 8) |
                   ((uint32_t)src[4 * i + 2] << 16) |
                   ((uint32_t)src[4 * i + 3] << 24);
}

/* Chaining value of one <=1024-byte leaf chunk. */
static void chunk_cv(const uint8_t *data, size_t len, uint64_t counter,
                     int root, uint32_t cv[8]) {
    size_t nblocks = len ? (len + BLOCK_LEN - 1) / BLOCK_LEN : 1;
    memcpy(cv, B3_IV, 8 * sizeof(uint32_t));
    for (size_t b = 0; b < nblocks; b++) {
        size_t off = b * BLOCK_LEN;
        size_t blen = len - off < BLOCK_LEN ? len - off : BLOCK_LEN;
        if (!len) blen = 0;
        uint32_t block[16];
        load_block(data + off, blen, block);
        uint32_t flags = 0;
        if (b == 0) flags |= FLAG_CHUNK_START;
        if (b == nblocks - 1) {
            flags |= FLAG_CHUNK_END;
            if (root) flags |= FLAG_ROOT;
        }
        uint32_t out[8];
        compress(cv, block, counter, (uint32_t)blen, flags, out);
        memcpy(cv, out, sizeof(out));
    }
}

static void parent_cv(const uint32_t l[8], const uint32_t r[8], int root,
                      uint32_t out[8]) {
    uint32_t block[16];
    memcpy(block, l, 8 * sizeof(uint32_t));
    memcpy(block + 8, r, 8 * sizeof(uint32_t));
    compress(B3_IV, block, 0, BLOCK_LEN,
             FLAG_PARENT | (root ? FLAG_ROOT : 0), out);
}

static uint64_t pow2_below(uint64_t n) { /* largest power of two < n */
    uint64_t p = 1;
    while (p * 2 < n) p *= 2;
    return p;
}

/* Subtree over whole chunks [c0, c0+count); ROOT never set here. */
static void subtree_cv(const uint8_t *data, size_t len, uint64_t c0,
                       uint64_t count, uint32_t cv[8]) {
    if (count == 1) {
        chunk_cv(data, len, c0, 0, cv);
        return;
    }
    uint64_t split = pow2_below(count);
    uint32_t l[8], r[8];
    subtree_cv(data, split * CHUNK_LEN, c0, split, l);
    subtree_cv(data + split * CHUNK_LEN, len - split * CHUNK_LEN, c0 + split,
               count - split, r);
    parent_cv(l, r, 0, cv);
}

void bkw_blake3(const uint8_t *data, size_t len, uint8_t out[32]) {
    uint32_t cv[8];
    uint64_t count = len ? (len + CHUNK_LEN - 1) / CHUNK_LEN : 1;
    if (count == 1) {
        chunk_cv(data, len, 0, 1, cv);
    } else {
        uint64_t split = pow2_below(count);
        uint32_t l[8], r[8];
        subtree_cv(data, split * CHUNK_LEN, 0, split, l);
        subtree_cv(data + split * CHUNK_LEN, len - split * CHUNK_LEN, split,
                   count - split, r);
        parent_cv(l, r, 1, cv);
    }
    for (int i = 0; i < 8; i++) {
        out[4 * i] = (uint8_t)cv[i];
        out[4 * i + 1] = (uint8_t)(cv[i] >> 8);
        out[4 * i + 2] = (uint8_t)(cv[i] >> 16);
        out[4 * i + 3] = (uint8_t)(cv[i] >> 24);
    }
}

/* ------------------------------------------------------------ manifest -- */

/* Chunk only: fills offsets/lengths, returns chunk count (or -1 if cap is
 * too small). */
long bkw_chunk(const uint8_t *data, size_t n, uint64_t min_size,
               uint64_t desired, uint64_t max_size, uint32_t mask_s,
               uint32_t mask_l, uint64_t *offsets, uint64_t *lengths,
               size_t cap) {
    gear_init();
    long k = 0;
    size_t s = 0;
    while (s < n) {
        size_t e = next_cut(data, n, s, min_size, desired, max_size, mask_s,
                            mask_l);
        if ((size_t)k >= cap) return -1;
        offsets[k] = s;
        lengths[k] = e - s + 1;
        k++;
        s = e + 1;
    }
    return k;
}

/* Full single-thread pipeline: chunk + digest every chunk.  digests must
 * hold 32*cap bytes. */
long bkw_manifest(const uint8_t *data, size_t n, uint64_t min_size,
                  uint64_t desired, uint64_t max_size, uint32_t mask_s,
                  uint32_t mask_l, uint64_t *offsets, uint64_t *lengths,
                  uint8_t *digests, size_t cap) {
    long k = bkw_chunk(data, n, min_size, desired, max_size, mask_s, mask_l,
                       offsets, lengths, cap);
    if (k < 0) return k;
    for (long i = 0; i < k; i++)
        bkw_blake3(data + offsets[i], lengths[i], digests + 32 * i);
    return k;
}
