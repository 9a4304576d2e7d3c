"""Fused Mosaic/Pallas CDC scan: gear + ladder + candidate masks in VMEM.

The XLA scan (:func:`.cdc_tpu._hash_ext_fast`) pays HBM for every pass:
the fmix32 gear values and each of the five doubling-ladder passes
materialize a u32 array the size of 4x the stream.  This kernel runs the
whole scan per VMEM-resident tile and writes only the packed candidate
words (1/4 byte per stream byte), so HBM sees the stream as bytes (the
row, its aligned copy, the strip matrix written and read) and the words.
Device time on one TPU v5e (PR 35's probe; PERF.md section 5 has the
table): a ``(1, 31 + 64 MiB)`` row 3.0 ms, of it the aligned copy 1.1,
the strip transpose 0.5 and the kernel 1.4; 32 rows of 1 MiB 1.0 ms;
128 rows of 1 MiB 3.9 ms.

Layout — the **strip decomposition**: the P-byte stream is split into
128 contiguous strips of S = P/128 bytes; strip ``l`` occupies lane
``l`` of a ``(S, 128)`` u8 array with stream position ``l*S + r`` at row
``r``.  A shift by ``s`` positions is then a pure **sublane** shift
(rows), never a lane relayout.  Each strip carries a 32-byte halo of the
previous strip's tail (real bytes, so hashes at strip starts are exact; only global
position 0 sees the spec's zero halo), and each grid step's tile carries
a 32-row halo of the previous tile via a second clamped BlockSpec.

Against the reference: this is the TPU replacement for the byte-at-a-time
FastCDC hot loop in ``client/src/backup/filesystem/dir_packer.rs:246-266``.

Output contract: ``(B, P/32) u32`` candidate words in **position-major
order** (word ``w`` bit ``t`` = candidate at position ``w*32 + t``) —
bit-identical to ``_pack_bits(cand)`` of the XLA path, so the two-level
compaction and the on-device cut selection consume either
interchangeably (tests assert equality).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gear import GEAR_SEED32

_LANES = 128
_HALO_ROWS = 32  # 31 context bytes + 1 alignment row (u8 tile = 32 sublanes)
_DEF_R = 2048  # strip rows per grid step (VMEM working set ~5 MiB)


def _fmix32_u32(x):
    h = x + jnp.uint32(GEAR_SEED32)
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> jnp.uint32(16))
    return h


def _words_shape(B: int, S: int, *inputs):
    """Output type of the scan kernel: the candidate words vary over
    whatever manual mesh axes the inputs vary over, which
    ``jax.shard_map`` (``check_vma=True``) wants stated on the
    ``pallas_call``; outside ``shard_map`` the set is empty."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in inputs))
    return jax.ShapeDtypeStruct((B, S // 32, _LANES), jnp.uint32, vma=vma)


def _words(ref):
    """A ``(1, 4R, 128) u8`` strip block as its ``(R, 128) u32`` words."""
    return pltpu.bitcast(ref[0], jnp.uint32)


def _strip_matrix(ext_b: jnp.ndarray):
    """``(B, 31+P) u8`` rows as the strip matrix the kernel reads:
    ``body[b, r, l] = stream[b, l*S + r]`` (``(B, S, 128) u8``, one
    transpose) and ``halo0`` (``(B, 32, 128) u8``), the 32 bytes ahead of
    each strip: strip ``l-1``'s tail, and for strip 0 the spec's zero byte
    plus the row's 31 halo bytes."""
    B, n = ext_b.shape
    S = (n - 31) // _LANES
    ext32 = jnp.pad(ext_b, ((0, 0), (1, 0)))
    body = ext32[:, 32:].reshape(B, _LANES, S).transpose(0, 2, 1)
    halo0 = jnp.concatenate(
        [ext32[:, :32, None], body[:, S - _HALO_ROWS:, :-1]], axis=2)
    return body, halo0


def _make_scan_kernel_u32(mask_s: int, mask_l: int, S: int, R: int):
    """The scan kernel: four stream bytes a u32 word, from the load on.

    Fed the ``u8`` strip matrix.  On the TPU four consecutive sublane
    rows of one lane of a ``u8`` array share a 32-bit word (the
    ``(32, 128)`` byte tile is ``(8, 128)`` words), so a ``(4R, 128)``
    ``u8`` block in VMEM *is* the ``(R, 128)`` ``u32`` block of packed
    stream words: ``pltpu.bitcast`` names it, nothing moves.  The kernel
    never materializes per-byte arrays:
    positions p = 4r+k live in four interleaved (rows, 128) u32 gear
    planes, a ladder shift by s byte positions is a plane permutation
    ``k -> (k-s) mod 4`` plus a sublane shift of ``(s+k'-k)/4`` rows,
    and the 32:1 bit-pack ORs plane bits at ``4r'+k``.  Bit-identical to
    ``_pack_bits`` by construction; the byte order inside the word is
    proven, not assumed, by the check against the XLA scan
    (:func:`fused_scan_available`) on the live runtime before
    production use.
    """
    HR = _HALO_ROWS // 4  # 8 u32 rows = the 32-byte halo
    R32 = R // 4  # word rows a grid step

    def kernel(nv_ref, halo0_ref, main_ref, prev_ref, wl_ref, ws_ref):
        b = pl.program_id(0)
        i = pl.program_id(1)
        halo = jnp.where(i > 0, _words(prev_ref), _words(halo0_ref))  # (HR, 128)
        w = jnp.concatenate([halo, _words(main_ref)], axis=0)  # (R32+HR, 128)
        rows = R32 + HR
        # per-byte gear values, one plane per byte-in-word slot
        g = [_fmix32_u32((w >> jnp.uint32(8 * k)) & jnp.uint32(0xFF))
             for k in range(4)]
        # 32-tap windowed sum by log-doubling over byte positions
        a = list(g)
        for t in range(5):
            s = 1 << t
            nxt = []
            for k in range(4):
                src = (k - s) % 4
                d = (s + src - k) // 4
                if d:
                    sh = jnp.concatenate(
                        [jnp.zeros((d, _LANES), dtype=jnp.uint32),
                         a[src][:rows - d]], axis=0)
                else:
                    sh = a[src]
                nxt.append(a[k] + (sh << jnp.uint32(s)))
            a = nxt
        # main rows only; plane k holds positions 4r+k
        pos_r = (jax.lax.broadcasted_iota(jnp.int32, (R32, _LANES), 1) * S
                 + (i * R32
                    + jax.lax.broadcasted_iota(jnp.int32, (R32, _LANES), 0))
                 * 4)
        n = nv_ref[b]
        wl = jnp.zeros((R32 // 8, _LANES), dtype=jnp.uint32)
        ws = jnp.zeros((R32 // 8, _LANES), dtype=jnp.uint32)
        for k in range(4):
            h = a[k][HR:]
            valid = (pos_r + k) < n
            cl = (((h & jnp.uint32(mask_l)) == jnp.uint32(0)) & valid)
            cs = cl & ((h & jnp.uint32(mask_s)) == jnp.uint32(0))
            cl3 = cl.astype(jnp.uint32).reshape(R32 // 8, 8, _LANES)
            cs3 = cs.astype(jnp.uint32).reshape(R32 // 8, 8, _LANES)
            for r2 in range(8):
                wl = wl | (cl3[:, r2, :] << jnp.uint32(4 * r2 + k))
                ws = ws | (cs3[:, r2, :] << jnp.uint32(4 * r2 + k))
        wl_ref[0] = wl
        ws_ref[0] = ws

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("mask_s", "mask_l", "interpret"))
def _fused_candidate_words_u32(ext_b: jnp.ndarray, nv_b: jnp.ndarray, *,
                               mask_s: int, mask_l: int,
                               interpret: bool = False):
    """The kernel over the strip matrix of ``ext_b``, read as packed
    words (see :func:`_make_scan_kernel_u32`): ``R`` strip rows a grid
    step, the tile ahead's last 32 rows as halo, 32 positions a word out.

    Position-major candidate words, bit-identical to the XLA
    ``_pack_bits`` path.

    Packing the words in XLA ahead of the kernel is what not to do on
    the v5e: a ``(…, 4)`` u8 -> u32 bitcast pads its minor dimension of 4
    to 128 lanes (5-9 GB of temporaries at 8-64 MiB rows, no fit at
    128 MiB; PR 22), and four ``flat[:, k::4]`` slices fit but are each
    lowered as a gather over an index vector, 0.164 s a 64 MiB row each
    where everything above takes 0.003 s (PR 35).
    """
    B, n = ext_b.shape
    P = n - 31
    assert P % (128 * 32) == 0, "P must be a multiple of 4096"
    S = P // _LANES
    R = _DEF_R if S % _DEF_R == 0 else S  # small buckets: one grid step
    body, halo0 = _strip_matrix(ext_b)
    nv = nv_b.astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, S // R),
        in_specs=[
            pl.BlockSpec((1, _HALO_ROWS, _LANES), lambda b, i, *_: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, R, _LANES), lambda b, i, *_: (b, i, 0),
                         memory_space=pltpu.VMEM),
            # previous tile's last 32 rows: block index in 32-row units,
            # clamped at 0 (tile 0 substitutes halo0 in-kernel)
            pl.BlockSpec((1, _HALO_ROWS, _LANES),
                         lambda b, i, *_: (b, jnp.maximum(
                             i * (R // _HALO_ROWS) - 1, 0), 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, R // 32, _LANES), lambda b, i, *_: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, R // 32, _LANES), lambda b, i, *_: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
    )
    wl, ws = pl.pallas_call(
        _make_scan_kernel_u32(mask_s, mask_l, S, R),
        out_shape=[_words_shape(B, S, body, nv)] * 2,
        grid_spec=grid_spec,
        interpret=interpret,
        name="cdc_scan_fused_v2",
    )(nv, halo0, body, body)
    # strip-major -> position-major: word (w, l) covers positions
    # l*S + w*32 ..+31, so transposing to (l, w) and flattening yields
    # flat word index j with base position j*32 — the _pack_bits order.
    wl = wl.transpose(0, 2, 1).reshape(B, P // 32)
    ws = ws.transpose(0, 2, 1).reshape(B, P // 32)
    return wl, ws


# True once fused_scan_available() has checked the kernel on a TPU;
# benchmark/deployment.py reads it for its ``scan_variant`` (ROADMAP D16)
_V2_SELECTED = False


def fused_candidate_words(ext_b: jnp.ndarray, nv_b: jnp.ndarray, *,
                          mask_s: int, mask_l: int):
    """``(B, 31+P) u8 -> ((B, P/32) u32, (B, P/32) u32)`` candidate words,
    bit-identical to the XLA path's ``_pack_bits(cand)``; ``P`` must be
    a multiple of 4096.  ``scan_select_batch(fused=True)`` traces
    through here; callers check :func:`fused_scan_available` first.
    """
    return _fused_candidate_words_u32(ext_b, nv_b,
                                      mask_s=mask_s, mask_l=mask_l)


def _check_kernel() -> None:
    """Run the kernel against the XLA oracle on the live runtime.  A
    kernel that does not lower raises the compiler's own error; one that
    lowers and disagrees raises here."""
    import numpy as np

    from .cdc_tpu import _candidate_words, _hash_ext_fast

    rng = np.random.default_rng(7)
    # 1 MiB rows = 4 grid steps (R32=512 of S32=2048 word rows): the
    # probe must exercise the multi-tile prev-halo path, not just tile
    # 0's halo0 branch
    P = 1 << 20
    ext = rng.integers(0, 256, (2, 31 + P), dtype=np.uint8)
    nv = np.array([P, P - 12345], dtype=np.int32)
    mask_s, mask_l = 0xFFF00000, 0xFFF80000
    wl, ws = _fused_candidate_words_u32(jnp.asarray(ext), jnp.asarray(nv),
                                        mask_s=mask_s, mask_l=mask_l)
    for r in range(2):
        h = _hash_ext_fast(jnp.asarray(ext[r]))
        rl, rs = _candidate_words(h, jnp.int32(nv[r]),
                                  jnp.uint32(mask_s), jnp.uint32(mask_l))
        if not (np.array_equal(np.asarray(wl[r]), np.asarray(rl))
                and np.array_equal(np.asarray(ws[r]), np.asarray(rs))):
            raise RuntimeError(
                "fused scan kernel disagrees with the XLA scan "
                f"on probe row {r}")


@functools.lru_cache(maxsize=1)
def fused_scan_available() -> bool:
    """True on a TPU, after the kernel lowered and matched the XLA oracle
    on this runtime (checked once, on first use): a kernel that does not
    lower or does not match raises rather than handing the scan to a
    slower path behind a green run.  Off the TPU (the CPU test
    configuration) False: the XLA ladder is the only form there.
    """
    global _V2_SELECTED
    if jax.devices()[0].platform != "tpu":
        return False
    _check_kernel()
    _V2_SELECTED = True
    return True
