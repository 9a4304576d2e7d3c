"""Pallas/Mosaic TPU kernels for the dedup pipeline hot ops.

The XLA formulation of the gear-table lookup materializes a (N, 256)
one-hot operand through HBM (~512 bytes of traffic per stream byte); here
the one-hot never leaves VMEM — each grid program stages 32 KiB of bytes,
expands+contracts them against the 256x4 limb table on the MXU in 8 KiB
sub-blocks, and writes only the 4-byte gear value per byte back to HBM.

STATUS: EXPERIMENTAL / not wired into the production pipeline.  The
measured round-3 variants here lose to the XLA path (per-limb matvecs
cost ~1M tiny MXU launches, ~315 ms/128 MiB vs ~110 ms for XLA's fused
nibble-bilinear form — PERF.md "dead ends").  They are kept as working,
parity-tested reference points for Mosaic layout experiments
(tests/test_pallas.py runs them on the TPU rig only); the production
scan path lives in cdc_tpu.py.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from .gear import GEAR

# bytes handled per grid program / per MXU sub-block
_TILE_BYTES = 32768
_SUB_BYTES = 8192
_LANES = 128

# (256, 128) staging shape: limb j in column j, zeros elsewhere.  The
# kernel only ever contracts one COLUMN at a time as a (1, 256) vector
# rhs — never the full matrix: a multi-column batched-dot rhs silently
# corrupts output columns on this Mosaic version (PERF.md).  The wide
# shape exists purely so the table tiles cleanly into VMEM.
_GEAR_LIMBS_F32 = np.zeros((256, 128), dtype=np.float32)
for _j in range(4):
    _GEAR_LIMBS_F32[:, _j] = (GEAR >> (8 * _j)) & 0xFF


@functools.lru_cache(maxsize=1)
def pallas_available() -> bool:
    """True when the Pallas TPU lowering is usable on this runtime."""
    if os.environ.get("BKW_PALLAS", "1") == "0":
        return False
    if jax.devices()[0].platform != "tpu":
        return False
    # on the TPU a lowering failure raises the compiler's own error
    probe = jnp.zeros(_TILE_BYTES, dtype=jnp.uint8)
    out = gear_values_pallas(probe)
    return int(np.asarray(out[0])) == int(GEAR[0])


def _gear_kernel(b_ref, tab_ref, g_ref):
    """One grid program: (TILE_ROWS, 128) u8 -> (TILE_ROWS, 128) u32.

    Rank-3 one-hot in VMEM contracted per 8-bit limb with a VECTOR rhs —
    the only batched-dot form this Mosaic version lowers correctly (a
    multi-column rhs silently corrupts output columns; see PERF.md).
    Mosaic also lacks f32->u32 casts: limbs go through i32 (values 0..255,
    so the cast is exact and the <<24 wrap is the bit pattern we want) and
    bitcast at the store.
    """
    sub_rows = _SUB_BYTES // _LANES

    def body(i, carry):
        blk = b_ref[pl.ds(i * sub_rows, sub_rows), :].astype(jnp.int32)
        cols = jax.lax.broadcasted_iota(
            jnp.int32, (sub_rows, _LANES, 256), 2)
        oh = (blk[:, :, None] == cols).astype(jnp.bfloat16)
        g = None
        for j in range(4):
            lj = jax.lax.dot_general(
                oh, tab_ref[:, j].astype(jnp.bfloat16)[None, :],
                dimension_numbers=(((2,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)[..., 0].astype(jnp.int32)
            g = lj if g is None else g | (lj << (8 * j))
        g_ref[pl.ds(i * sub_rows, sub_rows), :] = pltpu.bitcast(
            g, jnp.uint32)
        return carry

    jax.lax.fori_loop(0, _TILE_BYTES // _SUB_BYTES, body, 0)


try:  # pallas imports lazily guarded: CPU-only test runs never need them
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pl = None
    pltpu = None


_LADDER_ROWS = 512  # 64 Ki elements (256 KiB u32) per grid program


def _shift_flat(a, s: int):
    """Row-major shift of a (R,128) u32 tile by ``s`` elements, zero-fill
    from the left edge: y[r,l] = a[r,l-s] (l>=s) else a[r-1,128+l-s].

    Mosaic has no flattened-shift primitive; built from a one-row sublane
    shift plus a lane-dimension concatenate of the wrapped columns.
    """
    am1 = jnp.concatenate([jnp.zeros_like(a[:1]), a[:-1]], axis=0)
    return jnp.concatenate([am1[:, _LANES - s:], a[:, :_LANES - s]], axis=1)


def _make_ladder_cand_kernel(mask_s: int, mask_l: int):
    def kernel(nv_ref, g_ref, gprev_ref, cl_ref, cs_ref):
        """(R,128) gear values (+8-row left halo block) -> candidate bytes.

        The five doubling passes of the 32-tap windowed sum run entirely
        in VMEM over the halo-extended tile: position p needs g back to
        p-31, and the prepended halo row supplies 128 left elements, so
        every tile row is exact; the halo row's own left truncation is
        discarded with it.  Output is one u8 (0/1) per position for each
        mask — 1/4 the write traffic of materializing hashes, in the same
        (R,128) layout as the input (no relayouts, which Mosaic forbids
        for sub-32-bit types).
        """
        i = pl.program_id(0)
        halo = jnp.where(i > 0, gprev_ref[7:8, :],
                         jnp.zeros_like(gprev_ref[7:8, :]))
        a = jnp.concatenate([halo, g_ref[:]], axis=0)  # (R+1, 128)
        for t in range(5):
            s = 1 << t
            a = a + (_shift_flat(a, s) << jnp.uint32(s))
        h = a[1:]
        R = h.shape[0]
        base = i * (R * 128)
        pos = base + (jax.lax.broadcasted_iota(jnp.int32, h.shape, 0) * 128
                      + jax.lax.broadcasted_iota(jnp.int32, h.shape, 1))
        valid = pos < nv_ref[0]
        cand_l = ((h & jnp.uint32(mask_l)) == jnp.uint32(0)) & valid
        cand_s = cand_l & ((h & jnp.uint32(mask_s)) == jnp.uint32(0))
        cl_ref[:] = cand_l.astype(jnp.uint8)
        cs_ref[:] = cand_s.astype(jnp.uint8)

    return kernel


@functools.partial(jax.jit, static_argnames=("mask_s", "mask_l"))
def ladder_candidates_pallas(g: jnp.ndarray, n_valid, *,
                             mask_s: int, mask_l: int):
    """Gear values (flat u32, length multiple of LADDER block) ->
    (cand_l, cand_s) u8 arrays of the same length.

    ``n_valid`` bounds the valid positions (padding precedes/follows the
    real stream); callers account for any leading offset themselves.
    """
    n = g.shape[0]
    block = _LADDER_ROWS * _LANES
    assert n % block == 0, "caller pads to the ladder block size"
    rows = n // _LANES
    g2 = g.reshape(rows, _LANES)
    nv = jnp.full((1,), n_valid, dtype=jnp.int32)
    grid = rows // _LADDER_ROWS
    kernel = _make_ladder_cand_kernel(mask_s, mask_l)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((_LADDER_ROWS, _LANES), lambda i, *_: (i, 0),
                         memory_space=pltpu.VMEM),
            # 8-row halo block ending at the tile's first row; clamped at
            # the left edge (tile 0 zeroes it in-kernel)
            pl.BlockSpec((8, _LANES),
                         lambda i, *_: (jnp.maximum(
                             i * (_LADDER_ROWS // 8) - 1, 0), 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((_LADDER_ROWS, _LANES), lambda i, *_: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_LADDER_ROWS, _LANES), lambda i, *_: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
    )
    cl, cs = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), jnp.uint8),
                   jax.ShapeDtypeStruct((rows, _LANES), jnp.uint8)],
        grid_spec=grid_spec,
    )(nv, g2, g2)
    return cl.reshape(n), cs.reshape(n)


@jax.jit
def gear_values_pallas(b: jnp.ndarray) -> jnp.ndarray:
    """GEAR[b] for a u8 vector via the VMEM-resident one-hot matmul.

    Accepts any length; internally pads to the tile size and slices back.
    """
    n = b.shape[0]
    padded = -(-max(n, 1) // _TILE_BYTES) * _TILE_BYTES
    if padded != n:
        b = jnp.concatenate([b, jnp.zeros(padded - n, dtype=jnp.uint8)])
    rows = padded // _LANES
    tile_rows = _TILE_BYTES // _LANES
    b2 = b.reshape(rows, _LANES)
    tab = jnp.asarray(_GEAR_LIMBS_F32)
    g2 = pl.pallas_call(
        _gear_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.uint32),
        grid=(rows // tile_rows,),
        in_specs=[
            pl.BlockSpec((tile_rows, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((256, 128), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tile_rows, _LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
    )(b2, tab)
    return g2.reshape(padded)[:n]
