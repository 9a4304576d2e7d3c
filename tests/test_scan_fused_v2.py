"""The fused-scan kernel's logic (``cdc_scan_fused_v2``, four bytes a
u32 word) vs the XLA oracle.

The Mosaic lowering itself can only be proven on TPU
(``scan_fused.fused_scan_available`` checks it there against the XLA scan
on the live runtime); here the kernel BODY runs in pallas interpret mode
on CPU, which validates the plane-permutation ladder, halo plumbing, and
bit-pack math, and the relayout ahead of it: the ``u8`` strip matrix,
read as words by ``pltpu.bitcast`` (PR 35; the
interpreter gives the bitcast its documented meaning, four consecutive
rows of a lane little-endian in one word, and the chip's check holds
the hardware to it).
"""

import numpy as np
import pytest

import jax.numpy as jnp

import jax
from jax.experimental import pallas as pl

from conftest import pallas_interpret_works
from backuwup_tpu.ops import scan_fused
from backuwup_tpu.ops.cdc_tpu import _candidate_words, _hash_ext_fast

if not pallas_interpret_works():  # pragma: no cover
    pytest.skip("pallas interpret mode unavailable on this host",
                allow_module_level=True)


@pytest.mark.parametrize("case", ["random", "zeros", "short_rows",
                                  "multi_tile", "min_p", "single_row",
                                  "nv_mod_4", "multi_tile_short",
                                  "many_rows", "one_step_odd_rows"])
def test_v2_kernel_matches_xla_oracle(case):
    rng = np.random.default_rng(42)
    # multi_tile: S32 = P/512 = 2048 > R32 = 512 -> 4 grid steps, so the
    # prev-tile halo branch (i > 0) is exercised, not just halo0;
    # min_p: P=4096 makes R32 == HR == 8 (tightest legal geometry);
    # one_step_odd_rows: S32 = 520 is no multiple of 512, one grid step
    # over a block of 65 byte tiles
    P = {"multi_tile": 1 << 20, "multi_tile_short": 1 << 20, "min_p": 4096,
         "one_step_odd_rows": 65 * 4096}.get(case, 64 * 1024)
    B = {"single_row": 1, "nv_mod_4": 4, "many_rows": 9}.get(case, 2)
    ext = rng.integers(0, 256, (B, 31 + P), dtype=np.uint8)
    if case == "zeros":
        ext[0] = 0
    nv = np.full(B, P, dtype=np.int32)
    if case == "short_rows":
        nv[1] = P - 12345
    if case == "nv_mod_4":  # a row ends inside a word, at each byte of it
        nv[:] = P - 4096 - np.arange(4)
    if case == "multi_tile_short":  # the last valid byte in a middle tile
        nv[:] = [P - 1, (P >> 1) + 2051]
    if case == "many_rows":
        nv[:] = P - 257 * np.arange(B)
    mask_s, mask_l = 0xFFF00000, 0xFFF80000
    wl, ws = scan_fused._fused_candidate_words_u32(
        jnp.asarray(ext), jnp.asarray(nv),
        mask_s=mask_s, mask_l=mask_l, interpret=True)
    for r in range(B):
        h = _hash_ext_fast(jnp.asarray(ext[r]))
        rl, rs = _candidate_words(h, jnp.int32(nv[r]),
                                  jnp.uint32(mask_s), jnp.uint32(mask_l))
        assert np.array_equal(np.asarray(wl[r]), np.asarray(rl)), case
        assert np.array_equal(np.asarray(ws[r]), np.asarray(rs)), case


def test_strip_words_are_the_streams_little_endian_words():
    """What the kernel reads: word ``[r, l]`` of a strip block is stream
    bytes ``l*S + 4r .. + 3``, the first in the low byte, and halo word
    ``[r, l]`` the same of the 32 bytes ahead of strip ``l``."""
    rng = np.random.default_rng(5)
    B, P = 2, 8192
    S = P // 128
    ext = rng.integers(0, 256, (B, 31 + P), dtype=np.uint8)
    body, halo0 = scan_fused._strip_matrix(jnp.asarray(ext))
    assert body.shape == (B, S, 128) and halo0.shape == (B, 32, 128)

    def kernel(body_ref, halo_ref, w_ref, h_ref):
        w_ref[0] = scan_fused._words(body_ref)
        h_ref[0] = scan_fused._words(halo_ref)

    words, halo_w = pl.pallas_call(
        kernel, grid=(B,),
        in_specs=[pl.BlockSpec((1, S, 128), lambda b: (b, 0, 0)),
                  pl.BlockSpec((1, 32, 128), lambda b: (b, 0, 0))],
        out_specs=[pl.BlockSpec((1, S // 4, 128), lambda b: (b, 0, 0)),
                   pl.BlockSpec((1, 8, 128), lambda b: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, S // 4, 128), jnp.uint32),
                   jax.ShapeDtypeStruct((B, 8, 128), jnp.uint32)],
        interpret=True)(body, halo0)
    stream = np.concatenate([np.zeros((B, 1), np.uint8), ext], axis=1)
    for b in range(B):
        flat = np.ascontiguousarray(stream[b, 32:]).view("<u4")
        assert np.array_equal(np.asarray(words[b]),
                              flat.reshape(128, S // 4).T)
        for lane in (0, 1, 127):
            ahead = np.ascontiguousarray(
                stream[b, lane * S:lane * S + 32]).view("<u4")
            assert np.array_equal(np.asarray(halo_w[b, :, lane]), ahead)
