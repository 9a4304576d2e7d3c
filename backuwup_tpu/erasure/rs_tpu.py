"""Batched device Reed-Solomon encode/decode.

Multiplication by a constant in GF(2^8) is linear over GF(2):
``c * x = XOR_b (bit_b(x) ? c * 2^b : 0)``, an 8 x 8 bit matrix applied
to the bits of ``x``.  So the generator-matrix product needs no table
indexed by data: the host writes the matrix's coefficients out as one
``(8r, 8j)`` 0/1 matrix (:func:`bit_matrix`, from the products of each
coefficient with the eight powers of two in the :mod:`.gf_cpu` oracle's
table, which is where the field's polynomial is stated), the device
multiplies it with the shard bytes' bit planes as an integer matmul and
keeps the sums' parity.  The kernel is plain jnp/lax under ``jit`` over
a batch of shard stripes (no per-byte host work, no gather) and must be
bit-exact against the :mod:`.gf_cpu` oracle (tests pin the parity).

The k x k recovery-matrix inversion stays on the host (:func:`gf_cpu.
decode_matrix`): it is an O(k^3) operation on a <= 32-wide matrix, far
below device-dispatch cost.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import profile as obs_profile
from . import gf_cpu


_BITS = np.arange(8, dtype=np.uint8)
_POW2_PRODUCTS = gf_cpu.MUL_TABLE[:, 1 << _BITS]  # [c, b] = c * 2^b


def bit_matrix(mat: np.ndarray) -> np.ndarray:
    """``(r, j)`` GF(2^8) matrix -> the ``(8r, 8j)`` int8 0/1 matrix that
    does the same product on bits: row ``8r + i``, column ``8j + b`` is
    bit ``i`` of ``mat[r, j] * 2^b`` (out of the oracle's table)."""
    prods = _POW2_PRODUCTS[np.asarray(mat, dtype=np.uint8)]    # (r, j, b)
    r, j, _ = prods.shape
    rows = (prods[:, None, :, :] >> _BITS[None, :, None, None]) & 1
    return np.ascontiguousarray(rows.reshape(8 * r, 8 * j), dtype=np.int8)


@functools.lru_cache(maxsize=None)
def _matmul_batched():
    """jit GF(2^8) matmul: (bits (8r, 8j), stripes (B, j, L)) ->
    (B, r, L), ``bits`` the matrix's :func:`bit_matrix`.

    The stripe's bit planes are an ``(8j, L)`` 0/1 matrix; bit ``i`` of
    ``out[r, l]`` is the parity of row ``8r + i`` of ``bits`` times
    column ``l`` of it: an int8 matmul the MXU does, exact in int32 (a
    sum is at most ``8j``).  Of the forms probed on the v5e the fastest
    at a sealed packfile's ``(1, 4, 1 MiB)``, 0.08 ms (PERF.md section
    5, PR 37).  jit caches per (r, j, B, L) shape bucket.
    """
    # the jitted function's name is the program's name in a device trace
    def rs_gf_matmul(bits, stripes):
        with jax.named_scope("rs_bitplane_matmul"):
            b, j, ln = stripes.shape
            planes = ((stripes[:, :, None, :] >> _BITS[None, None, :, None])
                      & np.uint8(1)).reshape(b, 8 * j, ln)
            sums = jnp.einsum("pq,bql->bpl", bits, planes.astype(jnp.int8),
                              preferred_element_type=jnp.int32)
            out = (sums & 1).reshape(b, -1, 8, ln) << _BITS.astype(
                np.int32)[None, None, :, None]
            return jnp.sum(out, axis=2).astype(jnp.uint8)

    return jax.jit(rs_gf_matmul)


def _length_bucket(n: int) -> int:
    """Shard lengths are padded to a power of two (>= 4 KiB): every
    packfile has its own length, and an exact-length program would be
    compiled anew for each one (a served backup on the chip spent its
    send stage compiling).  The product is column-wise, so zero columns
    change nothing and are sliced off again."""
    return max(4096, 1 << (n - 1).bit_length())


def gf_matmul_stripes(mat: np.ndarray, stripes: np.ndarray) -> np.ndarray:
    """Device GF(2^8) matmul over a batch of stripes; returns host uint8."""
    stripes = np.asarray(stripes, dtype=np.uint8)
    ln = stripes.shape[2]
    pad = _length_bucket(ln) - ln
    if pad:
        stripes = np.pad(stripes, ((0, 0), (0, 0), (0, pad)))
    bits = bit_matrix(mat)
    obs_profile.device_upload(bits.nbytes + stripes.nbytes)
    out = _matmul_batched()(jnp.asarray(bits), jnp.asarray(stripes))
    obs_profile.device_wait()
    return np.asarray(jax.device_get(out), dtype=np.uint8)[:, :, :ln]


def encode_stripes(stripes: np.ndarray, m: int) -> np.ndarray:
    """(B, k, L) data shards -> (B, m, L) parity shards on device."""
    stripes = np.asarray(stripes, dtype=np.uint8)
    b, k, ln = stripes.shape
    if m == 0 or b == 0:
        return np.zeros((b, m, ln), dtype=np.uint8)
    parity_rows = gf_cpu.generator_matrix(k, m)[k:]
    return gf_matmul_stripes(parity_rows, stripes)


def decode_stripes(stripes: np.ndarray, k: int, m: int,
                   present: Sequence[int]) -> np.ndarray:
    """(B, k, L) surviving shards (rows in sorted ``present`` order) ->
    (B, k, L) reconstructed data shards."""
    stripes = np.asarray(stripes, dtype=np.uint8)
    if stripes.shape[0] == 0:
        return stripes
    cols = sorted(set(int(i) for i in present))
    rec = gf_cpu.decode_matrix(k, m, cols)[:, cols]
    return gf_matmul_stripes(rec, stripes)
