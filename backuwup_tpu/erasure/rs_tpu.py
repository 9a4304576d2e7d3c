"""Batched device Reed-Solomon encode/decode.

The GF(2^8) generator-matrix product is expressed as a table-lookup
multiply plus XOR-accumulate: gather ``MUL_TABLE[mat[i, j], shard[j, l]]``
and reduce over ``j`` with ``lax.bitwise_xor``.  Following the
``blake3_tpu`` idiom, the kernel is plain jnp/lax under
``jit(vmap(...))`` over shard stripes — no per-byte host work — and must
be bit-exact against the :mod:`.gf_cpu` oracle (tests pin the parity).

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


@functools.lru_cache(maxsize=None)
def _matmul_batched():
    """jit(vmap) GF(2^8) matmul: (mat (r, j), stripes (B, j, L)) -> (B, r, L).

    The multiplication table is closed over as a device constant; jit
    caches per (r, j, B, L) shape bucket.
    """
    table = jnp.asarray(gf_cpu.MUL_TABLE)

    def one(mat, stripe):
        with jax.named_scope("rs_gather_xor"):
            prods = table[mat.astype(jnp.int32)[:, :, None],
                          stripe.astype(jnp.int32)[None, :, :]]
            return jax.lax.reduce(prods, np.uint8(0), jax.lax.bitwise_xor,
                                  (1,))

    # the jitted function's name is the program's name in a device trace
    def rs_gf_matmul(mat, stripes):
        return jax.vmap(one, in_axes=(None, 0))(mat, stripes)

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
    mat = np.asarray(mat, dtype=np.uint8)
    stripes = np.asarray(stripes, dtype=np.uint8)
    ln = stripes.shape[2]
    pad = _length_bucket(ln) - ln
    if pad:
        stripes = np.pad(stripes, ((0, 0), (0, 0), (0, pad)))
    obs_profile.device_upload(mat.nbytes + stripes.nbytes)
    out = _matmul_batched()(jnp.asarray(mat), jnp.asarray(stripes))
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
