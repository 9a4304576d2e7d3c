"""Device-path BLAKE3 must be bit-exact vs the scalar spec implementation."""

import random

import numpy as np
import pytest
import jax.numpy as jnp

from backuwup_tpu.ops.blake3_cpu import blake3_hash
from backuwup_tpu.ops.blake3_tpu import (
    blake3_many_tpu,
    bucketed_batches,
    digest_padded,
)

EMPTY_DIGEST = "af1349b9f5f9a1a6a0404dea36dcc9499bcb25c9adc112b7cc9a93cae41f3262"
# Official test vector: input = single byte 0x00 (the 0..250 repeating
# pattern truncated to length 1), from BLAKE3's test_vectors.json.
ONE_BYTE_DIGEST = (
    "2d3adedff11b61f14c886e35afa036736dcd87a74d27b5c1510225d0f592e213")


def _corpus():
    rng = random.Random(7)
    lengths = [0, 1, 63, 64, 65, 1023, 1024, 1025, 2048, 2049, 3072, 4096,
               5000, 1024 * 7, 1024 * 8 + 1, 1024 * 16, 1024 * 31 + 17,
               1024 * 64, 1024 * 100 + 3]
    return [rng.randbytes(n) for n in lengths]


def test_empty_vector():
    assert blake3_many_tpu([b""])[0].hex() == EMPTY_DIGEST


def test_one_byte_official_vector():
    assert blake3_hash(b"\x00").hex() == ONE_BYTE_DIGEST
    assert blake3_many_tpu([b"\x00"])[0].hex() == ONE_BYTE_DIGEST


def test_matches_scalar_spec():
    corpus = _corpus()
    for data, got in zip(corpus, blake3_many_tpu(corpus)):
        assert got == blake3_hash(data), f"len={len(data)}"


def test_digest_padded_direct():
    # One bucket shape, mixed lengths inside it, including all-padding rows.
    datas = [b"", b"a", b"b" * 1500, b"c" * (16 * 1024)]
    buf = np.zeros((4, 16 * 1024), dtype=np.uint8)
    lens = np.zeros(4, dtype=np.int32)
    for i, d in enumerate(datas):
        buf[i, :len(d)] = np.frombuffer(d, dtype=np.uint8)
        lens[i] = len(d)
    root = np.asarray(digest_padded(jnp.asarray(buf), jnp.asarray(lens), L=16))
    for i, d in enumerate(datas):
        assert root[i].astype("<u4").tobytes() == blake3_hash(d)


@pytest.mark.parametrize("L", [3, 200, 768, 3072])
def test_digest_padded_tree_levels(L):
    """The tree reduction at widths whose narrow levels run unrolled (3,
    200) and in the tail loop (768: from 12 columns; 3072: from 48), with
    lengths at both ends of the row and around leaf boundaries."""
    from backuwup_tpu.ops.pipeline import _blake3_host
    rng = np.random.default_rng(L)
    lens = np.array([L * 1024, (L - 1) * 1024 + 1, 2049, 0], dtype=np.int32)
    buf = rng.integers(0, 256, (len(lens), L * 1024), dtype=np.uint8)
    root = np.asarray(digest_padded(jnp.asarray(buf), jnp.asarray(lens), L=L))
    for i, n in enumerate(lens):
        assert root[i].astype("<u4").tobytes() == \
            _blake3_host(buf[i, :n].tobytes()), (L, n)


def test_bucketing_covers_all_inputs_once():
    corpus = _corpus()
    seen = []
    for idxs, buf, lens, L in bucketed_batches(corpus):
        seen.extend(idxs)
        assert buf.shape[1] == L * 1024
        for row, i in enumerate(idxs):
            assert lens[row] == len(corpus[i])
    assert sorted(seen) == list(range(len(corpus)))
