"""TPU (device-path) CDC scan must be bit-identical to the CPU oracle."""

import numpy as np
import pytest

from backuwup_tpu.ops import cdc_cpu
from backuwup_tpu.ops.cdc_tpu import TpuCdcScanner, gear_hashes_tpu
from backuwup_tpu.ops.gear import CDCParams

SMALL = CDCParams.from_desired(4096)  # min 1024 / desired 4096 / max 12288


def _data(n, seed=7):
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8).tobytes()


@pytest.mark.parametrize("n", [0, 1, 31, 32, 33, 1000, 4096, 65536, 200_000])
def test_hashes_match_oracle(n):
    data = _data(n)
    np.testing.assert_array_equal(gear_hashes_tpu(data),
                                  cdc_cpu.gear_hashes(data))


def test_hashes_with_halo():
    data = _data(10_000)
    tail, rest = data[:5000], data[5000:]
    got = gear_hashes_tpu(rest, prev_tail=tail)
    np.testing.assert_array_equal(got, cdc_cpu.gear_hashes(data)[5000:])


@pytest.mark.parametrize("n", [0, 1, 1023, 1024, 5000, 200_000, 1_000_000])
def test_chunks_match_oracle(n):
    data = _data(n, seed=n or 1)
    scanner = TpuCdcScanner(SMALL)
    assert scanner.chunk_stream(data) == cdc_cpu.chunk_stream(data, SMALL)


def test_chunks_multi_segment():
    # Segment smaller than the stream forces the carried-halo path.
    data = _data(300_000, seed=3)
    scanner = TpuCdcScanner(SMALL, segment_size=65536)
    assert scanner.chunk_stream(data) == cdc_cpu.chunk_stream(data, SMALL)


def test_chunk_invariants():
    data = _data(500_000, seed=9)
    chunks = TpuCdcScanner(SMALL).chunk_stream(data)
    assert sum(c[1] for c in chunks) == len(data)
    offsets = [c[0] for c in chunks]
    assert offsets == sorted(offsets)
    for off, ln in chunks[:-1]:
        assert SMALL.min_size <= ln <= SMALL.max_size
    assert chunks[-1][1] <= SMALL.max_size


def test_segment_overflow_falls_back_to_oracle(monkeypatch):
    # Force the sparse-word capacity below the real candidate count so the
    # oracle-rescan branch runs; output must stay bit-identical.
    data = _data(200_000, seed=11)
    scanner = TpuCdcScanner(SMALL, segment_size=65536)
    monkeypatch.setattr(scanner, "_k_cap", lambda padded: 512)
    n_cand = len(cdc_cpu.candidate_positions(data[:65536], SMALL)[1])
    assert n_cand > 0  # sanity: there are candidates to overflow with
    assert scanner.chunk_stream(data) == cdc_cpu.chunk_stream(data, SMALL)


# --- the scan's word compaction (PR 47) -------------------------------------

# (word count, k_cap): the smallest segment bucket, a 1 MiB and a 4 MiB one
_COMPACT_SHAPES = {2048: 512, 32768: 512, 131072: 2048}
_COMPACT_MASKS = ["all-zero", "first-word", "last-word", "full-block",
                  "every-word", "exactly-k_cap", "k_cap-plus-1",
                  "density-2^-13", "density-2^-9"]


def _mask_indices(kind, n, k_cap, rng):
    if kind.startswith("density"):
        bits = int(kind.rsplit("-", 1)[1])
        return np.flatnonzero(rng.random(n) < 2.0 ** -bits)
    return {"all-zero": lambda: np.empty(0, np.int64),
            "first-word": lambda: np.array([0]),
            "last-word": lambda: np.array([n - 1]),
            "full-block": lambda: np.arange(256, 384),
            "every-word": lambda: np.arange(n),
            "exactly-k_cap": lambda: rng.choice(n, k_cap, replace=False),
            "k_cap-plus-1": lambda: rng.choice(n, k_cap + 1, replace=False),
            }[kind]()


def _contract(words_l, words_s, k_cap):
    """``_scan_segment``'s outputs in numpy: what the direct
    ``jnp.nonzero(size=k_cap, fill_value=-1)`` returned, to the bit."""
    nz = np.flatnonzero(words_l)
    widx = np.full(k_cap, -1, np.int32)
    widx[:min(len(nz), k_cap)] = nz[:k_cap]
    safe = np.clip(widx, 0, len(words_l) - 1)
    return widx, words_l[safe], words_s[safe], len(nz)


@pytest.mark.parametrize("kind", _COMPACT_MASKS)
@pytest.mark.parametrize("n", sorted(_COMPACT_SHAPES))
def test_compact_words_matches_flatnonzero(n, kind):
    import jax

    from backuwup_tpu.ops.cdc_tpu import _compact_words

    k_cap = _COMPACT_SHAPES[n]
    rng = np.random.default_rng(n + _COMPACT_MASKS.index(kind))
    idx = _mask_indices(kind, n, k_cap, rng)
    words_l = np.zeros(n, np.uint32)
    words_l[idx] = rng.integers(1, 1 << 32, len(idx), dtype=np.uint64)
    words_s = words_l & rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(
        np.uint32)
    got = jax.jit(_compact_words, static_argnums=2)(words_l, words_s, k_cap)
    widx, wl, ws, count = _contract(words_l, words_s, k_cap)
    # the callers' one overflow signal is the TRUE count, past k_cap too
    assert int(got[3]) == count == len(idx)
    np.testing.assert_array_equal(np.asarray(got[0]), widx)
    np.testing.assert_array_equal(np.asarray(got[1]), wl)
    np.testing.assert_array_equal(np.asarray(got[2]), ws)
    assert got[0].dtype == np.int32 and got[1].dtype == np.uint32


@pytest.mark.parametrize("n", sorted(_COMPACT_SHAPES))
def test_scan_segment_n_valid_inside_a_word(n):
    """A segment that ends inside a word: the bits past ``n_valid`` are no
    candidates, the words before it are the oracle's."""
    import jax.numpy as jnp

    from backuwup_tpu.ops.cdc_tpu import _HALO, _scan_segment

    params = CDCParams.from_desired(1024)  # a candidate every 256 bytes
    k_cap = n // 8  # twice the candidates of the valid half
    ext = np.frombuffer(_data(_HALO + 32 * n, seed=n), dtype=np.uint8)
    n_valid = 32 * (n // 2) + 13
    got = _scan_segment(jnp.asarray(ext), jnp.int32(n_valid),
                        jnp.uint32(params.mask_s), jnp.uint32(params.mask_l),
                        k_cap=k_cap)
    h = cdc_cpu.gear_hashes(ext[_HALO:].tobytes(), ext[:_HALO].tobytes())
    valid = np.arange(32 * n) < n_valid
    cand_l = ((h & np.uint32(params.mask_l)) == 0) & valid
    cand_s = cand_l & ((h & np.uint32(params.mask_s)) == 0)

    def pack(bits):
        return (bits.reshape(-1, 32).astype(np.uint32)
                << np.arange(32, dtype=np.uint32)).sum(axis=1,
                                                       dtype=np.uint32)

    widx, wl, ws, count = _contract(pack(cand_l), pack(cand_s), k_cap)
    assert 0 < count == int(got[3]) <= k_cap
    np.testing.assert_array_equal(np.asarray(got[0]), widx)
    np.testing.assert_array_equal(np.asarray(got[1]), wl)
    np.testing.assert_array_equal(np.asarray(got[2]), ws)
    assert widx.max() <= (n_valid - 1) // 32


@pytest.mark.parametrize("params", [
    pytest.param(CDCParams.from_desired(64 * 1024), id="mask_l_bits-14"),
    pytest.param(CDCParams(), id="mask_l_bits-18")])
def test_candidate_positions_match_oracle_at_the_cells_densities(params):
    """The benchmark cells' two densities over a multi-segment input (the
    last segment short): the device's candidates are ``gear_hashes``'s.
    The compaction has no capacity of its own below ``k_cap``, so the
    oracle rescan is ``test_segment_overflow_falls_back_to_oracle``'s."""
    data = _data(5 * (1 << 19) + 12345, seed=params.mask_l_bits)
    scanner = TpuCdcScanner(params, segment_size=1 << 20)
    pos_s, pos_l = scanner.candidate_positions(data)
    want_s, want_l = cdc_cpu.candidate_positions(data, params)
    assert len(want_l) > 4
    # the first 31 hashes of a stream have a short window on the device
    np.testing.assert_array_equal(pos_l[pos_l >= 31], want_l[want_l >= 31])
    np.testing.assert_array_equal(pos_s[pos_s >= 31], want_s[want_s >= 31])


def test_scan_select_forced_cut_fallback_and_parallel_paths(rng):
    """The pointer-doubling selection and its sequential fallback must both
    be bit-identical to the oracle: zero runs force non-candidate cuts
    (fallback), random data stays on the parallel path, and mixtures cross
    between them mid-stream."""
    import functools

    import jax.numpy as jnp
    import numpy as np

    from backuwup_tpu.ops import cdc_cpu
    from backuwup_tpu.ops.cdc_tpu import _HALO, scan_select_batch
    from backuwup_tpu.ops.gear import CDCParams
    from backuwup_tpu.ops.pipeline import DevicePipeline

    params = CDCParams.from_desired(1024)
    pipe = DevicePipeline(params, l_bucket=4)
    cases = [
        rng.randbytes(50_000),                      # parallel path
        b"\x00" * 40_000,                           # all forced (fallback)
        rng.randbytes(20_000) + b"\x00" * 20_000 + rng.randbytes(20_000),
        b"\x00" * 20_000 + rng.randbytes(30_000),   # forced then candidates
        rng.randbytes(1),                           # single byte
        rng.randbytes(params.min_size),             # exactly min
    ]
    P = 65536
    for data in cases:
        n = len(data)
        s_cap, l_cap, cut_cap = pipe._caps(P)
        buf = np.zeros((1, _HALO + P), dtype=np.uint8)
        buf[0, _HALO:_HALO + n] = np.frombuffer(data, dtype=np.uint8)
        fn = functools.partial(
            scan_select_batch, min_size=params.min_size,
            desired_size=params.desired_size, max_size=params.max_size,
            mask_s=params.mask_s, mask_l=params.mask_l,
            s_cap=s_cap, l_cap=l_cap, cut_cap=cut_cap)
        packed = np.asarray(fn(jnp.asarray(buf),
                               jnp.asarray(np.full(1, n, dtype=np.int32))))
        assert packed[0, 0] == 0, "unexpected overflow"
        n_cuts = int(packed[0, 1])
        ends = packed[0, 2:2 + n_cuts].tolist()
        ref = cdc_cpu.select_cuts(*_oracle_candidates(data, params),
                                  n, params).tolist()
        assert ends == ref, (n, len(ref))


def _oracle_candidates(data, params):
    from backuwup_tpu.ops import cdc_cpu
    return cdc_cpu.candidate_positions(data, params)
