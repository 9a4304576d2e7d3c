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
