"""Backend-level parity: the production TPU manifest path vs the CPU oracle.

The device-resident batch path (`DevicePipeline.manifest_batch` behind
`TpuBackend.manifest_many`) must produce bit-identical chunk boundaries and
digests to `CpuBackend` — dedup ratios depend on it (SURVEY.md section 7
hard part 1).
"""

import mmap
import random

import pytest

from backuwup_tpu.obs import profile as obs_profile
from backuwup_tpu.ops.backend import CpuBackend, TpuBackend, select_backend
from backuwup_tpu.ops.cdc_tpu import TpuCdcScanner
from backuwup_tpu.ops.gear import CDCParams

PARAMS = CDCParams.from_desired(4096)
SEGMENT = 64 * 1024


def _assert_manifests_equal(a, b):
    assert len(a) == len(b)
    for ma, mb in zip(a, b):
        assert [(r.offset, r.length, r.hash) for r in ma] == \
            [(r.offset, r.length, r.hash) for r in mb]


@pytest.fixture(scope="module")
def backends():
    return CpuBackend(PARAMS), TpuBackend(PARAMS)


def test_manifest_many_parity_mixed_sizes(backends, rng=random.Random(5)):
    cpu, tpu = backends
    streams = [
        b"",                       # empty file
        b"x",                      # single byte
        rng.randbytes(100),        # < min_size (single runt chunk)
        rng.randbytes(PARAMS.min_size),          # exactly min
        rng.randbytes(5000),
        rng.randbytes(65536),      # exactly one segment bucket
        rng.randbytes(65537),      # just over a bucket boundary
        rng.randbytes(200_000),    # multi-chunk
        b"\x00" * 50_000,          # no candidates -> max-size forced cuts
        rng.randbytes(60_000) * 2,  # internal duplication
    ]
    _assert_manifests_equal(cpu.manifest_many(streams),
                            tpu.manifest_many(streams))


def test_manifest_many_parity_large_batch(backends, rng=random.Random(6)):
    """Many small files of one bucket — the vmapped batch dispatch."""
    cpu, tpu = backends
    streams = [rng.randbytes(rng.randrange(1, 30_000)) for _ in range(64)]
    _assert_manifests_equal(cpu.manifest_many(streams),
                            tpu.manifest_many(streams))


def test_plain_manifest_many_runs_the_mesh_program_on_every_device(
        rng=random.Random(8)):
    """A plain ``manifest_many`` (no device index) goes the one way a
    pack batch goes to the chip: the bucketed streams run the mesh
    program, a dispatch labelled on every device of the default mesh
    (``conftest.py``: eight), and the manifests are the CPU oracle's."""
    import jax

    streams = [rng.randbytes(n) for n in (5000, 20_000, 50_000, 65_000)]
    base = obs_profile.baseline()
    got = TpuBackend(PARAMS).manifest_many(streams)
    dev = obs_profile.report(base)["device_dispatches"]
    assert sorted(dev, key=int) == [str(d) for d in range(jax.device_count())]
    assert all(dev[d]["scan"] == 1 and dev[d]["digest"] == 1 for d in dev)
    _assert_manifests_equal(CpuBackend(PARAMS).manifest_many(streams), got)


def test_manifest_stream_matches_manifest(backends, rng=random.Random(7)):
    cpu, tpu = backends
    data = rng.randbytes(300_000)
    pos = [0]

    def read(n):
        out = data[pos[0]:pos[0] + n]
        pos[0] += n
        return out

    refs = tpu.manifest_stream(read, segment_bytes=64 * 1024)
    assert [(r.offset, r.length, r.hash) for r in refs] == \
        [(r.offset, r.length, r.hash) for r in cpu.manifest(data)]


def _reader(data, sizes=()):
    """``read(n)`` over ``data``; the first reads return at most
    ``sizes[i]`` bytes (a read may return fewer than it was asked for)."""
    pos, caps = [0], list(sizes)

    def read(n):
        if caps:
            n = min(n, caps.pop(0))
        out = data[pos[0]:pos[0] + n]
        pos[0] += len(out)
        return out

    return read


def _first_cut_after(data, floor):
    return next(r.offset + r.length for r in CpuBackend(PARAMS).manifest(data)
                if r.offset + r.length >= floor)


# each case: (stream, sizes of the first reads, what the route must have
# done besides matching the oracle)
def _stream_cases():
    rng = random.Random(26)
    plain = rng.randbytes(3 * SEGMENT + 4321)
    return {
        "shorter_than_a_segment": (rng.randbytes(SEGMENT // 3), (), None),
        "exact_multiple_of_segment": (rng.randbytes(3 * SEGMENT), (), None),
        # the first read ends on a cut: the carry is one whole chunk and
        # the next window starts a chunk
        "cut_on_the_segment_boundary": (
            plain, (_first_cut_after(plain, SEGMENT // 2),), "no_assembly"),
        "chunk_spans_carry_and_window": (plain, (), "assembled"),
        # reads shorter than min_size: every window leaves one open chunk
        "single_open_chunk_carried_whole": (
            plain[:20_000], (PARAMS.min_size // 2,) * 40, "assembled"),
        "zeros_force_max_size_cuts": (b"\x00" * (2 * SEGMENT + 99), (), None),
        "trailing_chunk_below_min_size": (
            plain[:SEGMENT] + b"tail", (), None),
    }


@pytest.mark.parametrize("case", list(_stream_cases()))
def test_resident_stream_route_matches_the_oracle(backends, case):
    """``TpuBackend.manifest_stream`` (one resident segment at a time,
    ops/resident.py) against ``CpuBackend.manifest`` of the whole
    stream: offsets, lengths, digests, and the bytes handed to ``emit``."""
    cpu, tpu = backends
    data, sizes, expect = _stream_cases()[case]
    base = obs_profile.baseline()
    seen = []
    refs = tpu.manifest_stream(
        _reader(data, sizes), segment_bytes=SEGMENT,
        emit=lambda ref, chunk: seen.append((ref, bytes(chunk))))
    assert [(r.offset, r.length, r.hash) for r in refs] == \
        [(r.offset, r.length, r.hash) for r in cpu.manifest(data)]
    assert [r for r, _ in seen] == refs
    assert all(data[r.offset:r.offset + r.length] == c for r, c in seen)
    assembled = obs_profile.report(base)["stream"]["host_assembled_bytes"]
    if expect == "assembled":
        assert 0 < assembled
    elif expect == "no_assembly":
        # only the later, ordinary boundaries cost a copy: fewer bytes
        # than one max-size chunk a segment
        assert assembled <= 3 * PARAMS.max_size


@pytest.mark.parametrize("source", ["mmap_views", "bytes"])
def test_resident_stream_route_reads_views_and_bytes(backends, tmp_path,
                                                     source):
    """The packer hands ``read`` windows of an mmap; its fallback hands
    ``bytes``.  Both upload as they are, and the map closes afterwards."""
    cpu, tpu = backends
    data = random.Random(27).randbytes(2 * SEGMENT + 777)
    want = [(r.offset, r.length, r.hash) for r in cpu.manifest(data)]
    if source == "bytes":
        refs = tpu.manifest_stream(_reader(data), segment_bytes=SEGMENT)
    else:
        path = tmp_path / "image"
        path.write_bytes(data)
        with open(path, "rb") as f:
            mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
            view = memoryview(mm)
            refs = tpu.manifest_stream(_reader(view),
                                       segment_bytes=SEGMENT)
            view.release()
            mm.close()  # BufferError if a window is still held
    assert [(r.offset, r.length, r.hash) for r in refs] == want


def test_resident_stream_route_rescans_an_overflowed_slice_on_the_host():
    """A scan slice with more candidate words than its sparse capacity
    (``cap_factor`` 0 leaves 512; a 4-bit loose mask makes ~1,700 in
    64 KiB) is rescanned by the numpy oracle from the host's view."""
    params = CDCParams(min_size=64, desired_size=256, max_size=768,
                       mask_s_bits=10, mask_l_bits=4)
    tpu = TpuBackend(params)
    tpu._scanner = TpuCdcScanner(params, cap_factor=0)
    data = random.Random(28).randbytes(2 * SEGMENT + 300)
    base = obs_profile.baseline()
    refs = tpu.manifest_stream(_reader(data), segment_bytes=SEGMENT)
    assert [(r.offset, r.length, r.hash) for r in refs] == \
        [(r.offset, r.length, r.hash)
         for r in CpuBackend(params).manifest(data)]
    # every slice overflowed, so every byte was rescanned on the host
    assert obs_profile.report(base)["stream"]["host_assembled_bytes"] \
        >= len(data)


def test_select_backend_policy():
    assert select_backend("cpu").name == "cpu"
    assert select_backend("tpu").name == "tpu"


def test_native_backend_matches_cpu():
    pytest.importorskip("ctypes")
    from backuwup_tpu.ops.backend import NativeBackend
    from backuwup_tpu.native import NativeUnavailable
    try:
        nat = NativeBackend(PARAMS)
    except NativeUnavailable:
        pytest.skip("native toolchain unavailable")
    cpu = CpuBackend(PARAMS)
    data = random.Random(11).randbytes(200_000)
    got = nat.manifest_many([data, b"", data[:100]])
    want = cpu.manifest_many([data, b"", data[:100]])
    assert [[(r.offset, r.length, r.hash) for r in refs] for refs in got] \
        == [[(r.offset, r.length, r.hash) for r in refs] for refs in want]
