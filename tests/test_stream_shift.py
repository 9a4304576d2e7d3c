"""Content that shifts against the streaming route's windows.

An insertion or a deletion moves every byte behind it, so a file that is
regenerated with rows put in and taken out (``benchmark``'s ``dump-1m``)
meets the packer's windows with its cuts somewhere else every night:
the carry, the chunk astride two windows and the short last window take
new lengths in every backup.  ``TpuBackend.manifest_stream`` (one
resident window at a time, ``ops/resident.py``) against ``ops/cdc_cpu``
and ``ops/blake3_cpu`` over the whole stream, on seeded streams edited
so that a cut falls where the route has a seam; and the route's books:
every byte is uploaded once and scanned once, and what a window leaves
open moves to the next by the device slice and is counted as carried.
"""

import functools
import random

import pytest

from backuwup_tpu.obs import metrics as obs_metrics
from backuwup_tpu.obs import profile as obs_profile
from backuwup_tpu.ops.backend import TpuBackend
from backuwup_tpu.ops.blake3_cpu import blake3_hash
from backuwup_tpu.ops.cdc_cpu import chunk_stream
from backuwup_tpu.ops.gear import CDCParams
from backuwup_tpu.ops.resident import BLOCK_MIN, Geometry

PARAMS = CDCParams.from_desired(4096)
SEGMENT = 64 * 1024
EDGE = SEGMENT  # where the first window ends and the second begins


@pytest.fixture(scope="module")
def tpu():
    return TpuBackend(PARAMS)


def _oracle(data: bytes) -> list:
    return [(off, n, blake3_hash(data[off:off + n]))
            for off, n in chunk_stream(data, PARAMS)]


def _ends(data: bytes) -> list:
    return [off + n for off, n in chunk_stream(data, PARAMS)]


def _edited(data: bytes, at: int, shift: int, rng) -> bytes:
    """``data`` with ``shift`` seeded bytes put in at ``at`` (or, where
    it is negative, that many taken out), as a night's edit does."""
    if shift >= 0:
        return data[:at] + rng.randbytes(shift) + data[at:]
    return data[:at] + data[at - shift:]


def _with_cut_at(where: int, seed: int) -> bytes:
    """A seeded stream of three windows and a bit, edited near its start
    so that a content-defined cut (a chunk's end) falls at ``where``."""
    rng = random.Random(seed)
    for _ in range(50):
        data = rng.randbytes(3 * SEGMENT + 4321)
        cut = next(e for e in _ends(data) if e >= EDGE + 2 * PARAMS.max_size)
        out = _edited(data, 100, where - cut, rng)
        if where in _ends(out):
            return out
    raise AssertionError("no draw put a cut there")


def _forced_cut_astride() -> bytes:
    """Zeros carry no cut candidate, so chunks of ``max_size`` tile
    them: one lies astride the first window's edge."""
    rng = random.Random(31)
    data = rng.randbytes(EDGE - 2 * PARAMS.max_size - 777) \
        + bytes(4 * PARAMS.max_size) + rng.randbytes(SEGMENT + 999)
    astride = [(off, n) for off, n in chunk_stream(data, PARAMS)
               if off < EDGE < off + n]
    assert astride and astride[0][1] == PARAMS.max_size
    return data


def _moved_across(seed: int):
    """(before, after, digest): ``after`` is ``before`` with a few KiB
    put in near its start, which moves a chunk that lay wholly in the
    first window wholly into the second; taking them out again moves it
    back."""
    rng = random.Random(seed)
    for _ in range(50):
        before = rng.randbytes(3 * SEGMENT + 555)
        shift = 3 * PARAMS.max_size + rng.randrange(1, 64)
        after = _edited(before, 100, shift, rng)
        held = {d: off for off, _n, d in _oracle(after)}
        for off, n, digest in _oracle(before):
            if off + n <= EDGE <= held.get(digest, -1) == off + shift:
                return before, after, digest
    raise AssertionError("no draw moved a chunk across the edge")


@functools.lru_cache(maxsize=None)
def _cases() -> dict:
    """name -> (stream, sizes of the first reads, a digest the manifest
    has to hold).  A cut names the chunk's end: at ``EDGE`` the chunk's
    last byte is the window's last, at ``EDGE + 1`` the next window's
    first."""
    plain = random.Random(29).randbytes(2 * SEGMENT + 4000)
    before, after, moved = _moved_across(37)
    return {
        "cut_one_byte_before_the_window_s_last": (
            _with_cut_at(EDGE - 1, 41), (), None),
        "cut_at_the_window_s_last_byte": (_with_cut_at(EDGE, 42), (), None),
        "cut_at_the_next_window_s_first_byte": (
            _with_cut_at(EDGE + 1, 43), (), None),
        "cut_one_byte_behind_the_window_s_first": (
            _with_cut_at(EDGE + 2, 44), (), None),
        "max_size_forced_cut_astride_a_window": (
            _forced_cut_astride(), (), None),
        # two reads shorter than min_size: the carry is all that is
        # resident, one chunk still open, and no chunk is final
        "carry_is_the_whole_window": (
            plain, (SEGMENT, PARAMS.min_size // 4, PARAMS.min_size // 4),
            None),
        "eof_on_a_window_s_edge": (plain[:2 * SEGMENT], (), None),
        "insert_moves_content_to_the_next_window": (after, (), moved),
        "delete_moves_content_to_the_window_before": (before, (), moved),
    }


def _reader(data: bytes, sizes):
    """``read(n)`` over ``data``, the first reads at most ``sizes[i]``;
    notes the length of every window it hands out."""
    pos, caps, windows = [0], list(sizes), []

    def read(n):
        if caps:
            n = min(n, caps.pop(0))
        out = data[pos[0]:pos[0] + n]
        pos[0] += len(out)
        if out:
            windows.append(len(out))
        return out

    return read, windows


def _digest_rounds() -> float:
    return obs_metrics.registry().get("bkw_span_seconds").count_value(
        name="blake3.digest")


@pytest.mark.parametrize("case", list(_cases()))
def test_shifted_stream_matches_the_oracle_and_no_byte_goes_twice(tpu, case):
    data, sizes, held = _cases()[case]
    want = _oracle(data)
    read, windows = _reader(data, sizes)
    base, rounds0 = obs_profile.baseline(), _digest_rounds()
    seen = []
    refs = tpu.manifest_stream(
        read, segment_bytes=SEGMENT,
        emit=lambda ref, chunk: seen.append((ref, bytes(chunk))))
    assert [(r.offset, r.length, r.hash) for r in refs] == want
    assert [r for r, _ in seen] == refs
    assert all(data[r.offset:r.offset + r.length] == c for r, c in seen)
    if held is not None:
        assert held in {r.hash for r in refs}

    rep = obs_profile.report(base)
    stream = rep["stream"]
    assert sum(windows) == len(data) and stream["segments"] == len(windows)
    # every byte is scanned once, in the window that brought it
    assert rep["bytes"]["scan"] == len(data)
    # and uploaded once: the windows (a tail padded to the smallest
    # block) and the chunk rows of each digest round, nothing else
    rows = Geometry.of(PARAMS, tpu._scanner, SEGMENT).rows
    rounds = int(_digest_rounds() - rounds0)
    assert stream["uploaded_bytes"] == sum(
        -(-w // BLOCK_MIN) * BLOCK_MIN for w in windows) + 8 * rows * rounds
    # what a window left open stayed on the device: from the last chunk
    # start in front of each later window to that window's first byte
    starts = [off for off, _n, _d in want]
    edges = [sum(windows[:k]) for k in range(1, len(windows))]
    assert stream["carried_bytes"] == sum(
        e - max(s for s in starts if s < e) for e in edges)
    # every chunk is digested once, whichever window closed it
    tiles = stream["digest_classes"]
    assert sum(c["bytes"] for c in tiles.values()) == len(data)
    if case == "carry_is_the_whole_window":
        assert rounds < len(windows)  # a window closed no chunk
