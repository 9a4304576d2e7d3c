"""Zero-round-trip device manifest must be bit-identical to the oracle."""

import numpy as np
import pytest

import jax.numpy as jnp

from backuwup_tpu.ops import cdc_cpu
from backuwup_tpu.ops.blake3_cpu import Blake3Numpy
from backuwup_tpu.ops.cdc_tpu import _HALO
from backuwup_tpu.ops.gear import CDCParams
from backuwup_tpu.ops.manifest_device import (
    class_caps,
    class_leaf_sizes,
    scan_digest_batch,
)
from backuwup_tpu.ops.pipeline import DevicePipeline

SMALL = CDCParams.from_desired(4096)


def _oracle(data, params):
    chunks = cdc_cpu.chunk_stream(data, params)
    digests = Blake3Numpy().digest_batch([data[o:o + l] for o, l in chunks])
    return chunks, digests


def _stage(rows, P):
    buf = np.zeros((len(rows), _HALO + P), dtype=np.uint8)
    nv = np.zeros(len(rows), dtype=np.int32)
    for r, d in enumerate(rows):
        buf[r, _HALO:_HALO + len(d)] = np.frombuffer(d, dtype=np.uint8)
        nv[r] = len(d)
    return jnp.asarray(buf), nv


def test_class_plan_sizes():
    classes = class_leaf_sizes(SMALL)
    assert classes[-1] == SMALL.max_size // 1024
    caps = class_caps(SMALL, 1 << 20, 4)
    assert len(caps) == len(classes)
    assert all(c % 4 == 0 for c in caps)
    assert caps[-1] > 0  # cascade terminus always has slots


@pytest.mark.parametrize("sizes", [
    [65536], [65536, 30_000, 0, 65536], [1, 64, 1024]])
def test_scan_digest_batch_matches_oracle(sizes):
    P = 65536
    rows = [np.random.default_rng(3 + i).integers(
        0, 256, n, dtype=np.uint8).tobytes() for i, n in enumerate(sizes)]
    buf, nv = _stage(rows, P)
    pipe = DevicePipeline(SMALL)
    s_cap, l_cap, cut_cap = pipe._caps(P)
    classes = class_leaf_sizes(SMALL)
    caps = class_caps(SMALL, len(rows) * P, len(rows))
    packed, acc, ovf = scan_digest_batch(
        buf, jnp.asarray(nv), min_size=SMALL.min_size,
        desired_size=SMALL.desired_size, max_size=SMALL.max_size,
        mask_s=SMALL.mask_s, mask_l=SMALL.mask_l,
        s_cap=s_cap, l_cap=l_cap, cut_cap=cut_cap, fused=False,
        classes=classes, caps=caps)
    packed = np.asarray(packed)
    acc = np.asarray(acc)
    assert not np.asarray(ovf).any()
    dig8 = np.ascontiguousarray(acc.astype("<u4")).view(np.uint8).reshape(
        len(rows), cut_cap, 32)
    for r, data in enumerate(rows):
        ref_chunks, ref_digests = _oracle(data, SMALL)
        assert packed[r, 0] == 0
        n_cuts = int(packed[r, 1])
        ends = packed[r, 2:2 + n_cuts].astype(np.int64)
        offs = np.concatenate([[0], ends[:-1] + 1])
        got = list(zip(offs.tolist(), (ends - offs + 1).tolist()))
        assert got == ref_chunks
        assert [bytes(d) for d in dig8[r, :n_cuts]] == ref_digests


def test_manifest_segments_device_driver():
    P = 65536
    rng = np.random.default_rng(11)
    batches = []
    rows_all = []
    for b in range(3):
        rows = [rng.integers(0, 256, rng.integers(1000, P + 1),
                             dtype=np.uint8).tobytes() for _ in range(2)]
        rows_all.append(rows)
        batches.append(_stage(rows, P))
    pipe = DevicePipeline(SMALL)
    results = list(pipe.manifest_segments_device(iter(batches)))
    assert len(results) == 3
    for rows, res in zip(rows_all, results):
        for data, (chunks, digests) in zip(rows, res):
            ref_chunks, ref_digests = _oracle(data, SMALL)
            assert chunks == ref_chunks
            assert [bytes(d) for d in digests] == ref_digests


@pytest.mark.parametrize("params,kw,n", [
    pytest.param(CDCParams(), {}, 8 << 20, id="ref-1m"),
    pytest.param(CDCParams.from_desired(64 << 10),
                 dict(l_bucket=256, b_bucket=512), 4 << 20, id="vm-64k")])
def test_driver_is_exact_at_deployment_widths_without_the_oracle(
        params, kw, n):
    """The zero-round-trip driver at the widths deployments run (the
    shipped 256 KiB / 1 MiB / 3 MiB, and the VM-image profile's 64 KiB
    average) over a stream with a repeated quarter.  ``strict_overflow``
    turns every fall-back to the CPU oracle into an error, so the parity
    below is the device path's own and not oracle against oracle."""
    rng = np.random.default_rng(1234)
    d = rng.integers(0, 256, n, dtype=np.uint8)
    d[n // 2:n // 2 + n // 4] = d[:n // 4]
    data = d.tobytes()
    buf, nv = _stage([data], n)
    pipe = DevicePipeline(params, **kw)
    ((chunks, digests),), = pipe.manifest_segments_device(
        [(buf, nv)], strict_overflow=True)
    ref_chunks, ref_digests = _oracle(data, params)
    assert chunks == ref_chunks
    assert [bytes(x) for x in digests] == ref_digests
    # the same dedup decision follows from the same digests
    assert len({bytes(x) for x in digests}) == len(set(ref_digests))


def test_class_overflow_falls_back():
    # all-zero data chunks entirely at max size: the top class overflows
    # its calibrated capacity once the batch is large enough, and the
    # driver falls back to the host-tiled path with identical output
    P = 1 << 20
    data = b"\0" * P
    buf, nv = _stage([data], P)
    pipe = DevicePipeline(SMALL)
    (res,), = pipe.manifest_segments_device(iter([(buf, nv)]))
    chunks, digests = res
    ref_chunks, ref_digests = _oracle(data, SMALL)
    assert chunks == ref_chunks
    assert [bytes(d) for d in digests] == ref_digests
