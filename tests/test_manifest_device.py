"""Zero-round-trip device manifest must be bit-identical to the oracle:
the one batch driver (``DevicePipeline.manifest_segments_mesh``) on a
mesh of one device, the deployment's shape on one chip, and of eight."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from backuwup_tpu.obs import profile
from backuwup_tpu.ops import cdc_cpu
from backuwup_tpu.ops.blake3_cpu import Blake3Numpy
from backuwup_tpu.ops.cdc_tpu import _HALO
from backuwup_tpu.ops.gear import CDCParams
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


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("data",))


@pytest.mark.parametrize("n_dev", [1, 8])
def test_manifest_segments_mesh_driver(n_dev):
    """The one batch driver on a mesh of one device (the deployment's
    shape on one chip) and of eight: three batches through its window,
    every row the oracle's."""
    P = 65536
    rng = np.random.default_rng(11)
    batches = []
    rows_all = []
    for b in range(3):
        rows = [rng.integers(0, 256, rng.integers(1000, P + 1),
                             dtype=np.uint8).tobytes() for _ in range(2)]
        rows_all.append(rows)
        batches.append(_stage(rows, P))
    pipe = DevicePipeline(SMALL, mesh=_mesh(n_dev))
    results = list(pipe.manifest_segments_mesh(iter(batches)))
    assert len(results) == 3
    for rows, res in zip(rows_all, results):
        assert len(res) == len(rows)  # the rows padded on to fill the mesh: gone
        for data, (chunks, digests) in zip(rows, res):
            ref_chunks, ref_digests = _oracle(data, SMALL)
            assert chunks == ref_chunks
            assert [bytes(d) for d in digests] == ref_digests


@pytest.mark.parametrize("params,kw,n", [
    pytest.param(CDCParams(), {}, 8 << 20, id="ref-1m"),
    pytest.param(CDCParams.from_desired(64 << 10),
                 dict(l_bucket=256, b_bucket=512), 4 << 20, id="vm-64k")])
def test_mesh_driver_is_exact_at_deployment_widths_without_the_oracle(
        params, kw, n):
    """The batch driver on a mesh of one device, at the widths
    deployments run (the shipped 256 KiB / 1 MiB / 3 MiB, and the
    VM-image profile's 64 KiB average) over a stream with a repeated
    quarter.  ``strict_overflow`` turns every fall-back to the CPU oracle
    into an error, so the parity below is the device path's own and not
    oracle against oracle."""
    rng = np.random.default_rng(1234)
    d = rng.integers(0, 256, n, dtype=np.uint8)
    d[n // 2:n // 2 + n // 4] = d[:n // 4]
    data = d.tobytes()
    buf, nv = _stage([data], n)
    pipe = DevicePipeline(params, mesh=_mesh(1), **kw)
    ((chunks, digests),), = pipe.manifest_segments_mesh(
        [(buf, nv)], strict_overflow=True)
    ref_chunks, ref_digests = _oracle(data, params)
    assert chunks == ref_chunks
    assert [bytes(x) for x in digests] == ref_digests
    # the same dedup decision follows from the same digests
    assert len({bytes(x) for x in digests}) == len(set(ref_digests))


def test_pool_overflow_reruns_the_shard_on_the_host_tiled_path():
    # all-zero data chunks entirely at max size: the top tier overflows
    # its capacity once the batch is large enough, and the driver
    # re-runs the shard (on a mesh of one, the batch) on the host-tiled
    # path with identical output
    P = 1 << 20
    data = b"\0" * P
    buf, nv = _stage([data], P)
    pipe = DevicePipeline(SMALL, mesh=_mesh(1))
    base = profile.baseline()
    (res,), = pipe.manifest_segments_mesh(iter([(buf, nv)]))
    rep = profile.report(base)
    # the mesh program's launch and the host-tiled path's scan
    assert rep["dispatches"]["scan"] == 2
    assert rep["device_dispatches"]["0"]["scan"] == 1
    chunks, digests = res
    ref_chunks, ref_digests = _oracle(data, SMALL)
    assert chunks == ref_chunks
    assert [bytes(d) for d in digests] == ref_digests
    with pytest.raises(RuntimeError, match="pool capacity overflow"):
        list(pipe.manifest_segments_mesh(iter([(buf, nv)]),
                                         strict_overflow=True))
