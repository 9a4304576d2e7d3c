"""Ask the v5e's compiler, without the chip (PR 22, bring-up).

The TPU compiler is installed in the sandbox and compiles for a chip
that is described, not attached (``on-chip-measurement`` guide §2).
These tests compile the kernels of the served path at the widths the
engine really dispatches — 1 row x 128 MiB and 128 rows x 1 MiB, the two
ends of ``pipeline._SCAN_DISPATCH_BYTES`` — with production
``CDCParams()``, and the whole shard-mapped manifest and the dedup
probe/insert on a one- and a four-device mesh.  Nothing runs: a pass
says the chip's compiler accepts the program and how much device memory
it plans, never that it is right or fast.

What they have caught: every ``pallas_call`` under ``jax.shard_map``
needs ``vma`` on its ``out_shape``; a scan driver's ``(..., 4)`` u8
-> u32 bitcast took 5-9 GB of temporaries at 8-64 MiB rows and did not
fit the chip at 128 MiB.  What they did not catch, because a compile has
no clock: the four stride-4 slices that replaced that bitcast fit, and
were four gathers of 0.16 s a 64 MiB row each on the chip (PR 35), so
the relayout ahead of the scan kernel is now held to planning no gather.
Nor the RS product: ``MUL_TABLE[mat, shard]`` compiled and fitted for
seven PRs and was a gather of 82-97 ms a 3 MiB packfile on the chip, the
largest program of two cells' traces (PR 37), so ``rs_gf_matmul`` is held
to planning none either.  Nor the leaf pool's gather: ``leaf_cap`` byte
slices at byte offsets (``vmap(dynamic_slice)``) compiled for every PR
since the pool came, to a ``while`` of ``leaf_cap`` steps that took
1.8-3.5 us each on the chip, 0.52 s of the 0.76 s the device worked in a
``ref-1m.incr`` backup (PR 42), so ``pool_digest`` is held to planning no
loop over its lanes.  What that probe also taught: a gather is not slow
for being over ``u8``, it is slow for taking less than a row; two
gathers of whole 1 KiB ``u8`` rows are 7.5 ms at 164,864 lanes, and they
are what ``pool_digest`` now plans.

Only one process may load libtpu, and it keeps it until it exits, so
the topology is described inside a module-scoped fixture — never at
import, in a ``skipif`` or in ``parametrize`` — and everything built
from it is built in fixtures or tests of this one file.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from backuwup_tpu import defaults
from backuwup_tpu.ops import resident, scan_fused
from backuwup_tpu.ops.cdc_tpu import _HALO, TpuCdcScanner, _scan_segment
from backuwup_tpu.ops.blake3_tpu import _leaf_scan_pallas
from backuwup_tpu.ops.dedup_index import KEY_WORDS, _build_probe_fn
from backuwup_tpu.ops.digest_pool import leaf_capacity, pool_digest
from backuwup_tpu.ops.gear import CDCParams
from backuwup_tpu.ops.manifest_device import _mesh_scan_digest_fn, tier_plan
from backuwup_tpu.ops.pipeline import (
    _SCAN_DISPATCH_BYTES,
    DevicePipeline,
    _gather_digest,
)

GiB = 1 << 30
PARAMS = CDCParams()  # production 256 KiB / 1 MiB / 3 MiB
# (rows, row bytes): the two ends of the engine's dispatch budget
WIDE = (1, _SCAN_DISPATCH_BYTES)
MANY = (_SCAN_DISPATCH_BYTES >> 20, 1 << 20)
# the bucket `ref-1m.incr` dispatches twice a night: one 48-49 MiB file a row
CELL = (1, 64 << 20)
# smallest manifest bucket a production tree reaches: files just above
# min_size (smaller ones are one chunk and skip the scan), 8 rows
SMALLEST = (8, 2 * PARAMS.min_size)


@pytest.fixture(scope="module")
def topo():
    """A described (not attached) v5e 2x2; the persistent compile cache
    is off while it is in use — what is compiled for a described chip is
    written to the cache but cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def meshes(topo):
    return {n: Mesh(np.array(topo.devices[:n]), ("data",)) for n in (1, 4)}


def _caps(padded: int):
    pipe = DevicePipeline.__new__(DevicePipeline)
    pipe.params = PARAMS
    return pipe._caps(padded)


def _temp_bytes(lowered) -> int:
    return lowered.compile().memory_analysis().temp_size_in_bytes


def _planned_bytes(compiled) -> int:
    """Arguments, outputs and temporaries of a compiled program."""
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)


def _lower_scan(rows, width, one_chip):
    return scan_fused._fused_candidate_words_u32.lower(
        jax.ShapeDtypeStruct((rows, 31 + width), jnp.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip),
        mask_s=PARAMS.mask_s, mask_l=PARAMS.mask_l)


@pytest.mark.parametrize("rows,width", [WIDE, MANY, CELL],
                         ids=["wide", "many", "cell"])
def test_scan_kernel_compiles_at_dispatch_widths(one_chip, rows, width):
    # the XLA-side strip prep must stay a small multiple of the batch
    # (128 MiB): a bitcast form planned 9 GB here, then 16.5 GB
    assert _temp_bytes(_lower_scan(rows, width, one_chip)) < 1 * GiB


@pytest.mark.parametrize("rows,width", [WIDE, MANY, CELL],
                         ids=["wide", "many", "cell"])
def test_scan_relayout_plans_no_gather_and_fits(one_chip, rows, width):
    """The bytes reach the scan kernel by copies and one transpose: no
    gather (what a stride-4 slice of a ``u8`` row becomes on the v5e), and
    arguments, outputs and temporaries together under 4 GiB (a ``(1, N)
    u8`` argument alone is laid out at four times its bytes)."""
    compiled = _lower_scan(rows, width, one_chip).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and " gather(" not in hlo
    assert _planned_bytes(compiled) < 4 * GiB


def test_leaf_digest_kernel_compiles_at_dispatch_width(one_chip):
    rows, width = WIDE
    lanes = leaf_capacity(rows * width, rows * _caps(width)[2])
    lowered = jax.jit(_leaf_scan_pallas).lower(
        jax.ShapeDtypeStruct((lanes, 16, 16), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((lanes,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((lanes,), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((lanes,), jnp.int32, sharding=one_chip))
    assert _temp_bytes(lowered) < 1 * GiB


def test_pool_digest_compiles_at_dispatch_width(one_chip):
    rows, width = MANY
    chunks = rows * _caps(width)[2]
    lowered = pool_digest.lower(
        jax.ShapeDtypeStruct((rows * (31 + width) + 1024,), jnp.uint8,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((chunks,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((chunks,), jnp.int32, sharding=one_chip),
        leaf_cap=leaf_capacity(rows * width, chunks),
        tiers=tier_plan(PARAMS, rows * width, rows), pallas=True)
    assert _temp_bytes(lowered) < 2 * GiB


def _loop_bounds(hlo: str) -> set:
    """The constants that the conditions of the text's ``while`` loops
    compare their counters with: a loop of ``n`` steps shows ``n``."""
    bounds = set()
    for name in set(re.findall(r"condition=%([\w.\-]+)", hlo)):
        cond = re.search(r"^%" + re.escape(name) + r" \(.*?^\}", hlo,
                         re.M | re.S).group(0)
        bounds.update(int(c) for c in re.findall(r"constant\((\d+)\)", cond))
    return bounds


@pytest.mark.parametrize("rows,width,halo", [(1, 160 << 20, 0),
                                             (*CELL, _HALO)],
                         ids=["stream-160m", "cell"])
def test_pool_gather_plans_no_lane_loop_and_fits(one_chip, rows, width, halo):
    """``pool_digest`` at the two shapes ``ref-1m.incr`` runs: the long
    file's 160 MiB stream (``leaf_cap`` 164,864) and the ``(1, 64 MiB)``
    row inside the manifest program (66,048).  No ``while`` makes
    ``leaf_cap`` steps (the parent's gather did: 1.8-3.5 us a step on the
    chip), every gather over ``u8`` takes whole 1 KiB rows (a narrower
    one is the byte-at-a-time kind), and arguments, outputs and
    temporaries together stay under 2 GiB."""
    chunks = rows * _caps(width)[2]
    leaf_cap = leaf_capacity(rows * width, chunks)
    assert leaf_cap == {160 << 20: 164_864, 64 << 20: 66_048}[width]
    compiled = pool_digest.lower(
        jax.ShapeDtypeStruct((rows * (halo + width) + 1024,), jnp.uint8,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((chunks,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((chunks,), jnp.int32, sharding=one_chip),
        leaf_cap=leaf_cap, tiers=tier_plan(PARAMS, rows * width, rows),
        pallas=True).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo  # the leaf scan is in the program
    assert leaf_cap not in _loop_bounds(hlo)
    u8_gathers = [line for line in hlo.splitlines()
                  if re.search(r"= u8\[[^=]* gather\(", line)]
    assert u8_gathers and all(
        "slice_sizes={1,1024}" in line for line in u8_gathers)
    assert _planned_bytes(compiled) < 2 * GiB


@pytest.mark.parametrize("n_dev", [1, 4])
def test_mesh_manifest_compiles_with_the_pallas_kernels(meshes, n_dev):
    """The program the engine's one batch route runs: the scan kernel +
    select + leaf-pool digest with the Pallas leaf kernel, under
    ``shard_map`` with its varying-axes check on, handing dedup queries
    on."""
    mesh = meshes[n_dev]
    rows, width = SMALLEST
    per_shard = rows // n_dev
    s_cap, l_cap, cut_cap = _caps(width)
    fn = _mesh_scan_digest_fn(
        mesh, "data", PARAMS.min_size, PARAMS.desired_size, PARAMS.max_size,
        PARAMS.mask_s, PARAMS.mask_l, s_cap, l_cap, cut_cap, True,
        leaf_capacity(per_shard * width, per_shard * cut_cap),
        tier_plan(PARAMS, per_shard * width, per_shard), True, True)
    sharded = NamedSharding(mesh, P("data"))
    compiled = fn.lower(
        jax.ShapeDtypeStruct((rows, 31 + width), jnp.uint8, sharding=sharded),
        jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=sharded)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2  # scan + leaf
    assert compiled.memory_analysis().temp_size_in_bytes < 1 * GiB


@pytest.mark.parametrize("insert", [False, True], ids=["probe", "insert"])
@pytest.mark.parametrize("n_dev", [1, 4])
def test_dedup_program_compiles_at_default_capacity(meshes, n_dev, insert):
    mesh = meshes[n_dev]
    rows, width = MANY
    lanes = (rows // n_dev) * _caps(width)[2]  # one manifest batch's queries
    cap = defaults.DEDUP_SHARD_CAPACITY
    fn = _build_probe_fn(mesh, "data", cap, defaults.DEDUP_MAX_PROBES, insert)
    sharded = NamedSharding(mesh, P("data"))
    args = [
        jax.ShapeDtypeStruct((n_dev, cap, KEY_WORDS), jnp.uint32,
                             sharding=sharded),
        jax.ShapeDtypeStruct((n_dev, cap), jnp.uint32, sharding=sharded),
        jax.ShapeDtypeStruct((n_dev, lanes, KEY_WORDS), jnp.uint32,
                             sharding=sharded)]
    if insert:
        args.append(jax.ShapeDtypeStruct((n_dev, lanes), jnp.uint32,
                                         sharding=sharded))
    compiled = fn.lower(*args).compile()
    if n_dev > 1:  # queries ride the interconnect, table rows never move
        assert "all-gather" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def _scatter_updates(compiled) -> list:
    """How many updates each scatter of a compiled program applies (the
    v5e applies them one after the other, 8.7 ns each: PR 47)."""
    text = compiled.as_text()
    counts = []
    for m in re.finditer(r" scatter\(%[\w.-]+, %[\w.-]+, (%[\w.-]+)\)", text):
        dims = re.search(re.escape(m.group(1)) + r" = \w+\[([\d,]*)\]", text)
        counts.append(int(np.prod([int(d) for d in dims.group(1).split(",")
                                   if d])))
    return counts


@pytest.mark.parametrize("params,classes,k_cap", [
    pytest.param(CDCParams(16384, 65536, 196608, 18, 14),
                 ((64, 128), (256, 128)), 131072, id="64k-chunks"),
    pytest.param(CDCParams(), ((1024, 128), (2048, 32), (3072, 32)), 8192,
                 id="shipped-1m-chunks")])
def test_resident_stream_route_compiles_at_the_packers_segment(
        one_chip, params, classes, k_cap):
    """The streamed file's route (ops/resident.py) at the packer's 256 MiB
    segment, at the benchmark's 64 KiB chunks and at the shipped 1 MiB
    ones (rows of up to 3 MiB, a 3 MiB carry): the buffer's programs
    alias it in place, the scan slice is the 128 MiB program the route
    always ran, at the sparse capacity of either density, with no scatter
    of an update a candidate word (PR 47: one a 128-word block), and every
    class's gather+digest tile keeps its temporaries under a gibibyte
    beside the resident segment."""
    geo = resident.Geometry.of(params, TpuCdcScanner(params), 256 << 20)
    assert geo.classes == classes and geo.n_slices == 2
    assert geo.k_cap == k_cap

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    buf, i32 = shape((geo.size,), jnp.uint8), shape((), jnp.int32)
    in_place = [
        resident._next_resident.lower(buf, i32, i32, front=geo.front),
        resident._put_block.lower(
            buf, shape((geo.segment_bytes,), jnp.uint8), i32)]
    for lowered in in_place:
        mem = lowered.compile().memory_analysis()
        assert mem.alias_size_in_bytes == geo.size
        assert mem.temp_size_in_bytes < 1 << 20
    resident._resident_slice.lower(
        buf, i32, size=_HALO + geo.scan_slice).compile()
    scan = _scan_segment.lower(
        shape((_HALO + geo.scan_slice,), jnp.uint8), i32,
        shape((), jnp.uint32), shape((), jnp.uint32),
        k_cap=geo.k_cap).compile()
    assert scan.memory_analysis().temp_size_in_bytes < 1.25 * GiB
    updates = _scatter_updates(scan)
    assert updates and max(updates) <= geo.scan_slice // 32 // 128, updates
    for L, B in geo.classes:
        tile = _gather_digest.lower(
            buf, shape((2, geo.rows), jnp.int32), i32,
            shape((geo.rows, 8), jnp.uint32), B=B, L=L)
        assert _temp_bytes(tile) < 1 * GiB


def test_resident_stripe_route_compiles_at_a_sealed_packfiles_bucket(one_chip):
    """The send stage's resident route (erasure/resident.py) at RS 4+2
    and the 1 MiB bucket a 3 MiB packfile's shards fall in: the three
    programs that are the route's own (the heavy ones are
    ``rs_gf_matmul`` and ``digest_padded`` at shapes ``encode_shards``
    and ``digest_many`` compile too), each with temporaries of a few
    containers, not of a digest batch."""
    from backuwup_tpu.erasure import resident as stripe_resident

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    k, m, rows, count = 4, 2, 8, stripe_resident.COUNT
    bucket = stripe_resident.shard_bucket(768 << 10)
    assert bucket == 1 << 20
    span = stripe_resident._window_span(bucket)
    i32 = shape((), jnp.int32)
    stack = stripe_resident._shard_rows.lower(
        shape((1, k, bucket), jnp.uint8), shape((1, m, bucket), jnp.uint8),
        rows=rows)
    assert _temp_bytes(stack) < 16 << 20
    pieces = stripe_resident._window_pieces.lower(
        shape((rows, bucket), jnp.uint8),
        shape((k + m, 16), jnp.uint8), shape((rows, 8), jnp.uint32),
        shape((k + m, count, 2), jnp.int32),
        shape((k + m, count, 16), jnp.uint8), i32, span=span, L=256)
    assert _temp_bytes(pieces) < 16 << 20
    put = stripe_resident._put_digests.lower(
        shape(((k + m) * count, 8), jnp.uint32),
        shape((count, 8), jnp.uint32), i32)
    assert put.compile().memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("bucket", [1 << 20, 3 << 20], ids=["1MiB", "3MiB"])
@pytest.mark.parametrize("r", [2, 4], ids=["parity-2x4", "recovery-4x4"])
def test_rs_product_plans_no_gather_and_fits(one_chip, r, bucket):
    """``rs_gf_matmul`` at RS 4+2 as the send stage launches it (the
    parity block, ``(1, 4, Lb)``) and as a restore does (a ``(4, 4)``
    recovery matrix), at a sealed packfile's bucket and at the largest,
    which is no power of two: the matrix's bit blocks times the data's
    bit planes, so no operand is indexed by data, and neither the planes
    nor the ``int32`` sums reach HBM (read from the compile, PR 37: 0
    bytes of temporaries at every one of the four; a burst has several
    stripes in flight, so the 3 MiB bucket may never plan 256 MiB)."""
    from backuwup_tpu.erasure import rs_tpu

    compiled = rs_tpu._matmul_batched().lower(
        jax.ShapeDtypeStruct((8 * r, 32), jnp.int8, sharding=one_chip),
        jax.ShapeDtypeStruct((1, 4, bucket), jnp.uint8, sharding=one_chip)
    ).compile()
    assert " gather(" not in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 << 20
    # a (1, 4, Lb) u8 argument and a (1, r <= 4, Lb) result, four rows a
    # word: nothing padded, nothing copied beside them
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            < 8 * bucket + (1 << 20))
