"""Zero-round-trip manifest: scan -> select -> gather -> digest on device.

A host-tiled driver downloads each segment's cut list before it can
stage digest tiles, so every batch pays two serialized host round trips
while the device sits idle.  The reference has the same structure
collapsed onto one CPU (``dir_packer.rs:246-311``): chunk, then hash,
then index — all in one address space.  The TPU answer is to keep the
*data plane* entirely in HBM:

1. :func:`backuwup_tpu.ops.cdc_tpu.scan_select_batch` produces packed
   per-row cut lists on device (Mosaic strip scan + on-device selection).
2. Chunk meta (offset, length) is DERIVED on device from the cut lists —
   no host assembly.
3. Every chunk's 1 KiB leaves run through one flat leaf pool and 2-3
   small tree tiers (:func:`backuwup_tpu.ops.digest_pool.pool_digest`),
   and the root chaining values land in one dense ``(B*cut_cap, 8)``
   accumulator.
4. The caller downloads ``(cuts, digests, overflow)`` once — for a whole
   run of batches — and assembles manifests host-side.

Tier capacities are sized from the analytic chunk-length distribution
(:func:`tier_plan`); a tier overflow (data far from that distribution,
e.g. adversarial all-max chunks) sets a flag a shard and the affected
shard's rows fall back to the host-tiled path, preserving bit-exact
output.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .cdc_tpu import _HALO, scan_select_batch
from .gear import CDCParams

CHUNK_LEN = 1024


@functools.lru_cache(maxsize=16)
def _length_histogram(params: CDCParams) -> Tuple[float, Tuple[float, ...]]:
    """(mean_chunk_len, fraction per pow2 leaf class), computed
    analytically from the two-phase geometric cut process.

    On uniform data the gear hash at each position is iid uniform, so a
    chunk survives past length ``x`` with probability
    ``(1-p_s)^a (1-p_l)^b`` where ``a``/``b`` count positions seen by the
    strict/loose windows and ``p = 2^-mask_bits``; the forced cut at
    ``max_size`` truncates the tail.  Exact for random corpora; real
    corpora that deviate far enough to overflow the tiers' capacities
    fall back to the host-tiled path (still bit-exact), so this estimate
    only steers throughput, never correctness.
    """
    p_s = 2.0 ** -params.mask_s_bits
    p_l = 2.0 ** -params.mask_l_bits
    lens = np.arange(params.min_size, params.max_size + 1, dtype=np.float64)
    # positions examined by each phase for a chunk of length L (cuts land
    # at L-1): strict window spans [min-1, desired-2], loose beyond
    a = np.clip(lens - params.min_size + 1, 0,
                params.desired_size - params.min_size)
    b = np.clip(lens - params.desired_size + 1, 0, None)
    surv = (1 - p_s) ** a * (1 - p_l) ** b
    pmf = np.empty_like(surv)
    pmf[:-1] = surv[:-1] - surv[1:]
    pmf[-1] = surv[-1]  # forced cut at max_size absorbs the tail
    pmf = np.maximum(pmf, 0)
    pmf /= pmf.sum()
    mean = float((lens * pmf).sum())
    classes = class_leaf_sizes(params)
    leaves = -(-lens // CHUNK_LEN)
    fracs = []
    for i, c in enumerate(classes):
        lo = classes[i - 1] if i else 0
        fracs.append(float(pmf[(leaves > lo) & (leaves <= c)].sum()))
    return mean, tuple(fracs)


@functools.lru_cache(maxsize=16)
def class_leaf_sizes(params: CDCParams) -> Tuple[int, ...]:
    """Linear leaf-count class grid covering [1, max chunk leaves].

    ~12 classes bound per-chunk padding waste to one class step (~8% of
    ``max_size``) — pow2 classes measured ~2x padded-digest overcompute
    because most mass lands just above a boundary.
    """
    max_leaves = -(-params.max_size // CHUNK_LEN)
    step = max(8, -(-max_leaves // 12))
    step = -(-step // 8) * 8  # aligned steps keep tile shapes friendly
    out = list(range(step, max_leaves + 1, step))
    if not out or out[-1] != max_leaves:
        out.append(max_leaves)
    return tuple(out)


def _chunk_meta(packed: jnp.ndarray, row_len: int):
    """Packed cut rows -> flat per-chunk (abs offset, length, valid).

    Derived entirely on device.  Rows whose scan/select overflowed carry
    garbage cut lists; the host re-runs those rows on the oracle anyway,
    so their chunks are masked out here — otherwise one bad row would
    consume digest capacities and could flag the WHOLE batch overflowed.
    """
    B = packed.shape[0]
    cut_cap = packed.shape[1] - 2
    n_cuts = packed[:, 1]  # (B,)
    ends = packed[:, 2:]   # (B, cut_cap) inclusive ends, -1 padded
    offs = jnp.concatenate(
        [jnp.zeros((B, 1), dtype=ends.dtype), ends[:, :-1] + 1], axis=1)
    lens = ends - offs + 1
    valid = (jnp.arange(cut_cap, dtype=jnp.int32)[None, :]
             < n_cuts[:, None])  # (B, cut_cap)
    row_ok = packed[:, 0] == 0  # (B,)
    valid = valid & row_ok[:, None]
    lens = jnp.where(valid, lens, 0)
    # absolute byte offset of each chunk in the flattened batch buffer
    row_base = (jnp.arange(B, dtype=jnp.int32) * row_len + _HALO)[:, None]
    return ((row_base + offs).reshape(-1), lens.reshape(-1),
            valid.reshape(-1))


@functools.lru_cache(maxsize=64)
def tier_plan(params: CDCParams, total_bytes: int,
              n_rows: int) -> Tuple[Tuple[int, int], ...]:
    """((leaf_span, chunk_cap), ...) tree tiers for the leaf-pool digest.

    Chunk-count expectations come from the analytic length histogram
    (:func:`_length_histogram`), re-binned onto the 2-3 geometric tier
    spans (tree work is ~1/16 of leaf work, so coarse spans cost a few
    percent where payload-level class tiles could not afford them).
    Class bins that straddle a tier edge only blur the capacity estimate
    — overflow still cascades and, at the terminus, falls back
    bit-exactly.
    """
    from .digest_pool import tier_caps, tier_spans

    mean_len, fracs = _length_histogram(params)
    classes = class_leaf_sizes(params)
    spans = tier_spans(-(-params.max_size // CHUNK_LEN))
    return tier_caps(spans, tuple(zip(classes, fracs)),
                     total_bytes / max(mean_len, 1.0), n_rows)


@functools.partial(jax.jit, static_argnames=(
    "min_size", "desired_size", "max_size", "mask_s", "mask_l",
    "s_cap", "l_cap", "cut_cap", "fused", "leaf_cap", "tiers",
    "pallas_digest"))
def scan_digest_batch_pool(buf_d: jnp.ndarray, nv_b: jnp.ndarray, *,
                           min_size: int, desired_size: int, max_size: int,
                           mask_s: int, mask_l: int, s_cap: int, l_cap: int,
                           cut_cap: int, fused: bool, leaf_cap: int,
                           tiers: Tuple[Tuple[int, int], ...],
                           pallas_digest: bool = False):
    """One resident ``(B, _HALO+P)`` batch -> (packed cuts, digests, ovf).

    Everything stays on device: ``packed`` is ``scan_select_batch``'s
    ``(B, 2+cut_cap)`` cut rows, ``digests`` is ``(B*cut_cap, 8)`` u32
    root chaining values addressed by ``row*cut_cap + chunk``, ``ovf`` is
    ``(1,)`` i32 — the number of chunks the tier cascade could not place
    (nonzero means the caller must fall back).  The digest stage is ONE
    flat leaf scan + 2-3 tiny tree tiles
    (:func:`backuwup_tpu.ops.digest_pool.pool_digest`).
    """
    from .digest_pool import pool_digest

    row_len = buf_d.shape[1]
    packed = scan_select_batch(
        buf_d, nv_b, min_size=min_size, desired_size=desired_size,
        max_size=max_size, mask_s=mask_s, mask_l=mask_l,
        s_cap=s_cap, l_cap=l_cap, cut_cap=cut_cap, fused=fused)
    abs_offs, flat_lens, flat_valid = _chunk_meta(packed, row_len)
    flat = jnp.pad(buf_d.reshape(-1), (0, CHUNK_LEN))
    acc, ovf = pool_digest(
        flat, abs_offs, jnp.where(flat_valid, flat_lens, 0),
        leaf_cap=leaf_cap, tiers=tiers, pallas=pallas_digest)
    return packed, acc, ovf


@functools.lru_cache(maxsize=32)
def _mesh_scan_digest_fn(mesh, axis: str, min_size: int, desired_size: int,
                         max_size: int, mask_s: int, mask_l: int, s_cap: int,
                         l_cap: int, cut_cap: int, fused: bool, leaf_cap: int,
                         tiers: Tuple[Tuple[int, int], ...],
                         pallas_digest: bool, emit_queries: bool):
    """Compile the shard-mapped leaf-pool manifest program for one mesh.

    Each shard runs the SAME jitted :func:`scan_digest_batch_pool` body
    over its contiguous slice of the row axis — per-shard leaf pool,
    per-shard tier cascade, per-shard overflow flag.  ``out_specs``
    concatenate shard outputs along that axis, so the global ``packed``
    and ``acc`` keep the single-device addressing (``row*cut_cap+chunk``
    in batch row order) while ``ovf`` widens from ``(1,)`` to ``(D,)``:
    one flag PER SHARD, so adversarial data only re-runs the affected
    shard's rows on the host-tiled path, not the whole batch.

    With ``emit_queries`` each shard also slices its accumulator into a
    ``(1, bs*cut_cap, 4)`` dedup query slab
    (:func:`..dedup_index.queries_from_cvs`), giving a global
    ``(D, bs*cut_cap, 4)`` array already laid out for
    ``ShardedDedupIndex.insert_device`` — fingerprints flow
    manifest -> dedup probe without ever leaving the mesh.
    """
    from jax.sharding import PartitionSpec as P

    from .dedup_index import queries_from_cvs

    def shard_fn(buf_d, nv_b):
        packed, acc, ovf = scan_digest_batch_pool(
            buf_d, nv_b, min_size=min_size, desired_size=desired_size,
            max_size=max_size, mask_s=mask_s, mask_l=mask_l, s_cap=s_cap,
            l_cap=l_cap, cut_cap=cut_cap, fused=fused, leaf_cap=leaf_cap,
            tiers=tiers, pallas_digest=pallas_digest)
        if emit_queries:
            return packed, acc, ovf, queries_from_cvs(acc)[None]
        return packed, acc, ovf

    n_out = 4 if emit_queries else 3
    mapped = jax.shard_map(shard_fn, mesh=mesh, in_specs=(P(axis), P(axis)),
                       out_specs=tuple([P(axis)] * n_out))
    return jax.jit(mapped)


def scan_digest_batch_pool_mesh(buf_d, nv_b, *, mesh, axis: str,
                                min_size: int, desired_size: int,
                                max_size: int, mask_s: int, mask_l: int,
                                s_cap: int, l_cap: int, cut_cap: int,
                                fused: bool, leaf_cap: int,
                                tiers: Tuple[Tuple[int, int], ...],
                                pallas_digest: bool = False,
                                emit_queries: bool = False,
                                lower: bool = False):
    """Mesh twin of :func:`scan_digest_batch_pool` — same contract,
    data-parallel over the row axis with ``shard_map``.

    ``buf_d``/``nv_b`` must be sharded ``P(axis)`` over a row count
    divisible by the mesh size; ``leaf_cap``/``tiers`` are PER-SHARD
    capacities (sized for ``B/D`` rows).  Returns
    ``(packed, acc, ovf[, queries])`` where ``ovf`` is the ``(D,)``
    per-shard overflow vector.  Bit-identical to the single-device path:
    a shard sees exactly the rows a ``B/D``-row single-device batch would,
    and every kernel is row-independent.  With ``lower``
    the two arguments are shapes (``jax.ShapeDtypeStruct``) and the
    program is traced and lowered for them, not run
    (``jax.stages.Lowered``).
    """
    fn = _mesh_scan_digest_fn(mesh, axis, min_size, desired_size, max_size,
                              mask_s, mask_l, s_cap, l_cap, cut_cap, fused,
                              leaf_cap, tiers, pallas_digest, emit_queries)
    return (fn.lower if lower else fn)(buf_d, nv_b)
