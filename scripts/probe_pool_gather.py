#!/usr/bin/env python3
"""Probe: how the leaf pool's lanes get their bytes (PR 42).

``ops/digest_pool.pool_digest`` hands every 1 KiB BLAKE3 leaf of a batch
its bytes: lane ``j`` wants ``flat[off[j] : off[j] + 1024]`` as 256
little-endian words, zero from ``nbytes[j]`` on.  This script times the
candidate forms of that one step on the chip, each as a jitted program of
its own on random bytes, at the shapes the benchmark's cells run, and the
tree's whole ``pool_digest`` beside them.  A time is the device's: the
mean duration of the program's events on the ``XLA Modules`` line of a
traced pair of calls.  Every form is first held to a numpy rendering of
the contract at a small shape, and the tree's pool to ``blake3_cpu``.

    chiprun -- python scripts/probe_pool_gather.py          # the table
    JAX_PLATFORMS=cpu python scripts/probe_pool_gather.py --parity-only

Forms (``--forms``, default all):

* ``S``   the parent's: ``vmap(dynamic_slice(flat, off, 1024))``, which
          the v5e runs as a loop of ``leaf_cap`` steps;
* ``R``   the tree's form (``digest_pool._leaf_rows``): rows ``q`` and
          ``q + 1`` of the ``(R, 1024) u8`` view by two row gathers, the
          byte shift as ten ``where(bit, shifted, x)`` stages;
* ``T``   the same rows, transposed to lanes-minor words, the shift as
          a funnel and eight stages along the major axis;
* ``W``   the stream's words first (``(R, 256) u32``), two ``u32`` row
          gathers, funnel and eight stages along the row;
* ``H8``  two ``u8`` row gathers, XLA's word prep, then ``W``'s funnel
          and stages as one Pallas kernel over blocks of lanes;
* ``H``   ``W`` with that kernel: the stream's words once, two ``u32``
          row gathers, the Pallas shift kernel.

Results go to ``chiprun_out/probe_pool_gather.json`` and, as a table, to
stdout.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from backuwup_tpu.ops import digest_pool
from backuwup_tpu.ops.blake3_tpu import _bytes_to_words
from backuwup_tpu.ops.gear import CDCParams
from backuwup_tpu.ops.manifest_device import tier_plan

CH = digest_pool.CHUNK_LEN
PARAMS = CDCParams()
MiB = 1 << 20
HALO = 31


# --- the forms: (flat u8 (N,), off i32 (L,), nbytes i32 (L,)) -> (L, 256) u32


def _mask_bytes(data, nbytes):
    keep = jnp.arange(CH, dtype=jnp.int32)[None, :] < nbytes[:, None]
    return jnp.where(keep, data, jnp.uint8(0))


def _rows_words(rows_u8):
    return _bytes_to_words(rows_u8.reshape(-1, 16, 64)).reshape(-1, 256)


def _mask_words(words, nbytes, axis):
    i4 = 4 * jnp.arange(256, dtype=jnp.int32)
    i4, nbytes = (i4[None, :], nbytes[:, None]) if axis == 1 else (
        i4[:, None], nbytes[None, :])
    left = jnp.clip(nbytes - i4, 0, 4).astype(jnp.uint32)
    return words & jnp.where(
        left >= 4, jnp.uint32(0xFFFFFFFF),
        (jnp.uint32(1) << (left * 8)) - jnp.uint32(1))


def form_S(flat, off, nbytes):
    data = jax.vmap(lambda o: jax.lax.dynamic_slice(flat, (o,), (CH,)))(off)
    return _rows_words(_mask_bytes(data, nbytes))


def _u8_rows(flat, off):
    R = flat.shape[0] // CH
    D = flat[:R * CH].reshape(R, CH)
    q = off // CH
    return D[q], D[jnp.minimum(q + 1, R - 1)]


def form_R(flat, off, nbytes):
    return _rows_words(_mask_bytes(digest_pool._leaf_rows(flat, off), nbytes))


def _funnel_stages(A, B, s, axis):
    """``Z[i] = (A | B)[i + s // 4]`` with the bytes moved down by
    ``s % 4``, along ``axis`` of two (.., 256, ..) word arrays."""
    def along(x):
        return x[:, None] if axis == 1 else x[None, :]

    def take(x, lo, hi):
        return x[:, lo:hi] if axis == 1 else x[lo:hi]

    b8 = along(((s & 3) * 8).astype(jnp.uint32))
    up = (jnp.uint32(32) - b8) & jnp.uint32(31)

    def funnel(x, nxt_last):
        nxt = jnp.concatenate([take(x, 1, 256), nxt_last], axis=axis)
        return (x >> b8) | jnp.where(b8 > 0, nxt << up, jnp.uint32(0))

    A2 = funnel(A, take(B, 0, 1))
    B2 = funnel(B, jnp.zeros_like(take(B, 0, 1)))
    w = along(s >> 2)
    i = jnp.arange(256, dtype=jnp.int32)
    i = i[None, :] if axis == 1 else i[:, None]
    Z = jnp.where(i >= w, A2, B2)
    for bit in (128, 64, 32, 16, 8, 4, 2, 1):
        Z = jnp.where((w & bit) != 0, jnp.roll(Z, -bit, axis=axis), Z)
    return Z


def form_T(flat, off, nbytes):
    def words_t(rows_u8):  # (L, 1024) u8 -> (256, L) u32, lanes minor
        t = rows_u8.T.reshape(256, 4, -1).astype(jnp.uint32)
        return t[:, 0] | (t[:, 1] << 8) | (t[:, 2] << 16) | (t[:, 3] << 24)

    A, B = (words_t(x) for x in _u8_rows(flat, off))
    return _mask_words(_funnel_stages(A, B, off % CH, 0), nbytes, 0).T


def _stream_words(flat):
    """``(N,) u8`` -> ``(N // 1024, 256) u32`` little-endian words of whole
    rows.  Through the transpose on purpose: asked for as ``(R, 256, 4)``
    the v5e's compiler pads the minor 4 to 128 lanes and plans 21 GB at a
    160 MiB stream."""
    R = flat.shape[0] // CH
    t = flat[:R * CH].reshape(R, CH).T.reshape(256, 4, R).astype(jnp.uint32)
    return (t[:, 0] | (t[:, 1] << 8) | (t[:, 2] << 16) | (t[:, 3] << 24)).T


def _u32_rows(flat, off):
    W = _stream_words(flat)
    q = off // CH
    return W[q], W[jnp.minimum(q + 1, W.shape[0] - 1)]


def form_W(flat, off, nbytes):
    A, B = _u32_rows(flat, off)
    return _mask_words(_funnel_stages(A, B, off % CH, 1), nbytes, 1)


def _shift_kernel(s_ref, n_ref, a_ref, b_ref, o_ref):
    """``_funnel_stages`` and ``_mask_words`` on a block of lanes in
    VMEM: the rows are read once and the words written once."""
    from jax.experimental.pallas import tpu as pltpu

    a, b, s = a_ref[...], b_ref[...], s_ref[...]
    col = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    b8 = ((s & 3) * 8).astype(jnp.uint32)
    up = (jnp.uint32(32) - b8) & jnp.uint32(31)
    last = col == 255
    ra, rb = pltpu.roll(a, 255, axis=1), pltpu.roll(b, 255, axis=1)
    zero = jnp.uint32(0)
    a2 = (a >> b8) | jnp.where(b8 > 0, jnp.where(last, rb, ra) << up, zero)
    b2 = (b >> b8) | jnp.where((b8 > 0) & ~last, rb << up, zero)
    w = s >> 2
    z = jnp.where(col >= w, a2, b2)
    for bit in (128, 64, 32, 16, 8, 4, 2, 1):
        z = jnp.where((w & bit) != 0, pltpu.roll(z, 256 - bit, axis=1), z)
    left = jnp.clip(n_ref[...] - 4 * col, 0, 4).astype(jnp.uint32)
    o_ref[...] = z & jnp.where(
        left >= 4, jnp.uint32(0xFFFFFFFF),
        (jnp.uint32(1) << (left * 8)) - jnp.uint32(1))


def _shift_rows(A, B, s, nbytes):
    from jax.experimental import pallas as pl

    lanes = A.shape[0]
    T = min(512, lanes)
    col = pl.BlockSpec((T, 1), lambda i: (i, 0))
    row = pl.BlockSpec((T, 256), lambda i: (i, 0))
    return pl.pallas_call(
        _shift_kernel, grid=(pl.cdiv(lanes, T),),
        in_specs=[col, col, row, row], out_specs=row,
        out_shape=jax.ShapeDtypeStruct((lanes, 256), jnp.uint32),
        interpret=_INTERPRET, name="pool_leaf_shift",
    )(s[:, None], nbytes[:, None], A, B)


def form_H8(flat, off, nbytes):
    A, B = (_rows_words(x) for x in _u8_rows(flat, off))
    return _shift_rows(A, B, off % CH, nbytes)


def form_H(flat, off, nbytes):
    return _shift_rows(*_u32_rows(flat, off), off % CH, nbytes)


def part_words(flat, off, nbytes):
    """Not a form: the stream's words alone (what ``W`` and ``H`` pay
    ahead of their gathers)."""
    return _stream_words(flat)


def part_gather32(flat, off, nbytes):
    """Not a form: ``H`` without its shift kernel."""
    A, B = _u32_rows(flat, off)
    return A ^ B


def part_gather8(flat, off, nbytes):
    """Not a form: the two ``u8`` row gathers of ``R``, ``T``, ``H8``."""
    A, B = _u8_rows(flat, off)
    return A ^ B


FORMS = {"S": form_S, "R": form_R, "T": form_T, "W": form_W, "H8": form_H8,
         "H": form_H}
PARTS = {"words": part_words, "gather32": part_gather32,
         "gather8": part_gather8}
_INTERPRET = jax.devices()[0].platform != "tpu"


# --- the shapes ------------------------------------------------------------


def _consecutive(rng, start, end, lo, hi):
    """Chunks end to end from ``start`` until ``end``, lengths in [lo, hi]."""
    out, cur = [], start
    while cur < end:
        ln = min(int(rng.integers(lo, hi + 1)), end - cur)
        out.append((cur, ln))
        cur += ln
    return out


def shape_stream(rng, mib):
    """The long file's pool (``pipeline._pool_program``): one stream."""
    padded = mib * MiB
    chunks = _consecutive(rng, 0, padded - int(rng.integers(0, MiB)),
                          PARAMS.min_size, PARAMS.max_size)
    return padded + CH, padded, padded // PARAMS.min_size + 1, 1, chunks


def shape_rows(rng, rows, row_mib, lo, hi):
    """A manifest batch (``scan_digest_batch_pool``): ``rows`` files, a
    row each behind its 31-byte halo."""
    padded = row_mib * MiB
    chunks = []
    for r in range(rows):
        base = r * (HALO + padded) + HALO
        n = int(rng.integers(padded // 2, padded + 1))
        chunks += _consecutive(rng, base, base + n, lo, hi)
    cap = rows * (padded // PARAMS.min_size + 1)
    return rows * (HALO + padded) + CH, rows * padded, cap, rows, chunks[:cap]


def shape_tiny(rng):
    """Not a cell's: thousands of one- and two-lane chunks anywhere."""
    padded, cap = 4 * MiB, 2048
    offs = rng.integers(0, padded - 2048, cap)
    lens = rng.integers(1, 2049, cap)
    return padded + CH, padded, cap, 1, list(zip(offs.tolist(), lens.tolist()))


SHAPES = {
    "stream-160m": lambda rng: shape_stream(rng, 160),
    "row-64m": lambda rng: shape_rows(rng, 1, 64, PARAMS.min_size,
                                      PARAMS.max_size),
    "rows-4x1m": lambda rng: shape_rows(rng, 4, 1, PARAMS.min_size, MiB),
    "rows-8x1m": lambda rng: shape_rows(rng, 8, 1, PARAMS.min_size, MiB),
    "rows-16x1m": lambda rng: shape_rows(rng, 16, 1, PARAMS.min_size, MiB),
    "tiny-lanes-4m": shape_tiny,
    "small": lambda rng: shape_rows(rng, 2, 1, 1, 70_000),
}


def lane_plan(chunks, cap, leaf_cap):
    """``pool_digest``'s leaf plan in numpy: the chunk table padded to
    ``cap`` and every lane's byte offset and byte count."""
    offs = np.zeros(cap, np.int32)
    lens = np.zeros(cap, np.int32)
    offs[:len(chunks)] = [o for o, _ in chunks]
    lens[:len(chunks)] = [ln for _, ln in chunks]
    lv = -(-lens.astype(np.int64) // CH)
    owner = np.repeat(np.arange(cap), lv)[:leaf_cap]
    k = np.arange(len(owner)) - (np.cumsum(lv) - lv)[owner]
    off = np.zeros(leaf_cap, np.int32)
    nbytes = np.zeros(leaf_cap, np.int32)
    off[:len(owner)] = offs[owner] + k * CH
    nbytes[:len(owner)] = np.clip(lens[owner] - k * CH, 0, CH)
    return offs, lens, off, nbytes


def words_numpy(flat, off, nbytes):
    out = np.zeros((len(off), CH), np.uint8)
    for j in np.flatnonzero(nbytes):
        out[j, :nbytes[j]] = flat[off[j]:off[j] + nbytes[j]]
    return out.view("<u4")


# --- device seconds --------------------------------------------------------


def module_seconds(trace_dir, program):
    """Mean seconds of ``jit_<program>``'s events on the device planes'
    ``XLA Modules`` line, and how many there were."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    durs = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            durs += [ev.duration_ns / 1e9 for ev in line.events
                     if ev.name.split("(")[0] == "jit_" + program]
    return (sum(durs) / len(durs) if durs else None), len(durs)


def timed(fn, program, args, calls=2):
    """Device seconds a call of ``fn`` (compiled ahead), traced."""
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(calls):
                jax.block_until_ready(fn(*args))
        return module_seconds(d, program)


def named(fn, name):
    def program(*args):
        return fn(*args)
    program.__name__ = name
    return jax.jit(program)


# --- main ------------------------------------------------------------------


def parity(forms, seed):
    rng = np.random.default_rng(seed)
    n, padded, cap, rows, chunks = SHAPES["small"](rng)
    leaf_cap = digest_pool.leaf_capacity(padded, cap)
    flat = rng.integers(0, 256, n, dtype=np.uint8)
    flat[-CH:] = 0
    _offs, _lens, off, nbytes = lane_plan(chunks, cap, leaf_cap)
    want = words_numpy(flat, off, nbytes)
    bad = []
    for name in forms:
        got = np.asarray(jax.jit(FORMS[name])(
            jnp.asarray(flat), jnp.asarray(off), jnp.asarray(nbytes)))
        if not (got == want).all():
            bad.append(name)
    # the tree's whole pool against blake3_cpu (raises where it differs)
    digest_pool._pool_digest_probe(not _INTERPRET)
    return bad


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--parts", default=",".join(PARTS))
    ap.add_argument("--shapes", default=",".join(
        s for s in SHAPES if s != "small"))
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--parity-only", action="store_true")
    ap.add_argument("--out", default="chiprun_out/probe_pool_gather.json")
    args = ap.parse_args()
    forms = [f for f in args.forms.split(",") if f]
    parts = [p for p in args.parts.split(",") if p]
    dev = jax.devices()[0]
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "seed": args.seed, "rows": []}
    bad = parity(forms, args.seed)
    result["parity_failed"] = bad
    print("parity against numpy at the small shape:",
          "all equal" if not bad else f"DIFFER: {bad}", flush=True)
    if bad:
        return 1
    if args.parity_only:
        return 0
    if dev.platform != "tpu":
        print("no TPU: a time here would be the CPU's; stopping")
        return 1
    for shape in args.shapes.split(","):
        rng = np.random.default_rng(args.seed)
        n, padded, cap, rows, chunks = SHAPES[shape](rng)
        leaf_cap = digest_pool.leaf_capacity(padded, cap)
        offs, lens, off, nbytes = lane_plan(chunks, cap, leaf_cap)
        flat = jnp.asarray(rng.integers(0, 256, n, dtype=np.uint8))
        lane_args = (flat, jnp.asarray(off), jnp.asarray(nbytes))
        row = {"shape": shape, "flat_bytes": n, "leaf_cap": leaf_cap,
               "chunks": len(chunks), "lanes_used": int((nbytes > 0).sum()),
               "seconds": {}}
        for name, fn in ([(f, FORMS[f]) for f in forms]
                         + [(p, PARTS[p]) for p in parts]):
            try:
                secs, n_ev = timed(named(fn, "gather_" + name),
                                   "gather_" + name, lane_args)
            except Exception as e:  # a form the chip's compiler refuses
                secs, n_ev = None, 0
                row.setdefault("errors", {})[name] = str(e)[:400]
            row["seconds"][name] = secs
            print(f"{shape:14s} {name:9s} "
                  f"{'refused' if secs is None else f'{secs:.6f} s'} "
                  f"({n_ev} events)", flush=True)
        pool = named(lambda f, o, ln: digest_pool.pool_digest.__wrapped__(
            f, o, ln, leaf_cap=leaf_cap,
            tiers=tier_plan(PARAMS, padded, rows), pallas=True),
            "pool_whole")
        secs, n_ev = timed(pool, "pool_whole",
                           (flat, jnp.asarray(offs), jnp.asarray(lens)))
        row["seconds"]["pool_digest"] = secs
        print(f"{shape:14s} {'pool':9s} {secs:.6f} s ({n_ev} events)",
              flush=True)
        result["rows"].append(row)
        del flat, lane_args
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    cols = forms + parts + ["pool_digest"]
    print("\n| shape | leaf_cap | " + " | ".join(cols) + " |")
    print("|---|---|" + "---|" * len(cols))
    for row in result["rows"]:
        cells = ["refused" if row["seconds"][c] is None
                 else f"{row['seconds'][c] * 1e3:.2f}" for c in cols]
        print(f"| {row['shape']} | {row['leaf_cap']} | "
              + " | ".join(cells) + " |")
    print("(device milliseconds a call)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
