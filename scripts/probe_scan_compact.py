#!/usr/bin/env python3
"""Probe: how the scan compacts its candidate words (PR 47).

``ops/cdc_tpu._scan_segment`` hashes a slice, packs its candidate bits
32:1 into ``u32`` words and hands the host the nonzero words alone:
``widx`` their indices in ascending order (``-1`` from the true count
on), ``wl`` / ``ws`` the loose and strict words there, ``nz_words`` the
true count.  This script times the candidate forms of that one step on
the chip, each as a jitted program of its own over the words of random
bytes (``alone``) and inside the whole scan program from the bytes
(``scan``), at a ``(31 + 128 MiB)`` slice and **both densities the
benchmark's cells run**: ``mask_l_bits`` 18 (``dump-1m``, ``ref-1m``:
~512 nonzero words a slice, ``k_cap`` 8,192) and 14 (``vm-64k``: ~8,192
a slice, ``k_cap`` 131,072).  A time is the device's: the mean duration
of the program's events on the ``XLA Modules`` line of a traced pair of
calls.  Every form is first held to a numpy rendering of the contract
at small shapes.

    chiprun -- python scripts/probe_scan_compact.py          # the table
    JAX_PLATFORMS=cpu python scripts/probe_scan_compact.py --parity-only

Forms (``--forms``, default all):

* ``P``   the parent's: ``jnp.nonzero(words_l != 0, size=k_cap)``, in
          this JAX ``cumsum(bincount(cumsum(mask), length=size))``: a
          scatter-add of one update a word;
* ``B``   ``scan_select_batch.compact_words``' block pyramid: 128-word
          any-flags, ``nonzero`` of the blocks at a 16-fold cap, the
          hit blocks' words gathered, ``nonzero`` of those;
* ``G``   gather-side: a count a 128-word block, their exclusive
          cumsum, each non-empty block's id scattered at its first
          output slot (one update a *block*) and spread by a running
          maximum, each slot's block fetched as a row of loose and a
          row of strict words, the slot's word picked by its rank along
          the row (a ``cumsum`` over 128 lanes and a compare);
* ``T``   the tree's form (``cdc_tpu._compact_words``): G with the rank
          along the row as a product with a triangle of ones on the MXU;
* ``Gs``  G with the rank as seven shifted adds along the row (the
          v5e's compiler takes 20-29 s over G's ``cumsum`` of 128 lanes
          at 8,192 rows and under a second over either of these);
* ``Ge``  G with the strict words fetched one element a slot at
          ``widx`` (one row gather, one element gather);
* ``G2``  G with loose and strict blocks side by side in one
          ``(blocks, 256)`` array and one row gather;
* ``G32`` G at 32-word blocks (four times the updates, a quarter of
          each row);
* ``M``   two levels of bitmap, no rows: a bit a word in 131,072 meta
          words, ``nonzero`` of those (one update a *meta word*), each
          hit meta word's set bits spread over its slots by the same
          scatter and running maximum, the ``t``-th set bit by popcount
          bisection, then three element gathers a slot.

Results go to ``chiprun_out/probe_scan_compact.json`` and, as a table,
to stdout.
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

from backuwup_tpu.ops import cdc_tpu
from backuwup_tpu.ops.cdc_tpu import (
    _HALO,
    TpuCdcScanner,
    _candidate_words,
    _hash_ext_fast,
)
from backuwup_tpu.ops.gear import CDCParams

MiB = 1 << 20
# the cells' two densities: (label, params)
DENSITIES = {
    "1m-chunks (mask_l_bits 18)": CDCParams(),
    "64k-chunks (mask_l_bits 14)": CDCParams.from_desired(64 * 1024),
}


# --- the forms: (words_l u32 (W,), words_s u32 (W,), k_cap) ->
#     (widx i32 (k_cap,), wl u32 (k_cap,), ws u32 (k_cap,), nz_words i32)


def _at(words_l, words_s, widx):
    safe = jnp.clip(widx, 0, words_l.shape[0] - 1)
    return words_l[safe], words_s[safe]


def form_P(words_l, words_s, k_cap):
    nz = words_l != 0
    (widx,) = jnp.nonzero(nz, size=k_cap, fill_value=-1)
    return (widx, *_at(words_l, words_s, widx),
            jnp.sum(nz.astype(jnp.int32)))


def form_B(words_l, words_s, k_cap):
    n = words_l.shape[0]
    blk = 128
    nblk = n // blk
    b_cap = min(nblk, k_cap)  # 16 times the blocks expected to be hit
    wl2 = words_l.reshape(nblk, blk)
    nz2 = wl2 != 0
    any_b = jnp.any(nz2, axis=1)
    (bidx,) = jnp.nonzero(any_b, size=b_cap, fill_value=nblk)
    in_b = (bidx < nblk)[:, None]
    sub = jnp.where(in_b, wl2[jnp.clip(bidx, 0, nblk - 1)],
                    jnp.uint32(0)).reshape(-1)
    sub_widx = (bidx[:, None] * blk
                + jnp.arange(blk, dtype=jnp.int32)[None, :]).reshape(-1)
    (wsel,) = jnp.nonzero(sub != 0, size=k_cap, fill_value=sub.shape[0])
    ok = wsel < sub.shape[0]
    widx = jnp.where(ok, sub_widx[jnp.clip(wsel, 0, sub.shape[0] - 1)], -1)
    total = jnp.sum(nz2.astype(jnp.int32))
    # a block-level overflow must read as the callers' one signal
    over = jnp.sum(any_b.astype(jnp.int32)) > b_cap
    return (widx, *_at(words_l, words_s, widx),
            jnp.where(over, jnp.maximum(total, k_cap + 1), total))


def _block_slots(cnt, k_cap):
    """Each output slot's source block and its rank inside it, from the
    blocks' counts: ``(bid, t, total)``."""
    nblk = cnt.shape[0]
    off = jnp.cumsum(cnt) - cnt
    first = jnp.zeros(k_cap, jnp.int32).at[
        jnp.where(cnt > 0, off, k_cap)].max(
            jnp.arange(1, nblk + 1, dtype=jnp.int32), mode="drop")
    j = jnp.arange(k_cap, dtype=jnp.int32)
    bid = jnp.maximum(jax.lax.cummax(first) - 1, 0)
    t = j - jax.lax.cummax(jnp.where(first > 0, j, 0))
    return bid, t, jnp.sum(cnt)


def _pick(rows_nz, t, rank="cumsum"):
    """One-hot of each row's ``t``-th nonzero lane."""
    if rank == "cumsum":
        r = jnp.cumsum(rows_nz, axis=1, dtype=jnp.int32)
    else:
        r = rows_nz.astype(jnp.int32)
        s = 1
        while s < r.shape[1]:
            r = r + jnp.pad(r, ((0, 0), (s, 0)))[:, :-s]
            s *= 2
    return rows_nz & (r == (t + 1)[:, None])


def _form_G(words_l, words_s, k_cap, *, blk=128, rank="cumsum", strict="row"):
    n = words_l.shape[0]
    nblk = n // blk
    wl2 = words_l.reshape(nblk, blk)
    ws2 = words_s.reshape(nblk, blk)
    cnt = jnp.sum(wl2 != 0, axis=1, dtype=jnp.int32)
    bid, t, total = _block_slots(cnt, k_cap)
    if strict == "side":
        both = jnp.concatenate([wl2, ws2], axis=1)[bid]
        rows_l, rows_s = both[:, :blk], both[:, blk:]
    else:
        rows_l = wl2[bid]
        rows_s = ws2[bid] if strict == "row" else None
    pick = _pick(rows_l != 0, t, rank)
    lane = jnp.argmax(pick, axis=1).astype(jnp.int32)
    ok = jnp.arange(k_cap, dtype=jnp.int32) < total
    widx = jnp.where(ok, bid * blk + lane, -1)

    def picked(rows, words):
        got = jnp.sum(jnp.where(pick, rows, jnp.uint32(0)), axis=1,
                      dtype=jnp.uint32)
        return jnp.where(ok, got, words[0])

    wl = picked(rows_l, words_l)
    ws = (picked(rows_s, words_s) if rows_s is not None
          else words_s[jnp.clip(widx, 0, n - 1)])
    return widx, wl, ws, total


def form_G(words_l, words_s, k_cap):
    return _form_G(words_l, words_s, k_cap)


def form_Gs(words_l, words_s, k_cap):
    return _form_G(words_l, words_s, k_cap, rank="shift")


def form_Ge(words_l, words_s, k_cap):
    return _form_G(words_l, words_s, k_cap, strict="element")


def form_G2(words_l, words_s, k_cap):
    return _form_G(words_l, words_s, k_cap, strict="side")


def form_G32(words_l, words_s, k_cap):
    return _form_G(words_l, words_s, k_cap, blk=32)


def _popcount(x):
    return jax.lax.population_count(x).astype(jnp.int32)


def _nth_set_bit(x, t):
    """Bit index of the ``t``-th (0-based) set bit of each ``u32``."""
    pos = jnp.zeros(x.shape, jnp.int32)
    for width in (16, 8, 4, 2, 1):
        low = _popcount((x >> pos.astype(jnp.uint32))
                        & jnp.uint32((1 << width) - 1))
        up = t >= low
        t = jnp.where(up, t - low, t)
        pos = jnp.where(up, pos + width, pos)
    return pos


def form_M(words_l, words_s, k_cap):
    n = words_l.shape[0]
    nz = words_l != 0
    meta = cdc_tpu._pack_bits(nz)                     # a bit a word
    m_cap = min(meta.shape[0], k_cap)
    (midx,) = jnp.nonzero(meta != 0, size=m_cap, fill_value=meta.shape[0])
    mword = jnp.where(midx < meta.shape[0],
                      meta[jnp.clip(midx, 0, meta.shape[0] - 1)],
                      jnp.uint32(0))
    slot, t, _ = _block_slots(_popcount(mword), k_cap)
    bit = _nth_set_bit(mword[slot], t)
    total = jnp.sum(nz.astype(jnp.int32))
    ok = jnp.arange(k_cap, dtype=jnp.int32) < total
    widx = jnp.where(ok, midx[slot] * 32 + bit, -1)
    return widx, *_at(words_l, words_s, widx), total


def form_T(words_l, words_s, k_cap):
    return cdc_tpu._compact_words(words_l, words_s, k_cap)


FORMS = {"P": form_P, "B": form_B, "T": form_T, "G": form_G,
         "Gs": form_Gs, "Ge": form_Ge, "G2": form_G2, "G32": form_G32, "M": form_M}


# --- the contract in numpy -------------------------------------------------


def contract_numpy(words_l, words_s, k_cap):
    """What every form has to return, to the bit."""
    nz = np.flatnonzero(words_l)
    widx = np.full(k_cap, -1, np.int32)
    widx[:min(len(nz), k_cap)] = nz[:k_cap]
    safe = np.clip(widx, 0, len(words_l) - 1)
    return widx, words_l[safe], words_s[safe], len(nz)


def parity_masks(rng, n, k_cap):
    """The masks of ``tests/test_cdc_tpu.py``'s cases, as loose words."""
    def words(idx):
        w = np.zeros(n, np.uint32)
        w[np.asarray(idx, np.int64)] = rng.integers(
            1, 1 << 32, len(idx), dtype=np.uint64).astype(np.uint32)
        return w

    yield "all zero", words([])
    yield "first word", words([0])
    yield "last word", words([n - 1])
    yield "a full block", words(range(256, 384))
    yield "every word", words(range(n))
    if k_cap < n:
        yield "exactly k_cap", words(rng.choice(n, k_cap, replace=False))
        yield "k_cap + 1", words(rng.choice(n, k_cap + 1, replace=False))
    for bits in (13, 9):
        yield f"density 2^-{bits}", words(np.flatnonzero(
            rng.random(n) < 2.0 ** -bits))


def parity(forms, seed):
    rng = np.random.default_rng(seed)
    bad = []
    for n, k_cap in ((2048, 512), (32768, 512), (131072, 2048)):
        for label, wl in parity_masks(rng, n, k_cap):
            ws = wl & rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(
                np.uint32)
            want = contract_numpy(wl, ws, k_cap)
            for name in forms:
                got = jax.jit(FORMS[name], static_argnums=2)(
                    jnp.asarray(wl), jnp.asarray(ws), k_cap)
                same = all((np.asarray(g) == w).all()
                           for g, w in zip(got[:3], want[:3]))
                # past the capacity a form may say any count above it
                count = int(got[3])
                same &= (count == want[3] if name != "B" or want[3] <= k_cap
                         else count > k_cap)
                if not same:
                    bad.append(f"{name} at {n} words, {label}")
    return bad


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


def scan_with(form, k_cap):
    """``_scan_segment`` with ``form`` as its compaction."""
    def scan(ext, n_valid, mask_s, mask_l):
        h = _hash_ext_fast(ext)
        return form(*_candidate_words(h, n_valid, mask_s, mask_l), k_cap)
    return scan


def words_only(ext, n_valid, mask_s, mask_l):
    """Not a form: the scan program up to its packed words."""
    return _candidate_words(_hash_ext_fast(ext), n_valid, mask_s, mask_l)


# --- main ------------------------------------------------------------------


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--slice-mib", type=int, default=128)
    ap.add_argument("--seed", type=int, default=47)
    ap.add_argument("--parity-only", action="store_true")
    ap.add_argument("--out", default="chiprun_out/probe_scan_compact.json")
    args = ap.parse_args()
    forms = [f for f in args.forms.split(",") if f]
    dev = jax.devices()[0]
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "seed": args.seed, "slice_mib": args.slice_mib, "rows": []}
    bad = parity(forms, args.seed)
    result["parity_failed"] = bad
    print("parity against numpy at the small shapes:",
          "all equal" if not bad else f"DIFFER: {bad}", flush=True)
    if bad:
        return 1
    if args.parity_only:
        return 0
    if dev.platform != "tpu":
        print("no TPU: a time here would be the CPU's; stopping")
        return 1
    n = args.slice_mib * MiB
    rng = np.random.default_rng(args.seed)
    ext = jnp.asarray(rng.integers(0, 256, _HALO + n, dtype=np.uint8))
    for label, params in DENSITIES.items():
        k_cap = TpuCdcScanner(params)._k_cap(n)
        scan_args = (ext, jnp.int32(n), jnp.uint32(params.mask_s),
                     jnp.uint32(params.mask_l))
        words = jax.jit(words_only)(*scan_args)
        want = contract_numpy(np.asarray(words[0]), np.asarray(words[1]),
                              k_cap)
        row = {"density": label, "k_cap": k_cap, "nz_words": want[3],
               "alone": {}, "scan": {}}
        secs, _ = timed(named(words_only, "scan_words"), "scan_words",
                        scan_args)
        row["scan"]["words"] = secs
        print(f"{label:28s} words     {secs:.6f} s", flush=True)
        for name in forms:
            form = FORMS[name]
            try:
                alone = named(lambda wl, ws, f=form: f(wl, ws, k_cap),
                              "compact_" + name)
                got = alone(*words)
                same = (all((np.asarray(g) == w).all()
                            for g, w in zip(got[:3], want[:3]))
                        and int(got[3]) == want[3])
                a_secs, _ = timed(alone, "compact_" + name, words)
                s_secs, _ = timed(named(scan_with(form, k_cap),
                                        "scan_" + name), "scan_" + name,
                                  scan_args)
            except Exception as e:  # a form the chip's compiler refuses
                same, a_secs, s_secs = None, None, None
                row.setdefault("errors", {})[name] = str(e)[:400]
            row["alone"][name], row["scan"][name] = a_secs, s_secs
            row.setdefault("equal", {})[name] = same
            print(f"{label:28s} {name:9s} "
                  + ("refused" if a_secs is None else
                     f"alone {a_secs:.6f} s  scan {s_secs:.6f} s  "
                     f"{'equal' if same else 'DIFFERS'}"), flush=True)
        result["rows"].append(row)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)

    def ms(x):
        return "refused" if x is None else f"{x * 1e3:.2f}"

    print("\n| density | k_cap | nonzero words | | words | "
          + " | ".join(forms) + " |")
    print("|---|---|---|---|---|" + "---|" * len(forms))
    for row in result["rows"]:
        for kind in ("alone", "scan"):
            print(f"| {row['density']} | {row['k_cap']} | {row['nz_words']} "
                  f"| {kind} | {ms(row['scan']['words']) if kind == 'scan' else ''} | "
                  + " | ".join(ms(row[kind][f]) for f in forms) + " |")
    print("(device milliseconds a call; 'alone' is the compaction over the "
          "words, 'scan' the whole program from the bytes)")
    wrong = [f"{r['density']}: {f}" for r in result["rows"]
             for f, same in r["equal"].items() if same is False]
    if wrong:
        print("DIFFER from numpy at the slice:", wrong)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
