"""Traffic generator ``tree_churn``: one night's changes to a
``home_tree``, in place.  Every count is fixed; the seed decides which
files, where, and the bytes.

Small files (ranked by size, ties by name; the multiset of sizes is the
same for every seed on every night, so every seed offers the same bytes):

* ``small_deleted`` files go, at fixed ranks spread evenly;
* ``small_rewritten`` files get new bytes at their own sizes, one from
  each of as many equal strata of the ranking: a seeded place in an even
  stratum, the mirrored place in the odd stratum after it, so that a
  night's rewritten bytes hardly move (the sizes are evenly spaced);
* ``small_added`` new files take sizes from the tree's fixed list, at
  places spread evenly that move on by a fixed step a night.

One ``mid`` file, seeded, is replaced at its size.  ``f1`` and the long
file stay as they are.

``big/f0`` takes ``f0_overwrites`` overwrites of ``f0_overwrite_bytes``
and ``f0_insertions`` insertions of ``f0_insert_bytes``.  Rule
``chunk_targeted`` (the only one): how many new bytes an edit inside a
file of 1 MiB chunks makes is a throw of dice when its place is free
(one chunk or two, each 256 KiB to 3 MiB: PR 27 read 6.8-24.5 MB new a
night between seeds, and its cell was refused as too noisy), so the
edits are placed against the chunk boundaries of the benchmark's own
reference (``benchmark/reference/native.py`` over ``f0``, untimed;
nothing of the program is read).  Each edit goes to the middle of a chunk
of its own.  The chunks are a set drawn, seeded, from the ``f0_pool``
whose lengths are nearest ``f0_target_chunk_bytes`` among those whose
middle is at least ``f0_gap_bytes`` from another edit of the night and
``f0_recent_gap_bytes`` from every edit of the last ``f0_recent_nights``
nights (kept in ``<work>/tree_churn.json``), such that their lengths
come within half ``f0_new_bytes_tolerance`` of the night's aim.  The aim
is that many chunks of the target length, less what the run's nights so
far have made over it (or plus what they are short), up to half the
tolerance: so a run's sum does not drift with the seed.  A set after
which the reference finds another number of ``f0``'s old chunks gone
than the night has edits (a new cut inside an overwrite can let the
chunk run on into its neighbour), or ``f0``'s new bytes off the aim, is
dropped for the next; a pool that holds no such set is widened.  Where
nothing fits, the nearest that was tried stands: a run never stops here.
"""

from __future__ import annotations

import itertools
import json
from pathlib import Path

import numpy as np

from benchmark.reference import native
from benchmark.reference.gear import CDCParams

from .home_tree import BIG, MID, SMALL, small_sizes

STATE = "tree_churn.json"
PLANS = 8  # sets of chunks applied and checked by the reference, a pool
POOL_STEP, POOL_MAX = 4, 24


def _ranked(small: Path) -> list:
    return sorted(((p.stat().st_size, p.name) for p in small.iterdir()))


def _churn_small(small: Path, params: dict, rng: np.random.Generator,
                 generation: int) -> dict:
    ranked = _ranked(small)
    n_del = int(params["small_deleted"])
    gone = {len(ranked) * (2 * k + 1) // (2 * n_del) for k in range(n_del)}
    for r in gone:
        (small / ranked[r][1]).unlink()
    ranked = [f for r, f in enumerate(ranked) if r not in gone]
    n_rw = int(params["small_rewritten"])
    width = len(ranked) // n_rw
    rewritten = 0
    for k in range(n_rw):
        if k % 2 == 0:
            place = int(rng.integers(0, width))
        size, name = ranked[k * width + (place if k % 2 == 0
                                         else width - 1 - place)]
        (small / name).write_bytes(rng.bytes(size))
        rewritten += size
    sizes = small_sizes(params["small_list"])
    n_add = int(params["small_added"])
    added = 0
    for k in range(n_add):
        at = (len(sizes) * (2 * k + 1) // (2 * n_add)
              + int(params["small_added_step"]) * generation) % len(sizes)
        (small / f"a{generation:03d}_{k:02d}").write_bytes(
            rng.bytes(sizes[at]))
        added += sizes[at]
    return {"deleted": n_del, "rewritten": n_rw, "added": n_add,
            "rewritten_bytes": rewritten, "added_bytes": added}


def _plans(chunks: list, recent: list, params: dict,
           rng: np.random.Generator, n: int, miss):
    """Sets of ``n`` of ``chunks`` (offset, length) to put the night's
    edits in, in the order to try them.  Every set keeps the gaps.  From
    the ``f0_pool`` free chunks nearest the target length, then from
    pools ``POOL_STEP`` wider up to ``POOL_MAX``: the sets whose lengths
    ``miss`` the night's aim by nothing, in a seeded order, ``PLANS`` a
    pool at most; after them the nearest of the rest."""
    target = int(params["f0_target_chunk_bytes"])
    gap, recent_gap = int(params["f0_gap_bytes"]), \
        int(params["f0_recent_gap_bytes"])
    # an overwrite lies whole inside its chunk, clear of both ends
    room = 2 * int(params["f0_overwrite_bytes"])
    free = [(off, ln) for off, ln in chunks if ln >= room and all(
        abs(off + ln // 2 - at) >= recent_gap for at in recent)]
    free.sort(key=lambda c: (abs(c[1] - target), c[0]))
    seen: set = set()
    rest: list = []
    size = int(params["f0_pool"])
    while True:
        pool = free[:min(size, POOL_MAX)]
        fitting = []
        for picked in itertools.combinations(pool, n):
            middles = sorted(off + ln // 2 for off, ln in picked)
            if picked in seen or any(
                    b - a < gap for a, b in zip(middles, middles[1:])):
                continue
            seen.add(picked)
            by = miss(sum(ln for _o, ln in picked))
            (rest if by else fitting).append((by, picked))
        for j in rng.permutation(len(fitting))[:PLANS]:
            yield fitting[int(j)][1]
        if len(pool) >= min(len(free), POOL_MAX):
            break
        size += POOL_STEP
    for _by, picked in sorted(rest)[:PLANS]:
        yield picked


def _churn_f0(path: Path, params: dict, cdc: CDCParams,
              rng: np.random.Generator, recent: list, owed: int) -> dict:
    """``owed``: ``f0``'s new bytes of the nights so far, less that many
    nights of ``want``."""
    old = path.read_bytes()
    before = native.manifest(old, cdc)
    digests = {d for _o, _n, d in before}
    n_over, n_ins = int(params["f0_overwrites"]), int(params["f0_insertions"])
    over_len, ins_len = int(params["f0_overwrite_bytes"]), \
        int(params["f0_insert_bytes"])
    want = (n_over + n_ins) * int(params["f0_target_chunk_bytes"])
    room = want * float(params["f0_new_bytes_tolerance"])
    # what the run owes is paid back as far as one night can
    aim = want - min(max(owed, -room / 2), room / 2)

    def miss(fresh: int) -> int:
        """0 where the night's new bytes are near enough to its aim."""
        return int(max(abs(fresh - aim) - room / 2, 0))

    best = None  # where no plan fits: the nearest, so a run never stops
    plans = _plans([(o, n) for o, n, _d in before], recent, params, rng,
                   n_over + n_ins,
                   # an insertion's bytes are new beside its chunk's
                   lambda lengths: miss(lengths + n_ins * ins_len))
    for applied, picked in enumerate(plans, start=1):
        new = bytearray(old)
        # seeded, too, which chunk takes which edit; but an insertion
        # moves its chunk's bytes against the point where the looser
        # mask starts to count, which often brings a new cut and with
        # it the neighbour's end: of the set, the insertions take first
        # the chunks that stay under the desired size
        order = sorted(
            (picked[int(j)] for j in rng.permutation(len(picked))),
            key=lambda c: c[1] + ins_len <= cdc.desired_size)
        # (middle of the chunk, is an insertion)
        edits = [(off + ln // 2, i >= n_over)
                 for i, (off, ln) in enumerate(order)]
        # from the end, so that an insertion moves no edit still to come
        for at, insertion in sorted(edits, reverse=True):
            if insertion:
                new[at:at] = rng.bytes(ins_len)
            else:
                new[at - over_len // 2:at - over_len // 2 + over_len] = \
                    rng.bytes(over_len)
        after = native.manifest(bytes(new), cdc)
        kept = {d for _o, _n, d in after}
        gone = sum(1 for d in digests if d not in kept)
        fresh = sum(n for _o, n, d in after if d not in digests)
        off_by = (gone != len(edits), miss(fresh))
        if best is None or off_by < best[0]:
            best = (off_by, new, edits, gone, fresh, applied)
        if off_by == (False, 0):
            break
    if best is None:
        raise RuntimeError("tree_churn: f0 has no chunks that keep the gaps")
    _off_by, new, edits, gone, fresh, applied = best
    path.write_bytes(new)
    # where places lie in the new file: an insertion moves those after it
    inserted = [a for a, insertion in edits if insertion]

    def moved(at: int) -> int:
        return at + ins_len * sum(1 for a in inserted if a < at)

    return {"edits": [moved(at) for at, _insertion in edits],
            "recent": [moved(at) for at in recent], "draws": applied,
            "chunks_gone": gone, "new_bytes": fresh,
            "owed": owed + fresh - want}


def step(root: Path, params: dict, rng: np.random.Generator,
         ctx: dict) -> Path:
    generation = int(ctx["generation"])
    state_path = Path(ctx["work"]) / STATE
    state = (json.loads(state_path.read_text()) if state_path.exists()
             else {"nights": []})
    cdc = CDCParams(**{k: int(v) for k, v in params["cdc"].items()})
    night = _churn_small(root / SMALL, params, rng, generation)
    mids = sorted((root / MID).iterdir())
    mid = mids[int(rng.integers(0, len(mids)))]
    mid.write_bytes(rng.bytes(mid.stat().st_size))
    recent = [at for n in state["nights"] for at in n]
    f0 = _churn_f0(root / BIG / "f0", params, cdc, rng, recent,
                   int(state.get("f0_owed", 0)))
    state["f0_owed"] = f0["owed"]
    # the earlier nights' places, moved by this night's insertions
    k, nights = 0, []
    for n in state["nights"]:
        nights.append(f0["recent"][k:k + len(n)])
        k += len(n)
    nights.append(f0["edits"])
    state["nights"] = nights[-int(params["f0_recent_nights"]):] \
        if int(params["f0_recent_nights"]) else []
    state["last"] = dict(night, generation=generation, mid=mid.name,
                         f0_draws=f0["draws"], f0_new_bytes=f0["new_bytes"],
                         f0_chunks_gone=f0["chunks_gone"])
    state_path.write_text(json.dumps(state))
    return root
