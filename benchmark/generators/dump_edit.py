"""Traffic generator ``dump_edit``: a database host's next nightly dump,
written anew from the last one with rows put in, taken out and changed.

A night is ``slots`` edits, one in each of that many equal parts of the
old file, so no two share a chunk's neighbourhood: an insert or a delete
for each length of ``insert_delete_bytes`` (ascending; every
``delete_every``-th a delete, the others inserts) and an in-place
overwrite for each of ``overwrite_bytes``.  The lengths are the traffic
file's fixed lists, the same multiset every seed and night; the night's
``rng`` permutes which part gets which edit and draws where in its part
each one starts: any byte offset at least ``margin_bytes`` from the
part's ends, aligned to nothing.  Inserted and overwritten bytes are
seeded and incompressible.  Everything behind an insert or a delete
moves, so the content-defined cuts fall elsewhere against the packer's
windows every night; the file's length drifts by the inserts less the
deletes.  The new dump is a new file put in the old one's place, as a
dump job leaves it.

Equal work (``new_bytes_min`` / ``new_bytes_max``, with the traffic's
``cdc``): an edit makes new the chunk it falls in, from that chunk's
start to the first cut behind the edit that the old file had too, and
how long that is is dice at 1 MiB chunks (a night's sum 22.5 to 36.9 MiB
over 32 draws, PERF.md section 4).  So each edit is tried first against
the old file's cuts, by the reference and in memory: the few MiB from
its chunk's start are chunked as the new file will have them, and the
edit's new bytes are those up to where the cuts meet the old ones again.
An edit whose cuts do not meet within ``margin_bytes`` is drawn again;
then, while the night's sum lies outside the band, the edit with the
most (or the fewest) new bytes is drawn again, from ``rng`` as it
stands, so the same seed gives the same bytes.  A chunk an earlier night
had comes back now and then (an edit splits a chunk where an earlier
edit had merged two), so the digests of every chunk so far are kept
too, and only a chunk none of them names counts as new: the sum is the
reference's own count of the night, to the byte.  The old file's cuts
and those digests are kept beside the run's other scratch
(``work/dump_edit.npz``) and carried forward edit by edit; the first
night chunks the whole file once.
"""

from __future__ import annotations

import os
from pathlib import Path

import numpy as np

from benchmark import specs
from benchmark.reference import native
from benchmark.reference.gear import GEAR_WINDOW

from .dump_file import BLOCK, DUMP

REDRAWS = 64  # of single edits, a night
KEPT = "dump_edit.npz"


def edit_kinds(params: dict) -> list:
    """[(kind, length)] of a night, the same every seed and night."""
    every = int(params["delete_every"])
    kinds = [("delete" if (i + 1) % every == 0 else "insert", int(n))
             for i, n in enumerate(params["insert_delete_bytes"])]
    return kinds + [("overwrite", int(n)) for n in params["overwrite_bytes"]]


def chunked(data, cdc) -> tuple:
    """Where the reference's chunks of ``data`` start, with its length
    behind them, and the set of their digests."""
    spans = native.manifest(data, cdc)
    starts = np.array([off for off, _n, _d in spans] + [len(data)],
                      dtype=np.int64)
    return starts, {d for _o, _n, d in spans}


class _Edit:
    """One edit at ``at`` of the old file: ``removed`` old bytes give way
    to ``fresh``.  Tried against the old cuts (``settled`` says whether
    they met again): the new chunks (``lens``, ``digests``) start at
    ``a``, an old cut, and end at what was the old cut ``rejoin``;
    ``new_bytes`` are those of them no chunk so far (``seen``) equals."""

    def __init__(self, old, kept, at: int, kind: str, n: int,
                 rng: np.random.Generator, params: dict, cdc):
        self.at = at
        self.removed = 0 if kind == "insert" else n
        self.fresh = b"" if kind == "delete" else rng.bytes(n)
        self.settled = cdc is None
        if cdc is None:  # unscreened (a rehearsal): nothing is tried
            return
        starts, seen = kept
        behind, margin = at + self.removed, int(params["margin_bytes"])
        self.a = int(starts[np.searchsorted(starts, at, side="right") - 1])
        back = self.a + self.removed - len(self.fresh)  # local -> old
        # most cuts meet within a chunk or two: a short reach first
        for reach in (behind + cdc.desired_size + cdc.min_size,
                      behind + margin + cdc.max_size):
            reach = min(len(old), reach)
            spans = native.manifest(np.concatenate([
                old[self.a:at], np.frombuffer(self.fresh, dtype=np.uint8),
                old[behind:reach]]), cdc)
            if reach < len(old):
                spans = spans[:-1]  # the buffer's end cut the last one
            self.lens, self.digests, self.new_bytes = [], [], 0
            for off, length, digest in spans:
                self.lens.append(length)
                self.digests.append(digest)
                self.new_bytes += 0 if digest in seen else length
                rejoin = off + length + back
                if rejoin > behind + margin:
                    return
                # past the edit by the hash's window, every later
                # candidate is the old file's
                if rejoin >= behind + GEAR_WINDOW and \
                        starts[np.searchsorted(starts, rejoin)] == rejoin:
                    self.rejoin, self.settled = rejoin, True
                    return


def _draw(old, kept, slot: int, kind: str, n: int,
          rng: np.random.Generator, params: dict, cdc) -> _Edit:
    """An edit somewhere in part ``slot`` whose cuts meet the old ones."""
    slots, margin = int(params["slots"]), int(params["margin_bytes"])
    lo = slot * len(old) // slots + margin
    hi = (slot + 1) * len(old) // slots - margin - n
    if hi <= lo:
        raise ValueError("a part of the file is too small for its edit")
    for _ in range(REDRAWS):
        edit = _Edit(old, kept, int(rng.integers(lo, hi)), kind, n, rng,
                     params, cdc)
        if edit.settled:
            return edit
    raise SystemExit(f"dump_edit: no draw of {REDRAWS} met the old cuts "
                     f"again within {margin} bytes")


def plan(old, kept, params: dict, rng: np.random.Generator,
         cdc=None) -> list:
    """The night's edits in the order they lie in the old file.  With
    ``cdc`` (and ``kept``: the old file's cuts and every digest so far)
    the night's new bytes are brought into the traffic's band."""
    kinds = edit_kinds(params)
    if len(kinds) != int(params["slots"]):
        raise ValueError(f"{len(kinds)} edits for {params['slots']} slots")
    order = [kinds[int(j)] for j in rng.permutation(len(kinds))]
    edits = [_draw(old, kept, slot, kind, n, rng, params, cdc)
             for slot, (kind, n) in enumerate(order)]
    if cdc is None:
        return edits
    if int(params["margin_bytes"]) < cdc.max_size:
        raise ValueError("margin_bytes under a chunk's maximum: two "
                         "edits could share a chunk")
    lo, hi = int(params["new_bytes_min"]), int(params["new_bytes_max"])
    for _ in range(REDRAWS):
        total = sum(e.new_bytes for e in edits)
        if lo <= total <= hi:
            return edits
        pick = max if total > hi else min
        slot = pick(range(len(edits)), key=lambda i: edits[i].new_bytes)
        edits[slot] = _draw(old, kept, slot, *order[slot], rng, params,
                            cdc)
    raise SystemExit(f"dump_edit: {REDRAWS} redraws left the night's new "
                     f"bytes outside {lo}..{hi}")


def _starts_after(starts: np.ndarray, edits: list) -> np.ndarray:
    """The new file's cuts from the old one's and the edits' own."""
    out, shift, done = [], 0, 0
    for e in edits:
        out.append(starts[(starts >= done) & (starts < e.a)] + shift)
        out.append(e.a + shift + np.cumsum([0] + e.lens[:-1]))
        shift += len(e.fresh) - e.removed
        done = e.rejoin
    out.append(starts[starts >= done] + shift)
    return np.concatenate(out).astype(np.int64)


def _copy(src, dst, start: int, end: int) -> None:
    """Bytes ``start``..``end`` of ``src`` to the end of ``dst``, both
    open files, by the kernel where it can."""
    dst.flush()
    while start < end:
        try:
            n = os.copy_file_range(src.fileno(), dst.fileno(),
                                   min(end - start, 1 << 30), start)
        except OSError:
            n = 0
        if not n:  # another file system, an old kernel: through here
            src.seek(start)
            n = dst.write(src.read(min(end - start, BLOCK)))
            dst.flush()
        start += n


def _load(path: Path, size: int):
    """(cuts, digests so far) as the last night left them, if they are
    this file's."""
    if not path.exists():
        return None
    with np.load(path) as z:
        starts, raw = z["starts"], z["seen"].tobytes()
    if starts[-1] != size:
        return None
    return starts, {raw[i:i + 32] for i in range(0, len(raw), 32)}


def step(root: Path, params: dict, rng: np.random.Generator,
         ctx: dict) -> Path:
    path = root / DUMP
    old = np.memmap(path, dtype=np.uint8, mode="r")
    cdc = kept = None
    if params.get("new_bytes_max"):
        cdc = specs.cdc_params(params)
        kept = _load(Path(ctx["work"]) / KEPT, len(old)) \
            or chunked(old, cdc)
    edits = plan(old, kept, params, rng, cdc)
    size = len(old)
    del old
    new = path.with_name(path.name + ".new")
    with open(path, "rb") as src, open(new, "wb", buffering=0) as dst:
        pos = 0
        for e in edits:
            _copy(src, dst, pos, e.at)
            dst.write(e.fresh)
            pos = e.at + e.removed
        _copy(src, dst, pos, size)
    os.replace(new, path)
    if cdc is not None:
        seen = kept[1].union(*(e.digests for e in edits))
        np.savez(Path(ctx["work"]) / KEPT,
                 starts=_starts_after(kept[0], edits),
                 seen=np.frombuffer(b"".join(sorted(seen)), dtype=np.uint8))
    return root
