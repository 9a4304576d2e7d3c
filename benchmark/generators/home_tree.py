"""Tree generator ``home_tree``: ISSUE 24's home directory.

* ``big/f0``: ``big_bytes`` seeded bytes; ``big/f1``: the same bytes
  with ``big_insert_bytes`` seeded bytes put in at ``big_insert_at`` of
  its length (content-defined chunking has to find ``f0``'s chunks again
  behind the insertion);
* ``mid/m00`` ..: ``mid_files`` files of ``mid_bytes`` each;
* ``small/s0000`` ..: ``small_files`` files whose sizes are a fixed list,
  evenly spaced from ``small_min_bytes`` to ``small_max_bytes``: the same
  multiset for every seed, the seed permutes which file has which size;
* ``long/l0``: one file of ``long_bytes`` (left out where 0).

Every byte is seeded and its own except ``f1``'s copy of ``f0``.  One
directory a class: the packer hands a directory's files to the device as
one batch, so the small files are one digest batch as a directory of
documents is.

``needs_program_span`` (optional): the tree is built only for a program
whose ``obs/profile.py`` groups that span into ``report["batch"]``.  The
cell's tree is backed up whole inside every run's set-up, and a run is
stopped at 360 s: a program that compiles the batched route's programs
one after the other is still inside that first backup then (PERF.md
section 2: the parent of PR 34 read 337 s for it with its cache warm),
so for it the run ends here, at once and with exit code 1, not at the
time limit.  Nothing else of the program is read, and no size follows
from it.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

BIG, MID, SMALL, LONG = "big", "mid", "small", "long"
BLOCK = 16 << 20  # written a block at a time


def small_sizes(params: dict) -> list:
    """The fixed list, ascending: position i of n lies (i / (n - 1)) of
    the way from the smallest size to the largest."""
    n = int(params["small_files"])
    lo, hi = int(params["small_min_bytes"]), int(params["small_max_bytes"])
    if n < 2:
        return [lo] * n
    return [lo + (hi - lo) * i // (n - 1) for i in range(n)]


def write_seeded(path: Path, nbytes: int, rng: np.random.Generator) -> None:
    with open(path, "wb") as f:
        for at in range(0, nbytes, BLOCK):
            f.write(rng.bytes(min(BLOCK, nbytes - at)))


def _needs_program_span(span: str) -> None:
    from backuwup_tpu.obs import profile
    if span not in getattr(profile, "BATCH_GROUPS", {}):
        raise SystemExit(
            f"benchmark: this program has no span {span!r} (it compiles "
            f"the batched route's programs one after the other), and the "
            f"first backup of this tree does not fit a run with it; "
            f"nothing ran")


def build(root: Path, params: dict, rng: np.random.Generator) -> None:
    if params.get("needs_program_span"):
        _needs_program_span(params["needs_program_span"])
    for sub in (BIG, MID, SMALL):
        (root / sub).mkdir(parents=True, exist_ok=True)
    f0 = rng.bytes(int(params["big_bytes"]))
    (root / BIG / "f0").write_bytes(f0)
    at = int(len(f0) * float(params["big_insert_at"]))
    with open(root / BIG / "f1", "wb") as f:
        f.write(f0[:at])
        f.write(rng.bytes(int(params["big_insert_bytes"])))
        f.write(f0[at:])
    del f0
    for i in range(int(params["mid_files"])):
        write_seeded(root / MID / f"m{i:02d}", int(params["mid_bytes"]), rng)
    sizes = small_sizes(params)
    for i, j in enumerate(rng.permutation(len(sizes))):
        (root / SMALL / f"s{i:04d}").write_bytes(rng.bytes(sizes[int(j)]))
    if int(params.get("long_bytes", 0)):
        (root / LONG).mkdir(exist_ok=True)
        write_seeded(root / LONG / "l0", int(params["long_bytes"]), rng)
