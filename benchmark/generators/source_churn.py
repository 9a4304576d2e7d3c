"""Traffic generator ``source_churn``: one night's commits to a
``source_tree``, in place.  Every count is fixed; the seed decides which
directories, which files and the bytes.

* ``directories`` directories of at least ``min_dir_files`` files each
  are drawn (a night's commits touch a few subsystems); everything below
  happens inside them, the same share in each;
* ``rewritten`` files get new bytes at their own sizes, all of them at or
  under ``max_file_bytes`` (the chunker's minimum: one chunk a file);
* ``added`` new files take sizes from the tree's fixed list
  (``tree_params``) among those at or under ``max_file_bytes``, at places
  spread evenly that move on by ``added_step`` a night;
* ``deleted`` files go, among the files of that size class that the
  night does not rewrite;
* the added and the deleted files are one directory's (a night's new
  files belong to one change), the drawn directory that has most bytes
  to choose from, and the deleted files are the seeded draw brought, by
  the exchange below, as near the added files' bytes as that directory
  allows (some tens of bytes a night, a few KB where the directory has
  nothing nearer); the last added file then takes up what is left, so
  the tree's bytes stay level to the byte (``last`` keeps
  ``tree_bytes_change``: 0) and one directory's count of files moves, where
  additions and deletions spread over the drawn directories moved three
  counts and some tens of KB a night.  Why that is held: a program may
  cut its work by bytes and compile by counts (the program this was
  written beside cuts pack batches at 32 MiB of files and compiles an
  index insert for each new count of files in a batch), so that a few
  KB more in one directory moved a cut, and with it every later batch's
  count, in one night of six and not in the others: nights of the same
  traffic cost two compiles or eight (PERF.md section 2).  Nothing of
  the program is read to hold it.

Equal work.  A night's new bytes are the rewritten files' sizes plus the
added files', and ten directories' files are a few hundred draws from a
heavy-tailed list: a free draw puts nights some tens of percent apart.
So the night has an aim, ``new_bytes`` less what the run's nights so far
have made over it (or plus what they are short; ``<work>/
source_churn.json`` keeps the sum), and the seeded draw of rewritten
files is brought to it in memory, from the sizes alone: while the set is
off the aim by more than ``new_bytes_tolerance`` of it, the one exchange
of a drawn file for another file of its directory that comes nearest is
made.  Where no exchange comes nearer, the set stands: a run never stops
here.  Nothing of the program is read.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from .source_tree import file_sizes, skeleton

STATE = "source_churn.json"


def _listing(path: Path) -> list:
    """(name, size) of a directory's regular files, by name."""
    return sorted((p.name, p.stat().st_size) for p in path.iterdir()
                  if p.is_file())


def _settle(drawn: list, spare: list, aim: int, room: int) -> None:
    """``drawn`` and ``spare``: a directory each, its (name, size) files
    to rewrite and those left alone.  Exchanges one for one inside a
    directory until the drawn sizes sum to within ``room`` of ``aim``."""
    total = sum(size for files in drawn for _name, size in files)
    while abs(total - aim) > room:
        best = None
        for d, (ins, outs) in enumerate(zip(drawn, spare)):
            for i, (_n, a) in enumerate(ins):
                for o, (_m, b) in enumerate(outs):
                    off = abs(total - a + b - aim)
                    if best is None or off < best[0]:
                        best = (off, d, i, o)
        if best is None or best[0] >= abs(total - aim):
            return
        _off, d, i, o = best
        total += spare[d][o][1] - drawn[d][i][1]
        drawn[d][i], spare[d][o] = spare[d][o], drawn[d][i]


def step(root: Path, params: dict, rng: np.random.Generator,
         ctx: dict) -> Path:
    generation = int(ctx["generation"])
    state_path = Path(ctx["work"]) / STATE
    state = (json.loads(state_path.read_text()) if state_path.exists()
             else {"owed": 0})
    tree = params["tree_params"]
    limit = int(params["max_file_bytes"])
    n_dirs = int(params["directories"])
    per_dir = int(params["rewritten"]) // n_dirs
    listings = {rel: _listing(root / rel) for rel in skeleton(tree)}
    eligible = sorted(rel for rel, files in listings.items()
                      if len(files) >= int(params["min_dir_files"]))
    picked = [eligible[int(j)]
              for j in rng.permutation(len(eligible))[:n_dirs]]

    # the added files' sizes first: the rewritten set makes up the rest
    small = [n for n in file_sizes(tree) if n <= limit]
    n_add = int(params["added"])
    added = [small[(len(small) * (2 * k + 1) // (2 * n_add)
                    + int(params["added_step"]) * generation) % len(small)]
             for k in range(n_add)]
    want = int(params["new_bytes"])
    room = want * float(params["new_bytes_tolerance"])
    # what the run owes is paid back as far as one night can
    aim = want - min(max(int(state["owed"]), -room / 2), room / 2)

    drawn, spare = [], []
    for rel in picked:
        files = [f for f in listings[rel] if f[1] <= limit]
        order = [files[int(j)] for j in rng.permutation(len(files))]
        drawn.append(order[:per_dir])
        spare.append(order[per_dir:])
    _settle(drawn, spare, int(aim - sum(added)), int(room / 8))

    rewritten = 0
    for rel, files in zip(picked, drawn):
        for name, size in files:
            (root / rel / name).write_bytes(rng.bytes(size))
            rewritten += size
    # the additions and the deletions: one directory's, byte for byte
    home = max(range(n_dirs),
               key=lambda d: sum(size for _name, size in spare[d]))
    n_del = int(params["deleted"])
    gone, kept = [spare[home][:n_del]], [spare[home][n_del:]]
    _settle(gone, kept, sum(added), 0)
    # what the exchange leaves, the last added file takes up
    level = sum(added) - sum(size for _name, size in gone[0])
    if 0 < added[-1] - level <= limit:
        added[-1] -= level
        level = 0
    for k, size in enumerate(added):
        (root / picked[home] / f"a{generation:03d}_{k:02d}"
         ).write_bytes(rng.bytes(size))
    for name, _size in gone[0]:
        (root / picked[home] / name).unlink()

    new_bytes = rewritten + sum(added)
    state["owed"] = int(state["owed"]) + new_bytes - want
    state["last"] = {"generation": generation, "directories": picked,
                     "rewritten": sum(len(f) for f in drawn),
                     "rewritten_bytes": rewritten, "added": n_add,
                     "added_bytes": sum(added),
                     "deleted": n_del, "home": picked[home],
                     "tree_bytes_change": level,
                     "new_bytes": new_bytes}
    state_path.write_text(json.dumps(state))
    return root
