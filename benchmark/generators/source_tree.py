"""Tree generator ``source_tree``: ISSUE 41's source tree, a checkout of
many small files in many directories (``BASELINE.json`` ``configs[1]``,
the Linux kernel source tree, at a thirteenth of its scale with its
shapes kept).

* ``directories`` directories under the root, which holds none of the
  files itself: ``top_level`` of them directly under it (``t00`` ..), the
  rest below those, nested up to ``max_depth`` deep.  The skeleton (which
  directory lies in which) is fixed by the counts alone, the same for
  every seed;
* a fixed list of ``directories`` file counts (:func:`dir_counts`): the
  quantiles of a lognormal of median ``dir_files_median`` and sigma
  ``dir_files_sigma``, clipped to ``dir_files_min`` .. ``dir_files_max``
  and scaled so that they sum to ``files``.  The seed permutes which
  directory has which count;
* a fixed list of ``files`` sizes (:func:`file_sizes`): the quantiles of
  a lognormal of median ``size_median_bytes`` and sigma ``size_sigma``,
  clipped to ``size_min_bytes`` .. ``size_max_bytes`` and scaled so that
  their mean is ``size_mean_bytes``.  The same multiset for every seed;
  the seed permutes which file has which size.

Every byte is seeded and its own.

``needs_batch_report_entry`` (optional): the tree is built only for a
program whose ``obs/profile.report()`` has that entry in its ``batch``
section (``batches``: the count of pack batches, there since a pack
batch spans directories).  The cell's tree is backed up whole inside
every run's set-up and again in every timed backup, and a run is stopped
at 360 s: a program that hands a directory at a time to the device (375
batches a backup, a ``dedup_insert`` compile for each new directory
length in its first backup: 155.8 s with 120 compiles at this size,
PERF.md section 2) was stopped at 430 s inside its window, so for it the
run ends here, at once and with exit code 1, not at the time limit.
Nothing else of the program is read, and no size follows from it.
"""

from __future__ import annotations

import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

BLOCK = 16 << 20  # bytes drawn at a time


def _quantiles(n: int, median: float, sigma: float) -> list:
    """Position i of n: the (i + 1/2) / n quantile of the lognormal."""
    inv = NormalDist().inv_cdf
    return [median * math.exp(sigma * inv((i + 0.5) / n)) for i in range(n)]


def file_sizes(params: dict) -> list:
    """The fixed list, ascending.  Clipping cuts the tail's mass, so the
    scale that brings the mean to ``size_mean_bytes`` is found by
    halving."""
    n = int(params["files"])
    lo, hi = int(params["size_min_bytes"]), int(params["size_max_bytes"])
    raw = _quantiles(n, float(params["size_median_bytes"]),
                     float(params["size_sigma"]))
    want = n * int(params["size_mean_bytes"])

    def scaled(by: float) -> list:
        return [min(max(int(round(q * by)), lo), hi) for q in raw]

    low, high = 0.25, 4.0
    for _ in range(40):
        mid = (low * high) ** 0.5
        if sum(scaled(mid)) < want:
            low = mid
        else:
            high = mid
    return scaled(high)


def dir_counts(params: dict) -> list:
    """The fixed list, ascending, summing to ``files`` exactly: the
    scaled quantiles rounded down, and what is then short of the sum put
    back a file a directory from the largest down (round robin)."""
    n, files = int(params["directories"]), int(params["files"])
    lo, hi = int(params["dir_files_min"]), int(params["dir_files_max"])
    raw = _quantiles(n, float(params["dir_files_median"]),
                     float(params["dir_files_sigma"]))

    def scaled(by: float) -> list:
        return [min(max(int(q * by), lo), hi) for q in raw]

    low, high = 0.25, 4.0
    for _ in range(40):
        mid = (low * high) ** 0.5
        if sum(scaled(mid)) <= files:
            low = mid
        else:
            high = mid
    counts = scaled(low)
    short = files - sum(counts)
    k = n - 1
    while short > 0:
        if counts[k] < hi:
            counts[k] += 1
            short -= 1
        k = k - 1 if k else n - 1
    return counts


def skeleton(params: dict) -> list:
    """The directories' paths relative to the root, parents before
    children, the same for every seed: ``top_level`` under the root, each
    later one under a directory drawn (from the counts alone) among those
    not yet ``max_depth`` deep."""
    n, top = int(params["directories"]), int(params["top_level"])
    max_depth = int(params["max_depth"])
    fixed = np.random.default_rng([n, top, max_depth])
    paths = [f"t{i:02d}" for i in range(min(top, n))]
    depth = [1] * len(paths)
    for j in range(len(paths), n):
        open_to = [k for k in range(j) if depth[k] < max_depth]
        parent = open_to[int(fixed.integers(0, len(open_to)))]
        paths.append(f"{paths[parent]}/d{j:03d}")
        depth.append(depth[parent] + 1)
    return paths


def _needs_batch_report_entry(entry: str) -> None:
    from backuwup_tpu.obs import profile
    if entry not in profile.report()["batch"]:
        raise SystemExit(
            f"benchmark: this program's report has no batch entry "
            f"{entry!r} (it hands the device a directory at a time), and "
            f"the backups of this tree do not fit a run with it; nothing "
            f"ran")


def build(root: Path, params: dict, rng: np.random.Generator) -> None:
    if params.get("needs_batch_report_entry"):
        _needs_batch_report_entry(params["needs_batch_report_entry"])
    dirs = skeleton(params)
    counts = dir_counts(params)
    sizes = file_sizes(params)
    root.mkdir(parents=True, exist_ok=True)
    for rel in dirs:
        (root / rel).mkdir()
    count_of = [counts[int(j)] for j in rng.permutation(len(counts))]
    size_of = [sizes[int(j)] for j in rng.permutation(len(sizes))]
    places = [root / rel / f"f{k:03d}"
              for rel, n in zip(dirs, count_of) for k in range(n)]
    block, at = b"", 0
    for path, n in zip(places, size_of):
        if at + n > len(block):
            block, at = rng.bytes(max(BLOCK, n)), 0
        with open(path, "wb") as f:
            f.write(block[at:at + n])
        at += n
