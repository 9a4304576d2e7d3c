"""Traffic generator ``image_overwrite``: one night's writes to a VM disk
image, in place; the image keeps its size.

The write sizes cycle through ``write_bytes`` until they total
``overwrite_share`` of the image.  The regions of ``region_bytes`` are
ranked by the run's seed, once, so the hot regions stay hot; the i-th
write of a night goes to the rank at the (i + 1/2)/n quantile of
Zipf(``zipf_s``), so every seed and night gives each rank the same
writes.  Within its region a write lands ``align``-aligned inside a slot
of ``slot_bytes`` of its own, drawn without replacement, so no two writes
of a night overlap and the bytes a night dirties vary only by where the
content-defined cuts happen to fall.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

from .disk_image import IMAGE


def write_plan(image_bytes: int, params: dict) -> list:
    sizes, total = [], 0
    want = int(image_bytes * float(params["overwrite_share"]))
    cycle = [int(v) for v in params["write_bytes"]]
    while total < want:
        sizes.append(cycle[len(sizes) % len(cycle)])
        total += sizes[-1]
    return sizes


def step(root: Path, params: dict, rng: np.random.Generator,
         ctx: dict) -> Path:
    path = root / IMAGE
    size = path.stat().st_size
    region, align = int(params["region_bytes"]), int(params["align"])
    slot = int(params["slot_bytes"])
    n_regions = size // region
    # the ranking comes from the run's seed, not the generation's
    ranking = np.random.default_rng(int(ctx["seed"])).permutation(n_regions)
    weights = 1.0 / np.arange(1, n_regions + 1) ** float(params["zipf_s"])
    cdf = np.cumsum(weights / weights.sum())
    plan = write_plan(size, params)
    free = {}  # region -> its slots, in this night's seeded order
    with open(path, "r+b") as f:
        for i, n in enumerate(plan):
            rank = int(np.searchsorted(cdf, (i + 0.5) / len(plan)))
            r = int(ranking[min(rank, n_regions - 1)])
            if r not in free:
                free[r] = list(rng.permutation(region // slot))
            at = r * region + int(free[r].pop()) * slot \
                + int(rng.integers(0, (slot - n) // align + 1)) * align
            f.seek(at)
            f.write(rng.bytes(n))
    return root
