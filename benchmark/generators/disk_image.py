"""Tree generator ``disk_image``: one directory holding one VM disk image
(``images/disk0.img``) of ``image_bytes`` seeded bytes, all of them the
image's own: generation 0 stores the whole image."""

from __future__ import annotations

from pathlib import Path

import numpy as np

IMAGE = "images/disk0.img"
BLOCK = 16 << 20  # written a block at a time


def build(root: Path, params: dict, rng: np.random.Generator) -> None:
    path = root / IMAGE
    path.parent.mkdir(parents=True, exist_ok=True)
    total = int(params["image_bytes"])
    with open(path, "wb") as f:
        for at in range(0, total, BLOCK):
            f.write(rng.bytes(min(BLOCK, total - at)))
