"""Tree generator ``dump_file``: one directory holding one database dump
(``dump/db.dump``) of ``dump_bytes`` seeded bytes, all of them the
dump's own: generation 0 stores the whole file.

``first_cut_within_bytes`` (with the configuration's ``cdc``): the
check's numpy oracles sample a tree's files up to 2 MiB in all
(``check.ORACLE_SAMPLE_BYTES``) and, of a file longer than what is left,
take the head without its last, open chunk; where the tree is one file
and the reference's first chunk is longer than the sample (17 of 1,000
seeds at the shipped 256 KiB / 1 MiB / 3 MiB constants), nothing is left
to compare and the check refuses the run.  So the first block is drawn
again, from ``rng`` as it stands, until the reference's first cut falls
inside that many bytes.  No night's edit comes near it: the first lies
``margin_bytes`` into the file, behind a chunk that starts past the cut.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

from benchmark import specs
from benchmark.reference import native

DUMP = "dump/db.dump"
BLOCK = 16 << 20  # written a block at a time
REDRAWS = 16  # a draw passes with probability 0.985


def _first_block(n: int, params: dict, rng: np.random.Generator) -> bytes:
    within = int(params.get("first_cut_within_bytes", 0))
    block = rng.bytes(n)
    if not within:
        return block
    cdc = specs.cdc_params(params)
    for _ in range(REDRAWS):
        head = np.frombuffer(block, dtype=np.uint8)[:cdc.max_size + 1]
        if native.manifest(head, cdc)[0][1] < within:
            return block
        block = rng.bytes(n)
    raise SystemExit(f"dump_file: no draw of {REDRAWS} cut the dump's "
                     f"head within {within} bytes")


def build(root: Path, params: dict, rng: np.random.Generator) -> None:
    path = root / DUMP
    path.parent.mkdir(parents=True, exist_ok=True)
    total = int(params["dump_bytes"])
    with open(path, "wb") as f:
        for at in range(0, total, BLOCK):
            n = min(BLOCK, total - at)
            f.write(_first_block(n, params, rng) if at == 0
                    else rng.bytes(n))
