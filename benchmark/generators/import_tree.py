"""Traffic generator ``import_tree``: every generation is a directory the
store has never seen (a first backup, or an import).

``step`` builds generation *g* as ``work/import-<g>`` with the tree
generator the traffic names (``tree_generator``, from ``tree_params``)
and the run's ``rng`` for that generation, removes the directory of
generation *g* - ``keep_generations``, and returns the new one.  Nothing
is carried from one generation to the next: each one's bytes come from
the run's seed and its own number, so overlap with everything backed up
before is 0, and the only chunks a backup finds again are those the tree
repeats inside itself (``home_tree``'s ``f1``).  Every count and size is
the tree generator's fixed list; only the bytes are seeded.

Equal work (``f1_new_bytes_max``, with the traffic's ``cdc``): how soon
content-defined chunking finds ``f0``'s chunks again behind ``f1``'s
insertion is dice at 1 MiB chunks (1.3 to 16 MiB of ``f1`` new over 64
draws, PERF.md section 4), which alone would put a generation's new
bytes 15 % apart.  So the pair is drawn first, in memory and from a copy
of ``rng`` (``home_tree.build`` draws ``f0``, then the insertion), the
reference chunks both, and a draw whose ``f1`` brings more new bytes
than the limit is passed over: ``rng`` moves on by ``f0``'s length and
the next draw is tried.  The tree is then built from ``rng`` as it
stands, so the same seed gives the same bytes.
"""

from __future__ import annotations

import copy
import shutil
from pathlib import Path

import numpy as np

from benchmark import specs
from benchmark.reference import native

REDRAWS = 64  # a draw passes with probability ~0.8


def directory(work: Path, generation: int) -> Path:
    return Path(work) / f"import-{generation}"


def f1_new_bytes(f0: bytes, insertion: bytes, at: int, cdc) -> int:
    """Bytes of ``f1``'s chunks (``f0`` with ``insertion`` put in at
    ``at``) that are not chunks of ``f0``, by the reference."""
    old = {d for _o, _n, d in native.manifest(
        np.frombuffer(f0, dtype=np.uint8), cdc)}
    f1 = np.frombuffer(f0[:at] + insertion + f0[at:], dtype=np.uint8)
    return sum(n for _o, n, d in native.manifest(f1, cdc) if d not in old)


def _settle(rng: np.random.Generator, tree: dict, limit: int, cdc) -> None:
    """Leaves ``rng`` at a draw whose ``f1`` stays under ``limit``."""
    big = int(tree["big_bytes"])
    for _ in range(REDRAWS):
        probe = copy.deepcopy(rng)
        f0 = probe.bytes(big)
        insertion = probe.bytes(int(tree["big_insert_bytes"]))
        at = int(big * float(tree["big_insert_at"]))
        if f1_new_bytes(f0, insertion, at, cdc) <= limit:
            return
        rng.bytes(big)
    raise SystemExit(f"import_tree: no draw of {REDRAWS} kept f1's new "
                     f"bytes under {limit}")


def step(root: Path, params: dict, rng: np.random.Generator,
         ctx: dict) -> Path:
    generation = int(ctx["generation"])
    new = directory(ctx["work"], generation)
    if params.get("f1_new_bytes_max"):
        _settle(rng, params["tree_params"], int(params["f1_new_bytes_max"]),
                specs.cdc_params(params))
    specs.generator(params["tree_generator"]).build(
        new, params["tree_params"], rng)
    gone = directory(ctx["work"],
                     generation - int(params["keep_generations"]))
    shutil.rmtree(gone, ignore_errors=True)
    return new
