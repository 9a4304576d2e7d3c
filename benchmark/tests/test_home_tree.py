"""``home_tree`` and ``tree_churn``: the same seed gives the same bytes,
every seed the same sizes and counts, and every seed and night the same
work to within the numbers ISSUE 34 fixed: over 8 seeds x 12 nights the
reference's new bytes a night have max / min <= 1.25, and a run's 12
nights sum to within 3 % between seeds."""

import hashlib
import json
import subprocess
import sys

import numpy as np
import pytest

from benchmark import check, specs
from benchmark.generators.home_tree import small_sizes

CELL = "ref-1m.incr"
SEEDS = [1, 2, 3, 4, 5, 6, 7, 2**31 + 12345]
NIGHTS = 12


def _tree_digest(root):
    h = hashlib.sha256()
    for p in check.tree_files(root):
        h.update(str(p.relative_to(root)).encode())
        h.update(p.read_bytes())
    return h.hexdigest()


def _run(tmp, seed, nights, rehearse=True):
    """Generation 0 and ``nights`` nights: per night the tree's digest
    where asked, the sorted small-file sizes, the generator's own counts
    and the reference's new bytes."""
    cell = specs.cell(CELL, rehearse=rehearse)
    cfg, traffic = cell["config"], cell["traffic"]
    root = tmp / "src"
    specs.generator(cfg["tree"]["generator"]).build(
        root, cfg["tree"]["params"], np.random.default_rng([seed, 0]))
    reference = check.Reference(specs.cdc_params(cfg))
    reference.observe(root)
    out = []
    for g in range(1, nights + 1):
        root = specs.generator(traffic["generator"]).step(
            root, traffic["params"], np.random.default_rng([seed, g]),
            {"generation": g, "work": tmp, "seed": seed})
        last = json.loads((tmp / "tree_churn.json").read_text())["last"]
        out.append({
            "digest": _tree_digest(root) if nights <= 2 else None,
            "sizes": sorted(p.stat().st_size
                            for p in (root / "small").iterdir()),
            "counts": {k: last[k] for k in (
                "deleted", "rewritten", "added", "rewritten_bytes",
                "added_bytes", "f0_chunks_gone")},
            "new_bytes": reference.observe(root)["new_bytes"]})
    return out


@pytest.mark.parametrize("span, builds", [
    ("batch.compile", True), ("no.such.span", False)])
def test_the_tree_is_built_only_for_a_program_with_the_span_it_names(
        tmp_path, span, builds):
    """The cell's tree names the span of the side-by-side compile: a
    program without it (the parent of PR 34) cannot back the tree up
    inside a run, and its run ends here, at once and not at the time
    limit, with nothing written."""
    from benchmark.generators import home_tree
    params = dict(specs.cell(CELL, rehearse=True)["config"]["tree"]["params"],
                  needs_program_span=span)
    assert specs.cell(CELL)["config"]["tree"]["params"][
        "needs_program_span"] == "batch.compile"
    if builds:
        home_tree.build(tmp_path / "src", params, np.random.default_rng(1))
        assert (tmp_path / "src" / "big" / "f0").exists()
    else:
        with pytest.raises(SystemExit) as e:
            home_tree.build(tmp_path / "src", params,
                            np.random.default_rng(1))
        assert e.value.code not in (0, None)
        assert not (tmp_path / "src").exists()


def test_the_same_seed_gives_the_same_tree_and_nights(tmp_path):
    a = _run(tmp_path / "a", SEEDS[-1], 2)
    b = _run(tmp_path / "b", SEEDS[-1], 2)
    c = _run(tmp_path / "c", SEEDS[0], 2)
    assert a == b
    assert [n["digest"] for n in a] != [n["digest"] for n in c]


def test_sizes_and_counts_are_the_same_for_every_seed(tmp_path):
    cell = specs.cell(CELL, rehearse=True)
    t = cell["traffic"]["params"]
    runs = [_run(tmp_path / str(s), s, 3) for s in SEEDS[:3]]
    for night in range(3):
        sizes = [r[night]["sizes"] for r in runs]
        assert sizes[0] == sizes[1] == sizes[2]
        for r in runs:
            got = r[night]["counts"]
            assert (got["deleted"], got["rewritten"], got["added"]) == (
                t["small_deleted"], t["small_rewritten"], t["small_added"])
            assert got["f0_chunks_gone"] == \
                t["f0_overwrites"] + t["f0_insertions"]
            assert got["added_bytes"] == runs[0][night]["counts"][
                "added_bytes"]
    # the files' own sizes at the cell's size: a night's rewritten bytes
    # are the same to 1 %
    full = specs.cell(CELL)["config"]["tree"]["params"]
    sizes = small_sizes(full)
    assert len(sizes) == 1000 and (sizes[0], sizes[-1]) == (1024, 102400)
    width = len(sizes) // 20
    sums = [sum(sizes[k * width + (j if k % 2 == 0 else width - 1 - j)]
                for k in range(20)) for j in range(width)]
    assert max(sums) <= 1.01 * min(sums)


def test_traffic_states_the_configuration_s_tree_and_cdc():
    for rehearse in (False, True):
        cell = specs.cell(CELL, rehearse=rehearse)
        tree = cell["config"]["tree"]["params"]
        t = cell["traffic"]["params"]
        assert t["cdc"] == cell["config"]["cdc"]
        assert t["small_list"] == {k: tree[k] for k in t["small_list"]}
        assert t["rule"] == "chunk_targeted"


def _equal_work(tmp_path, rehearse):
    nights = {s: [n["new_bytes"] for n in
                  _run(tmp_path / str(s), s, NIGHTS, rehearse)]
              for s in SEEDS}
    every = [n for v in nights.values() for n in v]
    sums = [sum(v) for v in nights.values()]
    return max(every) / min(every), max(sums) / min(sums)


@pytest.mark.parametrize("rehearse", [
    pytest.param(True, id="rehearsal-size"),
    # ~311 MiB a seed: for the chip host (or any host with the minutes),
    # ``-m slow``; the numbers are the reference's, no device is touched
    pytest.param(False, id="cell-size", marks=pytest.mark.slow)])
def test_every_seed_and_night_is_the_same_work(tmp_path, rehearse):
    night_ratio, sum_ratio = _equal_work(tmp_path, rehearse)
    assert night_ratio <= 1.25, night_ratio
    assert sum_ratio <= 1.03, sum_ratio


@pytest.mark.slow
def test_rehearsal_ends_with_a_true_verdict():
    """``run.py --rehearse``: the whole cell at the rehearsal size on the
    CPU (~3 minutes), every comparison of the check sound."""
    done = subprocess.run(
        [sys.executable, str(specs.BENCH / "run.py"), "--workload", CELL,
         "--seed", str(SEEDS[-1]), "--seconds", "4", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=1200,
        cwd=specs.ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal_verdict"] is True
    assert line["correct"] is False  # a rehearsal is never a measurement
    assert "batch_device_decided_share" in line["metrics"]
