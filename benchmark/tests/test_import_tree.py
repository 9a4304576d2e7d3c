"""``import_tree``: the same seed gives the same bytes, a generation's
directory is gone once the next is built, and at the cell's own size
(the reference only, no device) over 4 seeds x 6 generations no chunk of
a generation but ``f1``'s is in any earlier one and a generation's new
bytes have max / min <= 1.03 (ISSUE 36)."""

import hashlib

import numpy as np

from benchmark import check, specs
from benchmark.generators import import_tree
from benchmark.reference import native

CELL = "ref-1m-fresh.import"
SEEDS = [1, 2, 3, 2**31 + 12345]
GENERATIONS = 6


def _generations(tmp, seed, n, rehearse):
    """Yields (generation, root) as the harness steps them."""
    traffic = specs.cell(CELL, rehearse=rehearse)["traffic"]
    root = tmp / "src"  # base "none": nothing is there
    for g in range(1, n + 1):
        root = import_tree.step(
            root, traffic["params"], np.random.default_rng([seed, g]),
            {"generation": g, "work": tmp, "seed": seed})
        yield g, root


def _tree_digest(root):
    h = hashlib.sha256()
    for p in check.tree_files(root):
        h.update(str(p.relative_to(root)).encode())
        h.update(p.read_bytes())
    return h.hexdigest()


def test_the_same_seed_gives_the_same_bytes_and_the_last_directory_goes(
        tmp_path):
    runs = {}
    for tag, seed in (("a", SEEDS[-1]), ("b", SEEDS[-1]), ("c", SEEDS[0])):
        digests = []
        for g, root in _generations(tmp_path / tag, seed, 3, rehearse=True):
            assert root == tmp_path / tag / f"import-{g}"
            assert not (tmp_path / tag / f"import-{g - 1}").exists()
            digests.append(_tree_digest(root))
        assert sorted(p.name for p in (tmp_path / tag).iterdir()) == [
            "import-3"]
        assert len(set(digests)) == 3  # every generation its own bytes
        runs[tag] = digests
    assert runs["a"] == runs["b"] and runs["a"] != runs["c"]


def test_traffic_carries_the_configuration_s_tree():
    for rehearse in (False, True):
        cell = specs.cell(CELL, rehearse=rehearse)
        assert cell["traffic"]["params"]["tree_params"] == \
            cell["config"]["tree"]["params"]
        assert cell["traffic"]["params"]["tree_generator"] == \
            cell["config"]["tree"]["generator"]
        assert cell["traffic"]["base"] == "none"
    assert specs.cell(CELL)["config"]["tree_bytes"] == 157058823


def test_every_generation_is_new_to_the_store_and_the_same_work(tmp_path):
    """The cell's size: ~150 MiB a generation, 24 of them, chunked by
    the reference (about a minute on the host)."""
    params = specs.cdc_params(specs.cell(CELL)["config"])
    new_bytes = []
    for seed in SEEDS:
        reference = check.Reference(params)
        earlier: set = set()
        for _g, root in _generations(tmp_path / str(seed), seed,
                                     GENERATIONS, rehearse=False):
            census = check.census(root)
            assert census == {"files": 534, "bytes": 157058823}
            mine, f1 = set(), set()
            for path in check.tree_files(root):
                into = f1 if path.name == "f1" else mine
                into.update(d for _o, _n, d in native.manifest(
                    check._read(path), params))
            assert not (mine & earlier)
            assert f1 & mine  # f1 finds f0 again behind the insertion
            assert not ((f1 - mine) & earlier)
            earlier |= mine | f1
            new_bytes.append(reference.observe(root)["new_bytes"])
    assert max(new_bytes) <= 1.03 * min(new_bytes), new_bytes
    # ~103 MiB of the 149.8 are new: f1 gives back ~46 MiB of f0
    assert 100 << 20 <= min(new_bytes) and max(new_bytes) <= 108 << 20
