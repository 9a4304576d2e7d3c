"""The generators are deterministic in the seed and write the byte
counts the files state."""

import hashlib
import json

import numpy as np
import pytest

from benchmark import check, specs


def _digest(root):
    h = hashlib.sha256()
    for p in check.tree_files(root):
        h.update(str(p.relative_to(root)).encode())
        h.update(p.read_bytes())
    return h.hexdigest()


def _generations(tmp, workload, seed, n):
    cell = specs.cell(workload, rehearse=True)
    cfg, traffic = cell["config"], cell["traffic"]
    root = tmp / "src"
    if traffic["base"] == "config_tree":
        specs.generator(cfg["tree"]["generator"]).build(
            root, cfg["tree"]["params"], np.random.default_rng([seed, 0]))
    out = []
    for g in range(1, n + 1):
        root = specs.generator(traffic["generator"]).step(
            root, traffic["params"], np.random.default_rng([seed, g]),
            {"generation": g, "work": tmp, "seed": seed})
        out.append((_digest(root), check.census(root)))
    return out


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      specs.benchmark()["workloads"]])
def test_same_seed_same_bytes(tmp_path, workload):
    big = 2**31 + 12345  # more than 32 signed bits hold
    a = _generations(tmp_path / "a", workload, big, 2)
    b = _generations(tmp_path / "b", workload, big, 2)
    c = _generations(tmp_path / "c", workload, big + 1, 2)
    assert a == b
    assert [d for d, _ in a] != [d for d, _ in c]
    # another seed, the same amount of work
    assert [n["bytes"] for _, n in a] == [n["bytes"] for _, n in c]
    assert a[0][0] != a[1][0]


def test_image_has_the_stated_bytes_and_nothing_repeats(tmp_path):
    cfg = specs.rehearsed(json.loads(
        (specs.BENCH / "configs" / "vm-64k.json").read_text()))
    specs.generator("disk_image").build(
        tmp_path / "t", cfg["tree"]["params"], np.random.default_rng(1))
    assert check.census(tmp_path / "t") == {"files": 1,
                                            "bytes": cfg["image_bytes"]}
    data = (tmp_path / "t" / "images" / "disk0.img").read_bytes()
    blocks = {data[at:at + 4096] for at in range(0, len(data), 4096)}
    assert len(blocks) == len(data) // 4096


def test_image_overwrite_plan_is_the_stated_share():
    cfg = json.loads((specs.BENCH / "configs" / "vm-64k.json").read_text())
    t = json.loads((specs.BENCH / "traffic" /
                    "nightly-image.json").read_text())
    from benchmark.generators.image_overwrite import write_plan
    plan = write_plan(cfg["image_bytes"], t["params"])
    assert cfg["image_bytes"] == cfg["tree"]["params"]["image_bytes"]
    assert cfg["image_bytes"] % t["params"]["region_bytes"] == 0
    share = sum(plan) / cfg["image_bytes"]
    assert 0.03 <= share < 0.031
    assert cfg["image_bytes"] > 256 << 20  # the packer's streaming route
