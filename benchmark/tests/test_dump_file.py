"""``dump_file`` / ``dump_edit`` (``dump-1m.incr``): the generators are
functions of seed and generation; a night is the traffic file's edits
(8 inserts, 4 deletes, 4 overwrites, its fixed lengths, one in each
sixteenth of the file, at offsets aligned to nothing); the traffic
carries the configuration's chunker and the rehearse blocks their small
trees; at the cell's own size the reference's new bytes of every night
lie in the band the traffic file states and within 10 % of their median,
and the cuts the generator carries forward are the reference's; the
readers this cell brings return nothing where there is nothing to read;
the rehearsal's verdict is true.  The reference and the generators only:
no device is touched except by the rehearsal, on the CPU."""

import hashlib
import json
import statistics
import subprocess
import sys

import numpy as np
import pytest

from benchmark import check, kernel_bytes_dump, readers, specs
from benchmark.generators import dump_edit, dump_file

CELL = "dump-1m.incr"
SEEDS = [1, 2, 3, 4, 5, 6, 7, 2**31 + 12345]
MiB = 1 << 20


def _cell(rehearse=False):
    cell = specs.cell(CELL, rehearse=rehearse)
    return cell["config"], cell["traffic"]


def _night(root, work, traffic, seed, g):
    return dump_edit.step(root, traffic["params"],
                          np.random.default_rng([seed, g]),
                          {"generation": g, "work": work, "seed": seed})


def _sha(root):
    return hashlib.sha256((root / dump_file.DUMP).read_bytes()).hexdigest()


def test_the_same_seed_gives_the_same_bytes_and_a_night_drifts_by_its_edits(
        tmp_path):
    cfg, traffic = _cell(rehearse=True)
    drift = sum(n if kind == "insert" else -n for kind, n
                in dump_edit.edit_kinds(traffic["params"])
                if kind != "overwrite")
    runs = {}
    for tag, seed in (("a", SEEDS[-1]), ("b", SEEDS[-1]), ("c", SEEDS[0])):
        root = tmp_path / tag / "src"
        dump_file.build(root, cfg["tree"]["params"],
                        np.random.default_rng([seed, 0]))
        assert check.census(root) == {"files": 1,
                                      "bytes": cfg["dump_bytes"]}
        digests = [_sha(root)]
        for g in (1, 2, 3):
            assert _night(root, tmp_path / tag, traffic, seed, g) == root
            assert check.census(root) == {
                "files": 1, "bytes": cfg["dump_bytes"] + g * drift}
            digests.append(_sha(root))
        assert len(set(digests)) == 4  # every night its own bytes
        runs[tag] = digests
    assert runs["a"] == runs["b"]
    assert not set(runs["a"]) & set(runs["c"])


def test_a_night_is_the_traffic_file_s_edits_one_in_each_sixteenth():
    cfg, traffic = _cell()
    p = traffic["params"]
    kinds = dump_edit.edit_kinds(p)
    assert [k for k, _n in kinds].count("insert") == 8
    assert [k for k, _n in kinds].count("delete") == 4
    assert [n for k, n in kinds if k == "overwrite"] == [4096, 8192, 16384,
                                                         65536]
    twelve = p["insert_delete_bytes"]
    assert twelve == sorted(twelve) and len(twelve) == 12
    assert (twelve[0], twelve[-1]) == (4096, MiB)
    steps = [b / a for a, b in zip(twelve, twelve[1:])]
    assert max(steps) / min(steps) < 1.001  # spaced log-evenly
    assert [n for k, n in kinds if k == "delete"] == twelve[2::3]
    assert p["margin_bytes"] >= cfg["cdc"]["max_size"]
    # where they fall (no file is read where the draw is not screened)
    size = cfg["dump_bytes"]
    old = np.broadcast_to(np.uint8(0), (size,))
    seen_orders = set()
    for seed in SEEDS:
        for g in (1, 2):
            edits = dump_edit.plan(old, None, p,
                                   np.random.default_rng([seed, g]))
            assert sorted((len(e.fresh), e.removed) for e in edits) == sorted(
                (0 if k == "delete" else n, 0 if k == "insert" else n)
                for k, n in kinds)  # the same multiset every seed and night
            for slot, e in enumerate(edits):
                lo, hi = slot * size // 16, (slot + 1) * size // 16
                assert lo + p["margin_bytes"] <= e.at
                assert e.at + max(e.removed, len(e.fresh)) \
                    <= hi - p["margin_bytes"]
            assert any(e.at % 512 for e in edits)  # aligned to nothing
            seen_orders.add(tuple(len(e.fresh) - e.removed for e in edits))
    assert len(seen_orders) == 2 * len(SEEDS)  # the seed permutes the slots


def test_traffic_carries_the_chunker_and_rehearse_blocks_their_small_trees(
        tmp_path):
    cfg, traffic = _cell()
    assert cfg["dump_bytes"] == cfg["tree"]["params"]["dump_bytes"] \
        == 480 * MiB
    assert (traffic["base"], traffic["warmup_generations"]) == \
        ("config_tree", 1)
    lo, hi = (traffic["params"][k] for k in ("new_bytes_min",
                                             "new_bytes_max"))
    assert hi - lo <= 0.14 * lo  # a band of +-7 % at the most
    small, small_traffic = _cell(rehearse=True)
    for c, t in ((cfg, traffic), (small, small_traffic)):
        assert t["params"]["cdc"] == c["cdc"]
        assert c["tree"]["generator"] == "dump_file"
        assert c["tree"]["params"] == {
            "dump_bytes": c["dump_bytes"], "cdc": c["cdc"],
            "first_cut_within_bytes": check.ORACLE_SAMPLE_BYTES}
        slot = c["dump_bytes"] // t["params"]["slots"]
        assert slot > 2 * t["params"]["margin_bytes"] + max(
            t["params"]["insert_delete_bytes"])
    assert small["dump_bytes"] == 12 * MiB < 256 * MiB  # the batched route
    assert not small_traffic["params"]["new_bytes_max"]  # unscreened
    assert small["deployment"] == cfg["deployment"] and small["warm"] == {}


def test_the_dump_s_first_cut_lies_inside_the_check_s_oracle_sample(
        tmp_path):
    """A first chunk of 2 MiB or more leaves the check's oracle sample
    of a one-file tree empty (17 of 1,000 seeds): ``dump_file`` draws
    the head again, and every other seed's bytes are what they were."""
    cfg, _traffic = _cell(rehearse=True)
    params = specs.cdc_params(cfg)
    plain = {"dump_bytes": cfg["dump_bytes"]}

    def first_chunk(root):
        return dump_edit.chunked(check._read(root / dump_file.DUMP),
                                 params)[0][1]

    long_seed = short_seed = None
    for seed in range(4300000217, 4300000417):
        root = tmp_path / f"plain-{seed}"
        dump_file.build(root, plain, np.random.default_rng([seed, 0]))
        if first_chunk(root) >= check.ORACLE_SAMPLE_BYTES:
            long_seed = seed
        else:
            short_seed = seed
        if long_seed and short_seed:
            break
    for seed in (long_seed, short_seed):
        root = tmp_path / f"screened-{seed}"
        dump_file.build(root, cfg["tree"]["params"],
                        np.random.default_rng([seed, 0]))
        assert first_chunk(root) < check.ORACLE_SAMPLE_BYTES
        sample = check.oracle_sample(root, params, seed)
        assert sum(len(refs) for _path, refs in sample) >= 1
        assert (_sha(root) == _sha(tmp_path / f"plain-{seed}")) \
            == (seed == short_seed)


@pytest.mark.parametrize("seeds,nights", [
    pytest.param(SEEDS[-1:], 3, id="one-seed"),
    # 8 seeds x 8 nights of 480 MiB by the C reference (~6 minutes)
    pytest.param(SEEDS, 8, id="cell-size", marks=pytest.mark.slow)])
def test_a_night_s_new_bytes_lie_in_the_stated_band(tmp_path, seeds, nights):
    cfg, traffic = _cell()
    params = specs.cdc_params(cfg)
    p = traffic["params"]
    new_bytes = []
    for seed in seeds:
        work = tmp_path / str(seed)
        root = work / "src"
        dump_file.build(root, cfg["tree"]["params"],
                        np.random.default_rng([seed, 0]))
        reference = check.Reference(params)
        first = reference.observe(root)
        assert first["new_bytes"] == cfg["dump_bytes"]
        assert abs(first["chunks"] - cfg["fingerprint_population"]) <= 20
        for g in range(1, nights + 1):
            _night(root, work, traffic, seed, g)
            seen = reference.observe(root)
            assert p["new_bytes_min"] <= seen["new_bytes"] \
                <= p["new_bytes_max"], (seed, g, seen["new_bytes"])
            assert 12 <= seen["new_chunks"] <= 28
            new_bytes.append(seen["new_bytes"])
            # two windows, never one and never a third
            assert 256 * MiB < check.census(root)["bytes"] < 512 * MiB
        # the cuts carried forward edit by edit are the reference's
        with np.load(work / dump_edit.KEPT) as kept:
            starts, _digests = dump_edit.chunked(
                check._read(root / dump_file.DUMP), params)
            assert np.array_equal(kept["starts"], starts)
    median = statistics.median(new_bytes)
    assert 0.9 * median <= min(new_bytes) and max(new_bytes) <= 1.1 * median


def test_the_cell_s_new_readers_return_nothing_where_nothing_is_to_read():
    """The parent of PR 43 has neither the carried bytes nor the window
    count, and a rehearsal has no device trace: the readers leave their
    metrics out and do not raise."""
    backup = {"user_bytes": 720 * MiB, "wall_s": 3.5,
              "pipeline": {"stream": {"uploaded_bytes": 721 * MiB,
                                      "host_assembled_bytes": 3 * MiB},
                           "padded_bytes": {"digest": 1440 * MiB}}}
    ctx = {"backups": [backup], "trace": None, "traced": backup,
           "meters": {}, "device": {"kind": "TPU v5 lite"}}

    def read(name):
        return readers.read(name, specs.layer_metric(name), ctx)

    assert read("dump_carried_per_user_byte") is None
    assert read("dump_gather_digest_hbm_share") is None
    assert read("dump_hbm_floor_share") is None
    assert read("dump_host_assembled_per_user_byte") == 3 / 720
    assert read("dump_digest_padded_per_user_byte") == 2.0
    backup["pipeline"]["stream"]["carried_bytes"] = 2 * MiB
    assert read("dump_carried_per_user_byte") == 2 / 720
    # a trace whose four programs hold the gather-and-digest program
    ctx["trace"] = {"busy_s": 0.5, "window_s": 3.5, "device_ops": [
        ["program jit__gather_digest", 0.25],
        ["program jit__scan_segment", 0.2], ["fusion.1", 0.3]]}
    floor = kernel_bytes_dump.gather_digest_floor_seconds(720 * MiB, 819e9)
    assert read("dump_gather_digest_hbm_share") == 100.0 * floor / 0.25
    assert read("dump_hbm_floor_share") == 100.0 * floor / 0.5
    ctx["trace"]["device_ops"] = [["program jit__scan_segment", 0.2]]
    assert read("dump_gather_digest_hbm_share") is None


def test_rehearsal_ends_with_a_true_verdict():
    """``run.py --rehearse``: the whole cell at the rehearsal size on the
    CPU, every comparison of the check sound."""
    done = subprocess.run(
        [sys.executable, str(specs.BENCH / "run.py"), "--workload", CELL,
         "--seed", str(SEEDS[-1]), "--seconds", "4", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=1200,
        cwd=specs.ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal_verdict"] is True
    assert line["correct"] is False  # a rehearsal is never a measurement
    for name in ("dump_wall_pack_s_per_gib", "dump_wall_attributed_share",
                 "dump_pack_attributed_share", "dump_stored_per_user_byte",
                 "dump_digest_padded_per_user_byte",
                 "dump_compiles_in_window"):
        assert name in line["metrics"], name
