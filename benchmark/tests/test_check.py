"""The check passes a sound record and fails each control: digests taken
over fewer bytes, a reference with another CDCParams, and a backup whose
holders persisted less than the new chunks x (k+m)/k.  No program code
runs here: the reference stands in the program's place."""

import numpy as np

from benchmark import check, specs
from benchmark.reference.gear import CDCParams

PARAMS = CDCParams(4096, 16384, 49152, 16, 12)
K, M = 4, 2


def _record(tmp_path, seed=3):
    cell = specs.cell("vm-64k.incr", rehearse=True)
    root = tmp_path / "src"
    specs.generator("disk_image").build(
        root, cell["config"]["tree"]["params"], np.random.default_rng(seed))
    ref = check.Reference(PARAMS)
    backups, recorded, placements = [], {}, []
    for g in range(2):
        if g:
            specs.generator("image_overwrite").step(
                root, cell["traffic"]["params"],
                np.random.default_rng([seed, g]),
                {"generation": g, "work": tmp_path, "seed": seed})
        obs = ref.observe(root)
        recorded.update(obs["fresh"])
        census = check.census(root)
        total = int(obs["new_bytes"] * (K + M) / K * 1.002)
        stored = [total // 6] * 5 + [total - 5 * (total // 6)]
        backups.append({
            "generation": g, "timed": bool(g), "census": census,
            "user_bytes": census["bytes"], "ref": obs, "error": None,
            "stats": {"files": census["files"], "failed_files": 0,
                      "bytes_read": census["bytes"],
                      "chunks": obs["chunks"], "dedup_divergences": 0},
            "host_rerun_rows": 0, "unsent_packfiles": 0,
            "stored": stored, "stored_total": total,
            "placed": {"packfiles": 3, "whole_copies": 0,
                       "partial_stripes": 0},
            "summary": {"size": int(obs["new_bytes"] * 1.002)}})
        placements += [(bytes([g]), bytes([i]), 1, i, 0.0)
                       for i in range(K + M)]
    return backups, recorded, placements, root


def test_sound_record_passes_and_every_control_fails(tmp_path):
    backups, recorded, placements, root = _record(tmp_path)
    v = check.judge(backups, recorded, placements, root, PARAMS, 3, K, M)
    assert v.ok, [r for r in v.rows if not r["ok"]]
    out = check.controls(backups, recorded, placements, root, PARAMS, 3,
                         K, M)
    assert out == {"ref_cdc": False, "truncated_digest": False,
                   "short_send": False, "one_whole_copy": False}


def test_a_restored_duplicate_and_a_short_stripe_fail(tmp_path):
    backups, recorded, placements, root = _record(tmp_path)

    def judged(generation, **changed):
        again = [dict(b) for b in backups]
        again[generation].update(changed)
        return check.judge(again, recorded, placements, root, PARAMS, 3,
                           K, M).ok

    assert not judged(1, summary={"size": 2 * backups[1]["summary"]["size"]})
    # after generation 0 a backup is held to k+m exactly: one whole
    # copy, one short stripe or receipts a few per cent short fail it
    sound = backups[1]["placed"]
    assert not judged(1, placed={**sound, "whole_copies": 1})
    assert not judged(1, placed={**sound, "partial_stripes": 1})
    assert not judged(1, stored=[int(n * 0.96)
                                 for n in backups[1]["stored"]])
    # generation 0 (set-up, the program tracing) is bounded, not exact
    assert judged(0, placed={"packfiles": 10, "whole_copies": 1,
                             "partial_stripes": 0},
                  stored=[int(n * 0.97) for n in backups[0]["stored"]])
    assert not judged(0, placed={"packfiles": 10, "whole_copies": 1,
                                 "partial_stripes": 1})
    assert not judged(0, stored=[int(n * 0.9)
                                 for n in backups[0]["stored"]])
    assert not judged(1, stored=[0] + backups[1]["stored"][1:])
    wrong = dict(recorded)
    wrong.pop(next(iter(backups[1]["ref"]["fresh"])))
    assert not check.judge(backups, wrong, placements, root, PARAMS, 3,
                           K, M).ok


def test_placement_census_counts_whole_copies_and_short_stripes():
    def stripe(pid, shards, peers=None):
        peers = peers or shards
        return [(bytes([pid]), bytes([p]), 1, i, 0.0)
                for p, i in zip(peers, shards)]

    rows = (stripe(1, range(6)) + stripe(2, range(5))
            + stripe(3, range(6), peers=[0, 1, 2, 3, 4, 4])
            + [(bytes([4]), bytes([0]), 1, -1, 0.0)]
            + stripe(5, range(6)) + [(bytes([5]), bytes([1]), 1, -1, 0.0)])
    assert check.placement_census(rows, K, M) == {
        "packfiles": 5, "whole_copies": 2, "partial_stripes": 2}


def test_oracle_sample_takes_the_head_of_a_large_file(tmp_path):
    (tmp_path / "d").mkdir()
    data = np.random.default_rng(5).bytes(3 << 20)
    (tmp_path / "d" / "big.bin").write_bytes(data)
    (sample,) = check.oracle_sample(tmp_path, PARAMS, 1)
    whole = check.native.manifest(np.frombuffer(data, np.uint8), PARAMS)
    assert sample[1] == [(n, d) for _o, n, d in whole[:len(sample[1])]]
    assert 0 < len(sample[1]) < len(whole)
