"""The trace reduction on a small recording kept beside it
(``data/small.xplane.pb``: three runs of one jitted 2048x2048 bf16 matrix
product on a TPU v5 lite with 50 ms of host sleep between them, recorded
by ``record_fixture.py`` in PR 24).  The expected numbers were read from
the same file by another reader (TensorFlow's ``xplane_pb2``, at
picosecond precision): 9 operations on ``/device:TPU:0``, 272.681 us
busy, two gaps of 51.2 and 51.3 ms."""

from pathlib import Path

import pytest

from benchmark import tracered

FIXTURE = str(Path(__file__).resolve().parent / "data" / "small.xplane.pb")


def test_busy_idle_and_breakdown_of_the_known_trace():
    r = tracered.reduce_xplane(FIXTURE, 0.5, phase="backup:2")
    assert r["device_planes"] == ["/device:TPU:0"]  # not the CUSTOM plane
    assert r["device_events"] == 9
    assert r["busy_s"] == pytest.approx(272.681e-6, rel=1e-4)
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / 0.5, rel=1e-12)
    assert r["device_ops"][0][0] == "program jit__lambda"
    assert r["device_ops"][0][1] == pytest.approx(272.69e-6, rel=1e-3)
    assert r["device_ops"][1][0].startswith("%fusion = bf16[2048,2048]")
    gaps = [g for g in r["idle_gaps"] if g[1] > 0.01]
    assert [round(s, 4) for _n, s in gaps] == [0.0513, 0.0512]
    assert all(n.startswith("backup:2 after %fusion") for n, _s in gaps)
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10


def test_union_counts_an_overlap_once():
    busy, gaps = tracered._union([(0.0, 10.0, "loop"), (2.0, 5.0, "body"),
                                  (20.0, 30.0, "next")])
    assert busy == 20.0
    assert gaps == [(10.0 / 1e9, "loop")]

