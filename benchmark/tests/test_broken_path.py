"""Drives a whole run (everything but the look for a chip) at the tiny
rehearsal size on the CPU, once sound and once with the timed path
broken underneath: the device backend alters one digest where it is
produced.  The sound run's verdict is true, the broken run's false.
About two minutes each (they compile); the compile cache is the
benchmark's own."""

import asyncio
import time

import pytest


def _run(workload, seed, **patch):
    import importlib.util
    from benchmark import specs
    from benchmark.cell import Run
    from benchmark.deployment import Deployment
    spec = importlib.util.spec_from_file_location(
        "bench_run", specs.BENCH / "run.py")
    run_py = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run_py)
    run_py.configure_compile_cache()
    cell = specs.cell(workload, rehearse=True)
    run = Run(cell, seed, 1.0, False, True, time.monotonic(), controls=True)
    return asyncio.run(run.run(Deployment))


@pytest.mark.parametrize("workload", ["vm-64k.incr"])
def test_sound_run_is_correct_and_its_controls_are_not(workload):
    out = _run(workload, 2**31 + 7)
    assert out["verdict"] is True
    assert out["controls"] == {"ref_cdc": False, "truncated_digest": False,
                               "short_send": False, "one_whole_copy": False}
    assert out["failed"] == 0 and out["attempted"] > 0


def test_an_altered_digest_is_not_correct(monkeypatch):
    from backuwup_tpu.ops import backend as be
    real = be.TpuBackend.manifest_many_classified
    calls = {"n": 0}

    def broken(self, streams, dedup):
        out, hints = real(self, streams, dedup)
        calls["n"] += 1
        for refs in out:
            if refs:
                bad = bytes([refs[0].hash[0] ^ 1]) + refs[0].hash[1:]
                refs[0] = be.ChunkRef(refs[0].offset, refs[0].length, bad)
                break
        return out, hints

    monkeypatch.setattr(be.TpuBackend, "manifest_many_classified", broken)
    out = _run("vm-64k.incr", 11)
    assert calls["n"] > 0
    assert out["verdict"] is False
