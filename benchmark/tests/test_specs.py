"""Every file under configs/, traffic/ and layer_metrics/ loads and names
only things that exist; BENCHMARK.json keeps to the contract's shapes."""

import importlib
import json
import re

import pytest

from benchmark import readers, specs

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _names(sub):
    return sorted(p.stem for p in (specs.BENCH / sub).glob("*.json"))


def test_benchmark_json_shapes():
    b = specs.benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for entry in b["configs"] + b["workloads"] + b["end_to_end"] \
            + b["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
    for w in b["workloads"]:
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    for c in b["configs"]:
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert c["file"].startswith("benchmark/")
        assert c["name"] in {w["config"] for w in b["workloads"]}
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


@pytest.mark.parametrize("name", _names("configs"))
def test_config_loads(name):
    c = json.loads((specs.BENCH / "configs" / f"{name}.json").read_text())
    assert c["name"] == name
    for spec in (c, specs.rehearsed(c)):
        gen = specs.generator(spec["tree"]["generator"])
        assert callable(gen.build)
    specs.cdc_params(c)
    entry = [e for e in specs.benchmark()["configs"] if e["name"] == name]
    assert entry and entry[0]["source"] == c["source"]
    assert entry[0]["reduced"] == c["reduced"]
    for key in c["reduced"]:
        assert key in c and key in c["reduced_why"]
    assert c["guarantees"]


@pytest.mark.parametrize("name", _names("traffic"))
def test_traffic_loads(name):
    t = json.loads((specs.BENCH / "traffic" / f"{name}.json").read_text())
    assert t["name"] == name and t["base"] in ("config_tree", "none")
    assert callable(specs.generator(t["generator"]).step)
    if "tree_generator" in t["params"]:
        assert callable(specs.generator(t["params"]["tree_generator"]).build)


@pytest.mark.parametrize("name", _names("layer_metrics"))
def test_layer_metric_loads(name):
    spec = specs.layer_metric(name)
    entry = [m for m in specs.benchmark()["per_layer"] if m["name"] == name]
    assert entry, f"{name} is not in BENCHMARK.json"
    for key in ("unit", "layer", "moves", "better", "source"):
        assert spec[key] == entry[0][key], key
    kind = spec["reader"]["kind"]
    assert kind in ("engine_report", "registry_sum", "trace", "meter",
                    "module")
    if kind == "module":
        mod = importlib.import_module(f"benchmark.layer_metrics.{name}")
        assert callable(mod.read)


def test_every_cell_resolves():
    b = specs.benchmark()
    for w in b["workloads"]:
        for rehearse in (False, True):
            cell = specs.cell(w["name"], rehearse=rehearse)
            assert cell["per_layer"] and len(cell["end_to_end"]) >= 2
            for m in cell["per_layer"]:
                specs.layer_metric(m["name"])
    with pytest.raises(specs.SpecError):
        specs.cell("no-such.cell")


def test_peaks_unknown_kind_is_an_error():
    assert specs.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(specs.SpecError):
        specs.peaks("TPU v99")


def test_reader_returns_nothing_where_nothing_is_to_read():
    spec = specs.layer_metric("index_device_hit_share")
    ctx = {"backups": [{"user_bytes": 1 << 30, "pipeline": {}}],
           "trace": None, "traced": None, "meters": {}}
    assert readers.read("index_device_hit_share", spec, ctx) is None
    ctx["backups"][0]["pipeline"] = {
        "tier": {"probes": {"device": 3, "host": 1}}}
    assert readers.read("index_device_hit_share", spec, ctx) == 75.0
    assert readers.read("device_idle_share",
                        specs.layer_metric("device_idle_share"), ctx) is None


def test_registry_sum_reader_sums_the_deltas_of_its_series():
    spec = {"reader": {"kind": "registry_sum", "den": "user_gib",
                       "num": {"family": "bkw_span_seconds",
                               "labels": {"name": "engine.pack"}}}}
    (key,) = readers.registry_series([spec])
    assert key == ("bkw_span_seconds", (("name", "engine.pack"),), "sum")
    ctx = {"backups": [{"user_bytes": 1 << 29, "registry": {key: 1.5}},
                       {"user_bytes": 1 << 29, "registry": {key: 2.5}}]}
    assert readers.read("engine_pack_s_per_gib", spec, ctx) == 4.0
