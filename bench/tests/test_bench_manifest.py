"""BENCHMARK.json keeps to the benchmark's rules, and every name in it
leads to a file of the benchmark: a configuration, a traffic mix, a
reference, a work count and one reader per per-layer metric."""
import re

import pytest

from harness import common

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}
MANIFEST = common.load_json(common.ROOT / "BENCHMARK.json")


@pytest.fixture(params=["committed"])
def manifest(request):
    return MANIFEST


def test_top_level_keys_command_and_paths():
    assert set(MANIFEST) == TOP
    assert MANIFEST["command"] == ["python3", "bench/run.py"]
    for p in MANIFEST["paths"]:
        assert (common.ROOT / p).is_dir() and not p.startswith("/")
    # a full check of 24 cells fits its 43200 s
    rs = MANIFEST["run_seconds"]
    assert 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_lengths(manifest):
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in manifest[key]]
        assert len(names) == len(set(names)), key
        assert all(NAME.match(n) for n in names), names
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in manifest["workloads"]:
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in manifest["configs"]:
        assert len(c["why"]) <= 200 and len(c["reduced"]) <= 16


def test_every_name_leads_to_its_files(manifest):
    m = common.Manifest(common.ROOT / "BENCHMARK.json")
    m.data = manifest
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}
    for w in manifest["workloads"]:
        conf = m.config(w["config"])
        assert conf["name"] == w["config"]
        assert m.mix(w["traffic"])["kind"] == conf["runner"]
        m.reference(conf)
        m.work(conf)
    for metric in manifest["per_layer"]:
        assert callable(m.reader(metric["name"]).read)


def test_bounds_and_every_cell_reports_enough(manifest):
    e2e = {x["name"]: x for x in manifest["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e[
        "setup_s"]
    assert all(0.01 <= x["bound"] <= 0.25 for x in e2e.values())
    assert all(x["source"] in ("host_clock", "device_trace")
               for x in e2e.values())
    m = common.Manifest(common.ROOT / "BENCHMARK.json")
    m.data = manifest
    for w in manifest["workloads"]:
        names = {x["name"] for x in m.end_to_end(w["name"])}
        assert "setup_s" in names and len(names) >= 2
        layers = m.per_layer(w["name"])
        assert layers
        # each per-layer metric moves an end-to-end metric its cells report
        assert all(x["moves"] in names for x in layers)


def test_roofline_and_utilization_names(manifest):
    for x in manifest["per_layer"]:
        if "roofline" in x["name"] or "mfu" in x["name"]:
            assert x["unit"] == "%"
        if "roofline" in x["name"]:
            assert x["name"].split(".")[0].endswith("_roofline")
