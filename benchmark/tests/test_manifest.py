"""BENCHMARK.json against the files it names and the rules of its
contract that a file can break."""

import json
import os
import re

import pytest

from benchmark import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return manifest.load_manifest()


def cells(bench):
    return [manifest.Cell(bench, w["name"]) for w in bench["workloads"]]


def test_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            for key in ("why", "layer", "source"):
                if key in entry and group != "end_to_end":
                    assert 1 <= len(entry[key]) <= 200 and "\t" not in entry[key]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_every_cell_resolves_its_files(bench):
    used = set()
    for cell in cells(bench):
        used.add(cell.config_name)
        assert cell.chips == cell.config["chips"]
        assert hasattr(cell.deployment(), "Deployment")
        assert cell.reference().expected(b"a", b"b") == (b"a", b"b")
        assert cell.traffic["arrival"] in ("closed", "open")
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer, cell.name
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]))
            assert m["moves"] in reported, (cell.name, m["name"])
    assert used == {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        path = os.path.join(manifest.ROOT, c["file"])
        assert c["file"].startswith("benchmark/") and os.path.exists(path)
        with open(path, encoding="utf-8") as f:
            assert json.load(f)["reduced"] == c["reduced"]


def test_layers_are_named_as_perf_md_names_them(bench):
    with open(os.path.join(manifest.ROOT, "PERF.md"), encoding="utf-8") as f:
        perf = f.read()
    for m in bench["per_layer"]:
        assert f"| {m['layer']} |" in perf, m["layer"]


def test_peaks_name_their_source():
    peaks = manifest.load_json("peaks.json")
    assert "Google Cloud" in peaks["source"]
    assert peaks["device_kinds"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_each_configuration_states_an_allocator_policy_that_applies(bench):
    from benchmark import allocator

    for cell in cells(bench):
        allocator.apply(cell.config["allocator"])
        assert "allocator" in cell.config["assumed"]
    with pytest.raises(KeyError):
        allocator.apply({"no_such_knob": 1})
