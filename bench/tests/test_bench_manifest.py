"""BENCHMARK.json against the contract's form, and every piece found by
its name."""
import re

import pytest

import common

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
MAN = common.manifest()


def e2e_of(cell):
    return [m["name"] for m in MAN["end_to_end"]
            if cell in m.get("workloads", [cell])]


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["command"] == ["python3", "bench/run.py"]
    assert MAN["paths"] == ["bench"]
    assert 1 <= MAN["run_seconds"] <= 51


@pytest.mark.parametrize("entry", MAN["configs"] + MAN["workloads"]
                         + MAN["end_to_end"] + MAN["per_layer"],
                         ids=lambda e: e["name"])
def test_names_and_units(entry):
    assert NAME.match(entry["name"])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]


def test_names_unique():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in MAN[group]]
        assert len(names) == len(set(names))
    metrics = [e["name"] for e in MAN["end_to_end"] + MAN["per_layer"]]
    assert len(metrics) == len(set(metrics))


@pytest.mark.parametrize("conf", MAN["configs"], ids=lambda c: c["name"])
def test_config_files_resolve(conf):
    data = common.load_json(common.ROOT / conf["file"])
    assert data["name"] == conf["name"]
    assert data["source"] == conf["source"]
    assert data["reduced"] == conf["reduced"]
    assert conf["file"].startswith("bench/")
    common.arch_config(data)
    assert any(w["config"] == conf["name"] for w in MAN["workloads"])


@pytest.mark.parametrize("wl", MAN["workloads"], ids=lambda w: w["name"])
def test_cells_resolve(wl):
    cell = common.cell_file(wl["name"])
    assert (common.BENCH / "drivers" / f"{cell['driver']}.py").exists()
    assert wl["chips"] in (1, 4)
    assert "setup_s" in e2e_of(wl["name"])
    assert len(e2e_of(wl["name"])) >= 2
    assert any(wl["name"] in m["workloads"] for m in MAN["per_layer"])
    assert cell["limits"]


@pytest.mark.parametrize("metric", MAN["per_layer"], ids=lambda m: m["name"])
def test_per_layer_resolves_and_moves(metric):
    reader = common.metric_reader(metric["name"])
    assert callable(reader.read)
    assert metric["moves"] in [m["name"] for m in MAN["end_to_end"]]
    for cell in metric["workloads"]:
        assert metric["moves"] in e2e_of(cell), (metric["name"], cell)


def test_bounds():
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    setup = [m for m in MAN["end_to_end"] if m["name"] == "setup_s"]
    assert setup and setup[0]["bound"] <= 0.25


def test_four_chip_share():
    four = sum(1 for w in MAN["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(MAN["workloads"]) // 2)


def test_peaks_known_and_unknown():
    assert common.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        common.peaks("TPU v99")
