"""The harness finds every configuration, mix and metric by name, so adding
one is one file plus entries; and BENCHMARK.json keeps to its contract."""

import json
import re

import pytest
from bench_helpers import ROOT

from bench import harness

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    c = harness.load_cell(cell)
    assert c.config["source"].startswith("https://")
    assert c.mix["loop"] in ("closed", "open")
    harness.load_reader  # noqa: B018
    for m in c.end_to_end + c.per_layer:
        assert callable(harness.load_reader(m["name"]).read)
    assert check_reference(c.config)


def check_reference(c):
    from bench import check

    return callable(check.load_reference(c["reference"]).forward)


def _write(path, obj):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(obj if isinstance(obj, str) else json.dumps(obj))


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """A checkout that adds a configuration, a mix and a metric as files
    and entries: the harness resolves them without any code change."""
    _write(tmp_path / "bench" / "configs" / "m9.json",
           {"source": "https://example.org/m9", "program": "smollm-135m"})
    _write(tmp_path / "bench" / "traffic" / "burst.json", {"loop": "open"})
    _write(tmp_path / "bench" / "metrics" / "answer.py",
           "def read(ctx):\n    return 42.0\n")
    spec = {
        "configs": [{"name": "m9", "file": "bench/configs/m9.json"}],
        "workloads": [{"name": "m9.burst", "config": "m9",
                       "traffic": "burst", "chips": 1}],
        "end_to_end": [{"name": "answer", "unit": "x"}],
        "per_layer": [{"name": "answer", "unit": "x",
                       "workloads": ["other"]}],
    }
    _write(tmp_path / "BENCHMARK.json", spec)
    cell = harness.load_cell("m9.burst", root=tmp_path)
    assert cell.config["program"] == "smollm-135m"
    assert cell.mix == {"loop": "open"}
    assert [m["name"] for m in cell.end_to_end] == ["answer"]
    assert cell.per_layer == []          # listed for another cell only
    assert harness.load_reader("answer", root=tmp_path).read(None) == 42.0
    with pytest.raises(harness.BenchError):
        harness.load_reader("missing", root=tmp_path)
    with pytest.raises(harness.BenchError):
        harness.load_cell("m9.other", root=tmp_path)


def test_unknown_device_kind_is_an_error():
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_s"] == 819e9
    with pytest.raises(harness.BenchError):
        harness.load_peaks("TPU v99")


def test_benchmark_json_keeps_to_its_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir()
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = set()
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"]: w for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        for w in m.get("workloads", cells):
            assert w in cells
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    assert any("mfu" in m["name"] for m in SPEC["per_layer"])
    configs = {c["name"]: c for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    for c in configs.values():
        assert (ROOT / c["file"]).is_file() and c["reduced"] == []
        assert c["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))


@pytest.mark.parametrize("key,value", [
    ("num_key_value_heads", 9), ("rope_theta", 500000.0),
    ("attention.mechanism", "dotprod")])
def test_program_that_differs_from_the_configuration_is_refused(key, value):
    """The configuration's ``program_fields`` hold the program to what the
    configuration states: a stated value the program does not have
    stops the run before any engine is built."""
    import copy

    c = copy.deepcopy(harness.load_cell("smollm-135m.inhibitor.offline").config)
    harness._check_program(c)
    head, _, tail = key.rpartition(".")
    (c[head] if head else c)[tail] = value
    with pytest.raises(harness.BenchError, match=tail):
        harness._check_program(c)
