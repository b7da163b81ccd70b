"""The harness finds every configuration, mix and metric by name, so adding
one is one file plus entries; and BENCHMARK.json keeps to its contract."""

import json
import math
import re

import pytest
from bench_helpers import ROOT

from bench import flops, harness

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


def check_contract(spec, root):
    """Assert that ``spec`` (a ``BENCHMARK.json`` under ``root``) keeps to
    the benchmark's contract."""
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    for p in spec["paths"]:
        assert (root / p).is_dir()
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = set()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"]: w for w in spec["workloads"]}
    for m in spec["end_to_end"] + spec["per_layer"]:
        for w in m.get("workloads", cells):
            assert w in cells
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    assert any("mfu" in m["name"] for m in spec["per_layer"])
    configs = {c["name"]: c for c in spec["configs"]}
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (root / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    for c in configs.values():
        assert (root / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in spec["paths"]))
        check_cut(c["reduced"], json.loads((root / c["file"]).read_text()))


#: keys that hold a width, which no cut may change: hidden, intermediate,
#: latent, state and projection sizes, head sizes, expansion factors,
#: experts per token, and window and convolution sizes (the vocabulary's
#: rows are no width: a chip may hold a slice of them)
WIDTH = re.compile(r"(_dim|_rank|_size|_factor|_per_tok|window|_state"
                   r"|_conv|conv_kernel|^expand)$")

#: counts of which a chip may hold its share of a layer; a cut of one
#: needs the deployment that the share is of
SHARES = ("num_experts", "num_attention_heads", "num_key_value_heads",
          "vocab_size")


def layer_period(c):
    """Layers in one period of the layer pattern: the expert step, and any
    ``*_pattern`` or ``*_interval`` count of the layers between kinds."""
    period = c.get("decoder_sparse_step", 1) if c.get("num_experts") else 1
    for key, v in c.items():
        if key.endswith(("_pattern", "_interval")) and isinstance(v, int):
            period = math.lcm(period, v)
    return period


def check_cut(reduced, c):
    """A configuration cut to one chip's share: ``reduced`` names each key
    changed from the source, the file lists the same keys under its own
    ``reduced`` and gives each key's source value under ``published``;
    no width is cut; a cut of experts, heads or vocabulary names the
    deployment that holds the rest (the published count and the chips
    that share a layer); and what is held keeps to the floors: whole
    periods of the layer pattern and 4 layers after the leading dense
    ones, 8 routed experts, an eighth of the vocabulary."""
    assert c.get("reduced") == reduced, "reduced differs from the file's"
    published = c.get("published", {})
    for key in reduced:
        assert NAME.match(key), f"{key!r} is no name"
        assert key in c, f"{key!r} is not a key of the file"
        assert key in published, f"{key!r} has no published value"
        assert key == "vocab_size" or not WIDTH.search(key), \
            f"{key!r} is a width"
        assert c[key] != published[key], f"{key!r} is not changed"
        if key in SHARES:
            d = c.get("deployment", {})
            assert d.get(key) == published[key], \
                f"{key!r} is cut with no deployment that holds the rest"
            assert isinstance(d.get("chips_per_layer"), int) and \
                d["chips_per_layer"] >= 2, "deployment without chips_per_layer"
    if c.get("num_experts"):
        assert c["num_experts"] >= 8, "fewer than 8 routed experts"
    sparse = flops.expert_layers(c)
    after_dense = c["num_hidden_layers"] - (sparse[0] if sparse else 0)
    assert after_dense >= 4, "fewer than 4 layers after the dense ones"
    if "num_hidden_layers" in reduced:
        assert after_dense % layer_period(c) == 0, \
            "the layers kept are no whole periods of the pattern"
    if "layer_types" in c:
        assert len(c["layer_types"]) == c["num_hidden_layers"], \
            "layer_types does not give every layer kept"
    assert 8 * c["vocab_size"] >= published.get("vocab_size",
                                                c["vocab_size"]), \
        "less than an eighth of the vocabulary"


def test_benchmark_json_keeps_to_its_contract():
    check_contract(SPEC, ROOT)


#: Qwen1.5-MoE-A2.7B (https://huggingface.co/Qwen/Qwen1.5-MoE-A2.7B) as a
#: chip's share: 4 of 24 layers, 10 of 60 experts, six chips to a layer
QWEN_MOE_CUT = {
    "source": "https://huggingface.co/Qwen/Qwen1.5-MoE-A2.7B",
    "num_hidden_layers": 4, "hidden_size": 2048, "intermediate_size": 5632,
    "num_attention_heads": 16, "num_key_value_heads": 16, "head_dim": 128,
    "vocab_size": 151936, "tie_word_embeddings": False,
    "num_experts": 10, "num_experts_per_tok": 4,
    "moe_intermediate_size": 1408, "shared_expert_intermediate_size": 5632,
    "decoder_sparse_step": 1, "mlp_only_layers": [],
    "reduced": ["num_hidden_layers", "num_experts"],
    "published": {"num_hidden_layers": 24, "num_experts": 60},
    "deployment": {"num_experts": 60, "chips_per_layer": 6},
}


def _lists_differ(spec, c):
    c["reduced"] = ["num_hidden_layers"]


def _key_missing(spec, c):
    del c["num_experts"]


def _experts_below_floor(spec, c):
    c.update(num_experts=6)
    c["deployment"]["chips_per_layer"] = 10


def _layers_below_floor(spec, c):
    c.update(num_hidden_layers=3)


def _vocab_below_floor(spec, c):
    _cut(spec, c, "vocab_size", 151936 // 10, 151936)
    c["deployment"]["vocab_size"] = 151936


def _width_cut(spec, c):
    _cut(spec, c, "moe_intermediate_size", 704, 1408)


def _window_cut(spec, c):
    c["sliding_window"] = 4096
    _cut(spec, c, "sliding_window", 1024, 4096)


def _no_deployment(spec, c):
    del c["deployment"]


def _heads_cut_without_deployment(spec, c):
    _cut(spec, c, "num_key_value_heads", 8, 16)


def _unchanged_key(spec, c):
    c["published"]["num_experts"] = 10


def _broken_period(spec, c):
    c.update(num_hidden_layers=6, decoder_sparse_step=2)


def _cut(spec, c, key, held, published):
    c[key] = held
    c["published"][key] = published
    c["reduced"].append(key)
    spec["configs"][0]["reduced"].append(key)


def _cut_spec(tmp_path, mutate=None):
    c = json.loads(json.dumps(QWEN_MOE_CUT))
    spec = json.loads(json.dumps(SPEC))
    spec["paths"] = ["bench"]
    spec["configs"] = [{"name": "qwen1.5-moe-a2.7b", "source": c["source"],
                        "file": "bench/configs/qwen1.5-moe-a2.7b.json",
                        "reduced": ["num_hidden_layers", "num_experts"],
                        "why": "sparse experts, a chip's share"}]
    spec["workloads"] = [{"name": "qwen1.5-moe-a2.7b.offline",
                          "config": "qwen1.5-moe-a2.7b", "traffic": "offline",
                          "chips": 1, "why": "offline decode"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    if mutate:
        mutate(spec, c)
    _write(tmp_path / "bench" / "configs" / "qwen1.5-moe-a2.7b.json", c)
    _write(tmp_path / "bench" / "traffic" / "offline.json",
           (ROOT / "bench" / "traffic" / "offline.json").read_text())
    _write(tmp_path / "BENCHMARK.json", spec)
    return spec


def test_configuration_cut_to_a_chips_share_keeps_to_the_contract(tmp_path):
    spec = _cut_spec(tmp_path)
    check_contract(spec, tmp_path)
    cell = harness.load_cell("qwen1.5-moe-a2.7b.offline", root=tmp_path)
    assert cell.config["num_experts"] == 10
    assert cell.config["deployment"]["chips_per_layer"] == 6


def _case(mutate, message):
    return pytest.param(mutate, message, id=mutate.__name__.strip("_"))


@pytest.mark.parametrize("mutate,message", [
    _case(_lists_differ, "reduced differs"),
    _case(_key_missing, "not a key of the file"),
    _case(_experts_below_floor, "fewer than 8 routed experts"),
    _case(_layers_below_floor, "fewer than 4 layers"),
    _case(_vocab_below_floor, "an eighth of the vocabulary"),
    _case(_width_cut, "'moe_intermediate_size' is a width"),
    _case(_window_cut, "'sliding_window' is a width"),
    _case(_no_deployment, "'num_experts' is cut with no deployment"),
    _case(_heads_cut_without_deployment,
          "'num_key_value_heads' is cut with no deployment"),
    _case(_unchanged_key, "'num_experts' is not changed"),
    _case(_broken_period, "no whole periods")])
def test_malformed_cut_is_refused(tmp_path, mutate, message):
    spec = _cut_spec(tmp_path, mutate)
    with pytest.raises(AssertionError, match=re.escape(message)):
        check_contract(spec, tmp_path)


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
