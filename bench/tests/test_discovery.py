"""Every file that BENCHMARK.json names is found by name and is sound."""

import json

import pytest

from bench import harness, model

SPEC = harness.load_spec()


def test_paths_hold_the_command():
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"][1] == "bench/run.py"
    assert (harness.ROOT / SPEC["command"][1]).exists()


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_config_files(entry):
    config = model.load_config(entry["name"])
    assert entry["file"] == f"bench/configs/{entry['name']}.json"
    assert config["name"] == entry["name"]
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    assert set(config["correct"]) == {"logit_rms_err"}


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_config_layers_equal_the_programs_graph(entry):
    from repro.net.graph import MODELS

    config = model.load_config(entry["name"])
    graph = MODELS[config["model"]](input_size=config["input_size"],
                                    num_classes=config["num_classes"])
    harness.check_graph(graph, config)
    first = config["layers"][0]
    changed = dict(config, layers=[dict(first, K=first["K"] + 2)] + config["layers"][1:])
    with pytest.raises(SystemExit):
        harness.check_graph(graph, changed)


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_every_op_has_a_module(entry):
    config = model.load_config(entry["name"])
    for op in {layer["op"] for layer in config["layers"]}:
        assert (model.OPS_DIR / f"{op}.py").is_file()
        assert all(hasattr(model.op(op), n) for n in model.OP_INTERFACE)


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_cells_find_their_files_and_metrics(cell):
    traffic = harness.load_traffic(cell["traffic"])
    assert traffic["loop"] == "closed"
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    e2e = harness.cell_metrics(SPEC, cell["name"], per_layer=False)
    per_layer = harness.cell_metrics(SPEC, cell["name"], per_layer=True)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    assert per_layer
    for m in e2e + per_layer:
        assert callable(harness.reader(m["name"]))
    for m in per_layer:
        assert m["moves"] in names


def test_every_metric_names_known_cells_and_layers():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert set(m.get("workloads", [])) <= cells
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
    layers = {m["layer"] for m in SPEC["per_layer"]}
    assert layers == {"serving engine", "forward", "kernels", "model step", "device"}


def test_peaks_table_names_its_source():
    peaks = json.loads((harness.BENCH_DIR / "peaks.json").read_text())
    assert "Google Cloud" in peaks["TPU v5 lite"]["source"]
    assert harness.load_peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        harness.load_peak("TPU v4")
