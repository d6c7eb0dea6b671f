"""Registration is data: every name in BENCHMARK.json resolves to its file,
and the file keeps to the benchmark's contract."""
import inspect
import json
import re

import pytest

from chipbench import registry

BENCH = registry.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_through_the_registry(cell):
    c = registry.cell(cell, BENCH)
    assert c.chips == 1
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e
    for m in c.end_to_end + c.per_layer:
        assert callable(registry.reader(m["name"]))
    assert c.config["name"] == c.config_name
    assert registry.reference(c.config["reference"]).readings


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_units_and_uniqueness(kind):
    names = [e["name"] for e in BENCH[kind]]
    assert len(names) == len(set(names))
    for e in BENCH[kind]:
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                             "higher")
            assert e["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")


def test_configs_used_and_files_under_paths():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert c["reduced"] == registry.config(c["name"], BENCH)["reduced"]


def test_bounds():
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_per_layer_names_its_cells():
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert set(m["workloads"]) <= cells and m["layer"]


def test_configuration_sizes_match_the_program():
    from chipbench.stack import program_config
    for c in BENCH["configs"]:
        program_config(registry.config(c["name"], BENCH))


def test_a_split_metric_is_read_by_its_quantity_reader():
    want = registry.HERE / "metrics" / "decode_step_ms.py"
    for name in ("decode_step_ms.chat", "decode_step_ms.offline",
                 "decode_step_ms.some-later-mix"):
        assert inspect.getsourcefile(registry.reader(name)) == str(want)


def test_a_name_without_a_file_fails():
    with pytest.raises(FileNotFoundError):
        registry.reader("no_such_metric")
    with pytest.raises(FileNotFoundError):
        registry.reader("no_such_metric.chat")
    with pytest.raises(FileNotFoundError):
        registry.traffic("no-such-mix")
    with pytest.raises(KeyError):
        registry.cell("no.such-cell", BENCH)
    bad = dict(BENCH, configs=[dict(BENCH["configs"][0],
                                    file="chipbench/configs/none.json")])
    with pytest.raises(FileNotFoundError):
        registry.config(BENCH["configs"][0]["name"], bad)
