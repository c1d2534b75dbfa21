"""BENCHMARK.json keeps to its contract, and every cell, configuration,
mix and metric it names resolves to its files by name."""
import re

import pytest

from bench.harness import model, spec, traffic

BENCH = spec.load_json(spec.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all(not w.startswith("/") and ".." not in w
               for w in BENCH["command"])


def test_names_units_and_bounds():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert set(m["workloads"]) <= set(CELLS)
        assert len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    cell = spec.resolve(name)
    assert cell.reference.exists()
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.load_module(spec.metric_file(m["name"])).read)
    for m in cell.per_layer:
        assert m["moves"] in e2e
    cc = cell.cell
    assert cc["engine"]["max_len"] >= traffic.max_tokens(cell.traffic)
    assert cc["engine"]["max_len"] % cc["engine"]["block_size"] == 0
    assert cc["engine"]["prefill_chunk"] % cc["engine"]["block_size"] == 0
    assert cc["check"]["limits"]
    assert set(cc["check"]["limits"]) <= {"logprob_gap", "logprob_gap_mean"}


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_is_what_the_program_runs(c):
    cfg = spec.load_json(spec.ROOT / c["file"])
    assert cfg["name"] == c["name"] and cfg["source"].startswith(c["source"])
    assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    widths = ("hidden_size", "intermediate_size", "head_dim",
              "num_attention_heads", "num_key_value_heads", "vocab_size")
    assert not set(c["reduced"]) & set(widths)
    model.program_config(cfg)       # raises where the program departs


def test_metric_lookup_by_stem():
    assert spec.metric_file("decode_step_ms.serve").name == \
        "decode_step_ms.py"
    with pytest.raises(FileNotFoundError):
        spec.metric_file("no_such_metric.serve")
