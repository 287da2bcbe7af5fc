"""The harness finds every cell's pieces by name, a cell added as files
alone loads, and BENCHMARK.json keeps to the benchmark's contract."""
import json
import re
import shutil

import pytest

from benchutil import ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves(name):
    from bench import harness
    cell = harness.resolve(name)
    for fn in ("setup", "window", "check"):
        assert callable(getattr(cell.driver, fn))
    assert "setup_s" in {m["name"] for m in cell.end_to_end}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.metric_reader(m["name"]).read)


def test_cell_added_as_files_alone_loads(tmp_path):
    from bench import harness
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads(json.dumps(SPEC))
    mix = json.loads((ROOT / "bench/traffic/s4k-b3-zipf.json").read_text())
    mix.update(what="a copy under a new name", global_batch=2)
    (tmp_path / "bench/traffic/s4k-b2-zipf.json").write_text(
        json.dumps(mix))
    spec["workloads"].append({"name": "train.copy", "config":
                              "qwen3-moe-30b-a3b-1L", "traffic":
                              "s4k-b2-zipf", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "train.qwen3moe-1L.s4k" in m.get("workloads", []):
            m["workloads"].append("train.copy")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.resolve("train.copy", root=tmp_path)
    assert cell.traffic["what"] == "a copy under a new name"
    assert cell.driver.__file__.startswith(str(tmp_path))
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in SPEC["per_layer"]}


def test_unknown_cell_is_refused():
    from bench import harness
    with pytest.raises(harness.BenchError):
        harness.resolve("no.such.cell")


def test_spec_keeps_to_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][1].startswith(SPEC["paths"][0] + "/")
    assert 1 <= SPEC["run_seconds"] <= 51
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = [m["name"] for m in metrics] + [w["name"] for w in
                                            SPEC["workloads"]]
    names += [c["name"] for c in SPEC["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(SPEC["paths"][0] + "/")


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_config_departs_from_its_source_only_where_it_says(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    # a key cut for size is listed alike in the file and the spec, with
    # its published value beside it; a width is never cut
    assert cfg["reduced"] == entry["reduced"]
    assert set(cfg["published"]) == set(entry["reduced"])
    for k, v in cfg["published"].items():
        assert cfg[k] != v
        assert not k.endswith(("_dim", "_rank", "_size")) or k == "vocab_size"
    # a value the program runs apart from the source is no cut: the file
    # keeps the published value and names the run's under departures
    for k in set(cfg.get("departures", {})) - {"what"}:
        assert k not in entry["reduced"] and k in cfg
        assert cfg["departures"][k] != cfg[k]


def test_reference_runs_the_departures():
    from bench.harness import load_module
    ref = load_module(ROOT / "bench/refs/moe_lm.py", "bench_ref_moe_lm")
    cfg = json.loads((ROOT / SPEC["configs"][0]["file"]).read_text())
    run = ref.as_run(cfg)
    assert cfg["router_aux_loss_coef"] == 0.001     # the published weight
    assert run["router_aux_loss_coef"] == 0.01      # the program's
    assert {k: v for k, v in run.items() if k != "router_aux_loss_coef"} \
        == {k: v for k, v in cfg.items() if k != "router_aux_loss_coef"}
