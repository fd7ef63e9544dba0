"""The benchmark finds its parts by name, and ``BENCHMARK.json`` keeps the
form the benchmark's contract gives it."""
import json
import re
import shutil

import numpy as np
import pytest

from bench_fixtures import REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture
def index():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def test_a_new_cell_and_metric_are_found_by_name(tmp_path):
    """Adding a cell or a metric is adding files and entries: nothing that
    is already there is edited."""
    from bench import spec

    shutil.copytree(REPO / "bench", tmp_path / "bench")
    index = json.loads((REPO / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    wl = json.loads((tmp_path / "bench/workloads/qwen2-0.5b.train.s2048.json").read_text())
    wl["traffic"] = "b4.s512"
    (tmp_path / "bench/workloads/qwen2-0.5b.train.s512.json").write_text(json.dumps(wl))
    (tmp_path / "bench/traffic/b4.s512.json").write_text(json.dumps(
        {"global_batch": 4, "seq_len": 512, "kind": "uniform"}))
    (tmp_path / "bench/metrics/steps_seen.py").write_text(
        "def read(r):\n    return float(r.steps)\n")
    index["workloads"].append({"name": "qwen2-0.5b.train.s512", "config": "qwen2-0.5b",
                               "traffic": "b4.s512", "chips": 1, "why": "t"})
    index["per_layer"].append({"name": "steps_seen", "unit": "steps", "better": "higher",
                               "source": "program_counter", "layer": "host loop",
                               "moves": "tokens_per_s",
                               "workloads": ["qwen2-0.5b.train.s512"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(index))
    cell = spec.load("qwen2-0.5b.train.s512", root=tmp_path, parts=tmp_path / "bench")
    assert cell.mix["seq_len"] == 512 and cell.cfg["hidden_size"] == 896
    assert [m["name"] for m in cell.per_layer if m["name"] == "steps_seen"]
    read = spec.metric_reader("steps_seen", parts=tmp_path / "bench")
    assert read(type("R", (), {"steps": 7})()) == 7.0
    assert all(p.read_bytes() == b for p, b in before.items())
    old = spec.load("qwen2-0.5b.train.s2048", root=tmp_path, parts=tmp_path / "bench")
    assert "steps_seen" not in [m["name"] for m in old.per_layer]
    with pytest.raises(KeyError):
        spec.load("no-such-cell", root=tmp_path, parts=tmp_path / "bench")
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("no_such_metric", parts=tmp_path / "bench")


def test_index_keeps_its_form(index):
    assert set(index) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= index["run_seconds"] <= 51
    for p in index["paths"]:
        assert (REPO / p).is_dir() and re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
    assert (REPO / index["command"][1]).is_file()
    configs = {c["name"] for c in index["configs"]}
    cells = {w["name"] for w in index["workloads"]}
    for c in index["configs"]:
        assert NAME.match(c["name"]) and (REPO / c["file"]).is_file()
        assert c["name"] in {w["config"] for w in index["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in index["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in index["workloads"]) <= max(1, len(cells) // 2)
    e2e = {m["name"] for m in index["end_to_end"]}
    assert "setup_s" in e2e
    for m in index["end_to_end"] + index["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in index["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in index["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert (REPO / "bench" / "metrics" / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("name", [w for w in json.loads(
    (REPO / "BENCHMARK.json").read_text())["workloads"]], ids=lambda w: w["name"])
def test_each_cell_matches_the_program(name):
    """Every cell's configuration builds the program's model with the very
    parameter layout the benchmark's weights and reference use, and the
    configuration file changes from its source only what ``reduced`` says."""
    from bench import spec
    from repro.models.registry import build_model
    from repro.utils.trees import tree_paths

    cell = spec.load(name["name"])
    shapes = tree_paths(build_model(cell.program.arch_of(cell.cfg)).param_shapes())
    assert {k: tuple(v.shape) for k, v in shapes.items()} == \
        {k: v[0] for k, v in cell.reference.layout(cell.cfg).items()}
    index = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in index["configs"] if c["name"] == cell.workload["config"])
    assert entry["reduced"] == cell.cfg["reduced"]
    assert set(cell.cfg.get("published", {})) == set(entry["reduced"])
    mix = cell.mix
    assert mix["global_batch"] % int(np.prod(cell.workload["mesh"]["shape"])) == 0
