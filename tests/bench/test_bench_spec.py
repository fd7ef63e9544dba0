"""The benchmark finds its parts by name, and ``BENCHMARK.json`` keeps the
form the benchmark's contract gives it."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench_fixtures import REPO, TINY

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


#: the files of a made-up family: its reference and program modules under
#: names of their own (delegating to the dense ones), a reader of the
#: trainer's counters and a reader of a scope the harness does not know
NEW_FAMILY = {
    "bench/reference/toyfam.py": '''"""A made-up family's reference: the dense one under its own name."""
from bench.reference import dense

layout, loss = dense.layout, dense.loss
train_flops_per_token = dense.train_flops_per_token
''',
    "bench/programs/toyfam.py": '''"""A made-up family's program side: the dense one under its own name."""
from bench.programs import dense

arch_of = dense.arch_of
''',
    "bench/metrics/toy_window_loss.py": '''"""Mean loss of the window's steps, from the trainer's counters."""


def read(r):
    return sum(c["loss"] for c in r.counters) / len(r.counters) if r.counters else None
''',
    "bench/metrics/toy_moe_ms.py": '''"""Device time per step of the operations scoped ``moe``."""
from bench import scopes

SCOPES = ("moe",)


def read(r):
    return scopes.scope_ms(r, "moe")
''',
}

#: the dense count at ``TINY`` widths and 32 tokens (``test_bench_flops``)
TOY_FLOP_PER_TOKEN = {"seq_len": 32, "value": 663_552}

#: the tests that iterate BENCHMARK.json's configurations and cells
GENERIC_TESTS = [
    "tests/bench/test_bench_modules.py::"
    "test_named_modules_give_the_programs_layout_and_the_flop_count",
    "tests/bench/test_bench_spec.py::test_each_cell_matches_the_program",
    "tests/bench/test_bench_spec.py::test_index_keeps_its_form",
    "tests/bench/test_bench_flops.py::test_published_widths",
]


def test_a_model_of_a_new_family_joins_as_new_files_only(tmp_path, monkeypatch):
    """A configuration of a new family, its cell, a counter metric and a
    metric of a new scope are new files and new entries: each is found by
    name, the tests that iterate the index pass on the new configuration
    from its own ``flop_per_token``, and every file that was there is
    byte-identical afterwards."""
    from bench import scopes, spec, trace

    for d in ("bench", "tests/bench"):
        shutil.copytree(REPO / d, tmp_path / d, ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    before = {p: p.read_bytes() for d in ("bench", "tests/bench")
              for p in (tmp_path / d).rglob("*") if p.is_file()}

    for rel, text in NEW_FAMILY.items():
        (tmp_path / rel).write_text(text)
    cfg = json.loads((REPO / "bench/configs/qwen2-0.5b.json").read_text())
    cfg.update(TINY, reference="bench/reference/toyfam.py",
               program="bench/programs/toyfam.py", flop_per_token=TOY_FLOP_PER_TOKEN)
    (tmp_path / "bench/configs/toyfam.json").write_text(json.dumps(cfg))
    mix = json.loads((REPO / "bench/traffic/b4.s2048.json").read_text())
    mix.update(seq_len=32)
    (tmp_path / "bench/traffic/b4.s32.json").write_text(json.dumps(mix))
    wl = json.loads((REPO / "bench/workloads/qwen2-0.5b.train.s2048.json").read_text())
    wl.update(config="toyfam", traffic="b4.s32")
    (tmp_path / "bench/workloads/toyfam.train.s32.json").write_text(json.dumps(wl))
    index = json.loads((REPO / "BENCHMARK.json").read_text())
    index["configs"].append({"name": "toyfam", "source": "https://example.org/toyfam",
                             "file": "bench/configs/toyfam.json", "reduced": [],
                             "why": "a made-up family"})
    index["workloads"].append({"name": "toyfam.train.s32", "config": "toyfam",
                               "traffic": "b4.s32", "chips": 1, "why": "t"})
    for name, source, layer in (("toy_window_loss", "program_counter", "loss / LM head"),
                                ("toy_moe_ms", "device_trace", "MoE")):
        index["per_layer"].append({"name": name, "unit": "1", "better": "lower",
                                   "source": source, "layer": layer,
                                   "moves": "tokens_per_s",
                                   "workloads": ["toyfam.train.s32"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(index))

    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-rA", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly", *GENERIC_TESTS],
        cwd=tmp_path, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"),
                 PYTHONDONTWRITEBYTECODE="1"))
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-2000:]
    for passed in (GENERIC_TESTS[0] + "[toyfam]",
                   GENERIC_TESTS[1] + "[toyfam.train.s32]"):
        assert f"PASSED {passed}" in proc.stdout, proc.stdout[-4000:]

    cell = spec.load("toyfam.train.s32", root=tmp_path, parts=tmp_path / "bench")
    assert Path(cell.reference.__file__) == tmp_path / "bench/reference/toyfam.py"
    assert Path(cell.program.__file__) == tmp_path / "bench/programs/toyfam.py"
    assert [m["name"] for m in cell.per_layer][-2:] == ["toy_window_loss", "toy_moe_ms"]
    counted = spec.metric_reader("toy_window_loss", parts=tmp_path / "bench")
    assert counted(SimpleNamespace(counters=[{"loss": 2.0}, {"loss": 4.0}])) == 3.0
    monkeypatch.setattr(scopes, "METRICS", tmp_path / "bench/metrics")
    assert scopes.known_scopes() == scopes.SCOPES + ("moe",)
    assert scopes.scope_of("jit(step)/jvp()/while/body/mlp/moe/dot_general") == "moe"
    moe = spec.metric_reader("toy_moe_ms", parts=tmp_path / "bench")
    assert moe(SimpleNamespace(trace=trace.Reduced((0, 1), {}), steps=0)) is None
    old = spec.load("qwen2-0.5b.train.s2048", root=tmp_path, parts=tmp_path / "bench")
    assert not {"toy_window_loss", "toy_moe_ms"} & {m["name"] for m in old.per_layer}
    assert all(p.read_bytes() == b for p, b in before.items())


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
