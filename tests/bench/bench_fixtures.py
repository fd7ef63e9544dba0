"""Helpers of the benchmark's CPU tests: a tiny cell laid out as the
benchmark lays out its files, and the import path of ``bench``."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)

#: the cell whose limits and optimizer the tiny cells borrow
LIMITS_OF = "qwen2-0.5b.train.s2048"

TINY = dict(num_hidden_layers=2, hidden_size=64, intermediate_size=128,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            vocab_size=512)


def configs(root: Path = REPO, reference: str = None) -> list:
    """The ``configs`` entries of ``root/BENCHMARK.json``; with
    ``reference``, those whose file names that reference module."""
    entries = json.loads((root / "BENCHMARK.json").read_text())["configs"]
    return [c for c in entries if reference is None or
            json.loads((root / c["file"]).read_text())["reference"] == reference]


def tiny_cell(root: Path, *, name: str = "tiny", config: str = "qwen2-0.5b",
              chips: int = 1, mesh=(1, 1, 1), batch: int = 4, seq: int = 64,
              cfg_over: dict = None):
    """Write BENCHMARK.json and bench/{configs,traffic,workloads} for one
    tiny cell under ``root``, at the widths of ``TINY`` (and ``cfg_over``),
    with copies of the reference and program modules, and load it."""
    from bench import spec

    parts = root / "bench"
    for d in ("configs", "traffic", "workloads"):
        (parts / d).mkdir(parents=True, exist_ok=True)
    for d in ("reference", "programs"):
        shutil.copytree(REPO / "bench" / d, parts / d, dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((REPO / "bench" / "configs" / f"{config}.json").read_text())
    cfg.update(TINY, **(cfg_over or {}))
    (parts / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((REPO / "bench" / "traffic" / "b4.s2048.json").read_text())
    mix.update(global_batch=batch, seq_len=seq)
    (parts / "traffic" / "tiny.json").write_text(json.dumps(mix))
    wl = json.loads((REPO / "bench" / "workloads" / f"{LIMITS_OF}.json").read_text())
    wl.update(config="tiny", traffic="tiny", chips=chips,
              mesh={"axes": ["pod", "data", "model"], "shape": list(mesh)})
    (parts / "workloads" / f"{name}.json").write_text(json.dumps(wl))
    index = json.loads((REPO / "BENCHMARK.json").read_text())
    index["workloads"] = [{"name": name, "config": "tiny", "traffic": "tiny",
                           "chips": chips, "why": "a CPU test"}]
    (root / "BENCHMARK.json").write_text(json.dumps(index))
    return spec.load(name, root=root, parts=parts)
