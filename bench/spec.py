"""Where the benchmark finds its parts: every part is a file found by name.

    BENCHMARK.json                      the cells, metrics and bounds
    bench/workloads/<cell>.json         a cell: configuration, traffic, chips,
                                        mesh, optimizer, correctness limits
    bench/configs/<config>.json         a configuration as it is run
    bench/traffic/<traffic>.json        a traffic mix (``bench.traffic``)
    bench/metrics/<metric>.py           a per-layer metric: ``read(r)``

A later cell, configuration, mix or metric is one more file and one more
entry in ``BENCHMARK.json``; no file here changes for it.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, List, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


@dataclass
class Cell:
    name: str
    workload: dict
    cfg: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def chips(self) -> int:
        return self.workload["chips"]


def use_checkout_cache() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (the path is part of the cache's key), for every program
    however short its compile; also what the program's own
    ``use_compile_cache`` then takes.  Call before JAX starts."""
    import os

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def _json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"{path} does not exist")
    return json.loads(path.read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(name: str, root: Path = ROOT, parts: Path = BENCH) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its files under
    ``parts``."""
    index = _json(root / "BENCHMARK.json")
    entry = next((w for w in index["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in index['workloads']]}")
    workload = _json(parts / "workloads" / f"{name}.json")
    for key in ("config", "traffic", "chips"):
        if workload[key] != entry[key]:
            raise ValueError(f"{name}: {key} is {workload[key]!r} in its file "
                             f"and {entry[key]!r} in BENCHMARK.json")
    return Cell(name, workload,
                _json(parts / "configs" / f"{workload['config']}.json"),
                _json(parts / "traffic" / f"{workload['traffic']}.json"),
                [m for m in index["end_to_end"] if _applies(m, name)],
                [m for m in index["per_layer"] if _applies(m, name)])


def metric_reader(name: str, parts: Path = BENCH) -> Callable[[object], Optional[float]]:
    """``read`` of ``parts/metrics/<name>.py``."""
    path = parts / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"per-layer metric {name!r} has no reader {path}")
    spec = importlib.util.spec_from_file_location(f"bench.metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
