"""Where the benchmark finds its parts: every part is a file found by name.

    BENCHMARK.json                      the cells, metrics and bounds
    bench/workloads/<cell>.json         a cell: configuration, traffic, chips,
                                        mesh, optimizer, correctness limits
    bench/configs/<config>.json         a configuration as it is run; its
                                        ``reference`` and ``program`` keys
                                        name the two modules below, and
                                        ``flop_per_token`` (``seq_len``,
                                        ``value``) states what its
                                        reference's
                                        ``train_flops_per_token`` gives
    bench/reference/<name>.py           the plain reference of a family of
                                        configurations: ``layout(cfg)``,
                                        ``loss(cfg, precision, params,
                                        tokens, labels)``,
                                        ``train_flops_per_token(cfg, seq)``;
                                        it imports nothing of the program
    bench/programs/<name>.py            the program's side of it:
                                        ``arch_of(cfg)``, the config's sizes
                                        as the program's ``ArchConfig``,
                                        refused where its mechanisms are not
                                        the reference's
    bench/traffic/<traffic>.json        a traffic mix (``bench.traffic``)
    bench/metrics/<metric>.py           a per-layer metric: ``read(r)`` of a
                                        ``cell.Reading`` (the window's
                                        trace, and ``counters``, the
                                        trainer's numbers of each step);
                                        ``SCOPES``, where it reads program
                                        scopes of its own (``bench.scopes``)

A later cell, configuration, mix or metric is one more file and one more
entry in ``BENCHMARK.json``; a model of a new family brings its reference
and program modules as new files too, and its readers declare the scopes
they read.  No file here or under ``tests/bench`` changes for any of them:
the harness (``cell``, ``check``, ``calibrate``, ``flops``, ``scopes``,
``reference/train``) and its tests hold nothing of one family or
configuration.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Callable, List, Optional, Sequence

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
    #: the modules the configuration names (``MODULES``)
    reference: ModuleType
    program: ModuleType

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


#: config key -> the functions the module it names must give
MODULES = {"reference": ("layout", "loss", "train_flops_per_token"),
           "program": ("arch_of",)}


def module_at(path: Path, name: str, needs: Sequence[str], what: str) -> ModuleType:
    """The module in file ``path``, which must give every function of
    ``needs``; ``what`` names it in the error."""
    if not path.is_file():
        raise FileNotFoundError(f"{what}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [f for f in needs if not callable(getattr(mod, f, None))]
    if missing:
        raise AttributeError(f"{what}: {path} has no {', '.join(missing)}")
    return mod


def config_modules(cfg: dict, cfg_path: Path, root: Path = ROOT) -> dict:
    """Each module of ``MODULES`` that the configuration file names, by a
    path from ``root``."""
    out = {}
    for key, needs in MODULES.items():
        if key not in cfg:
            raise KeyError(f"{cfg_path} names no {key!r} module")
        path = root / cfg[key]
        out[key] = module_at(path, f"bench.{path.parent.name}.{path.stem}", needs,
                             f"{key} of {cfg_path.name}")
    return out


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
    cfg_path = parts / "configs" / f"{workload['config']}.json"
    cfg = _json(cfg_path)
    return Cell(name, workload, cfg,
                _json(parts / "traffic" / f"{workload['traffic']}.json"),
                [m for m in index["end_to_end"] if _applies(m, name)],
                [m for m in index["per_layer"] if _applies(m, name)],
                **config_modules(cfg, cfg_path, root))


def metric_reader(name: str, parts: Path = BENCH) -> Callable[[object], Optional[float]]:
    """``read`` of ``parts/metrics/<name>.py``."""
    return module_at(parts / "metrics" / f"{name}.py", f"bench.metrics.{name}",
                     ("read",), f"reader of per-layer metric {name!r}").read
