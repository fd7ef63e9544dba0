"""The training loop's own instrumentation: a step annotation and host
spans per step on the profiler's clock, the operator's ``profile_dir`` /
``profile_steps`` window, recompiles reported at the step that made
them, and the plan's ``sync_plan`` record."""
import glob
import os

import jax
import numpy as np
import pytest

HOST_SPANS = ("train.batch", "train.device_put", "train.dispatch", "train.fetch")


class _Shape:
    global_batch = 4
    seq_len = 16
    name = "tiny"
    kind = "train"


def _trainer(steps, pipeline=None, **cfg):
    from repro.configs import get_smoke_arch
    from repro.models import ModelSettings, build_model
    from repro.runtime.train_loop import Trainer, TrainerConfig
    from repro.utils.jax_compat import make_mesh

    st = ModelSettings(param_dtype="float32", compute_dtype="float32",
                       remat="none", loss_chunk=8, max_seq=64)
    model = build_model(get_smoke_arch("qwen2-0.5b"), st)
    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
    tcfg = TrainerConfig(steps=steps, lr=5e-3, warmup=1, log_every=0,
                         mode="dfabric", seed=7, **cfg)
    return Trainer(model, mesh, _Shape(), tcfg, data_pipeline=pipeline)


def _host_events(log_dir):
    """(name, start, end, step_num) of every ``train`` / ``train.*`` event."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "train" or ev.name.startswith("train."):
                    stats = dict(ev.stats)
                    out.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                                stats.get("step_num")))
    return sorted(out, key=lambda e: e[1])


def test_each_step_holds_its_host_spans_in_order(tmp_path):
    tr = _trainer(3)
    jax.profiler.start_trace(str(tmp_path))
    try:
        tr.train()
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    steps = [e for e in events if e[0] == "train"]
    assert [int(e[3]) for e in steps] == [0, 1, 2]
    for _, lo, hi, _ in steps:
        inside = [e[0] for e in events if e[0] != "train" and lo <= e[1] and e[2] <= hi]
        assert inside == list(HOST_SPANS)


def test_profile_steps_trace_only_their_steps(tmp_path):
    tr = _trainer(4, profile_dir=str(tmp_path), profile_steps=(1, 3))
    out = tr.train()
    assert out["step"] == 4
    steps = [int(e[3]) for e in _host_events(str(tmp_path)) if e[0] == "train"]
    assert steps == [1, 2]


def test_profiling_is_off_by_default():
    from repro.runtime.train_loop import TrainerConfig

    cfg = TrainerConfig()
    assert cfg.profile_dir is None and cfg.profile_steps is None


class _ShapeChange:
    """Token batches whose sequence length doubles from step ``at`` on."""

    def __init__(self, at):
        self.at = at

    def batch_at(self, step):
        seq = _Shape.seq_len * (2 if step >= self.at else 1)
        rng = np.random.default_rng(step)
        tok = rng.integers(0, 64, size=(_Shape.global_batch, seq), dtype=np.int32)
        return {"tokens": tok, "labels": np.roll(tok, -1, axis=1)}

    def state_dict(self, step):
        return {"step": step}


@pytest.mark.parametrize("at", [2, 3])
def test_recompile_is_reported_at_its_step(at):
    tr = _trainer(at + 2, pipeline=_ShapeChange(at))
    tr.train()
    compiled = [r for r in tr.metrics.records if r["event"] == "compile"]
    assert [r["step"] for r in compiled] == [0, at]
    assert tr.metrics.counters["compiles"] == sum(r["compiles"] for r in compiled)


def test_one_compile_listener_per_process():
    from jax._src import monitoring

    from repro.runtime import train_loop

    for _ in range(2):
        _trainer(1).train()
    assert monitoring.get_event_duration_listeners().count(train_loop._count_compile) == 1


def test_sync_plan_record_on_one_chip():
    """The trainer logs how its plan's slow legs are cut, once, at build;
    one chip has no ``pod`` axis and so no slow leg."""
    tr = _trainer(1)
    rec = [(r["slow_chunks_rows"], r["slow_chunks_flat"])
           for r in tr.metrics.records if r["event"] == "sync_plan"]
    assert rec == [(0, 0)]


@pytest.mark.parametrize("text,want", [("5:8", (5, 8)), ("0:1", (0, 1)),
                                       ("5", None), ("8:5", None), ("a:b", None)])
def test_profile_steps_argument(text, want):
    import argparse

    from repro.launch.train import step_range

    if want is None:
        with pytest.raises(argparse.ArgumentTypeError):
            step_range(text)
    else:
        assert step_range(text) == want
