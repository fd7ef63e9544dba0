"""One run of a training cell through the program's own entry point.

Set-up builds one ``Trainer`` (``build_model`` -> ``Trainer`` in
``mode="dfabric"``, at ``cells.cell_settings``), makes the seed's weights
on the device in one jitted call, and drives that trainer through its first
``check_steps`` steps with ``Trainer.train()`` and the program's own data
pipeline.  Those steps compile the step and are what ``correct`` compares.
The same trainer, parameters and optimizer state then go on into the
measured window: ``Trainer.train()`` again, ended by the trainer's own
preemption flag, which a ``SIGALRM`` raises after ``--seconds``.

The benchmark opens its spans around the calls the trainer makes into the
layers below it: ``bench.host_input`` around the pipeline's ``batch_at``
(a delegating pipeline handed to ``Trainer(data_pipeline=)``) and
``bench.step_call`` around ``step_fn``.  ``bench.window`` spans the window.

Nothing here belongs to one family of models: the configuration's
``program`` module maps its file onto the program's arch, and its
``reference`` module gives the weight layout, the reference loss and the
FLOP per token (``bench/spec.py``).
"""
from __future__ import annotations

import dataclasses
import math
import shutil
import signal
import statistics
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, flops, spec, trace, traffic
from bench.reference import params as rparams, train as rtrain
class SpanPipeline:
    """Delegates to the program's pipeline; times each ``batch_at`` (when it
    starts and how long it takes: one call a step) and keeps the batches of
    the first ``keep`` steps for the comparison."""

    def __init__(self, inner, keep: int):
        self.inner, self.keep = inner, keep
        self.starts: List[float] = []
        self.durations: List[float] = []
        self.kept: Dict[int, Dict[str, np.ndarray]] = {}

    def batch_at(self, step: int):
        t0 = time.perf_counter()
        self.starts.append(t0)
        with jax.profiler.TraceAnnotation("bench.host_input"):
            out = self.inner.batch_at(step)
        self.durations.append(time.perf_counter() - t0)
        if step < self.keep:
            self.kept[step] = {k: np.array(v) for k, v in out.items()}
        return out

    def state_dict(self, step: int):
        return self.inner.state_dict(step)


class SpanStep:
    """Delegates to the trainer's jitted step inside a ``bench.step_call``
    span."""

    def __init__(self, inner):
        self.inner = inner

    def __call__(self, *args):
        with jax.profiler.TraceAnnotation("bench.step_call"):
            return self.inner(*args)


@dataclass
class Setup:
    cell: spec.Cell
    arch: object
    trainer: object
    pipeline: SpanPipeline
    lay: rparams.Layout
    init: Callable
    opt_init: Callable
    dtype: object
    times: Dict[str, float] = field(default_factory=dict)


def mesh_of(workload: dict):
    from repro.utils.jax_compat import make_mesh

    shape = tuple(workload["mesh"]["shape"])
    n = int(np.prod(shape))
    return make_mesh(shape, tuple(workload["mesh"]["axes"]),
                     devices=jax.devices()[:n])


def build(cell: spec.Cell, seed: int) -> Setup:
    """The trainer of the cell, as users build it; no array made yet."""
    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import DataConfig, TokenPipeline
    from repro.launch.cells import cell_settings
    from repro.models.registry import build_model
    from repro.optim import grad_sync
    from repro.runtime.train_loop import Trainer, TrainerConfig, mesh_info
    from repro.utils.trees import tree_paths

    t0 = time.perf_counter()
    cfg, mix, wl = cell.cfg, cell.mix, cell.workload
    arch = cell.program.arch_of(cfg)
    shape = ShapeConfig(cell.name, mix["seq_len"], mix["global_batch"], "train")
    settings = dataclasses.replace(
        cell_settings(arch, shape, attn_impl=cfg["attn_impl"], remat=cfg["remat"]),
        param_dtype=cfg["param_dtype"], compute_dtype=cfg["compute_dtype"])
    model = build_model(arch, settings)
    opt = wl["optimizer"]
    from repro.optim.adamw import AdamWConfig
    for k, v in dataclasses.asdict(AdamWConfig()).items():
        if opt[k] != v:
            raise ValueError(f"{cell.name}: the program's AdamW has {k}={v}, "
                             f"the workload file {opt[k]}")
    tcfg = TrainerConfig(steps=opt["total_steps"], lr=opt["lr"],
                         warmup=opt["warmup"], log_every=0, mode=wl["mode"],
                         seed=seed)
    data = DataConfig(seed=seed, **{k: mix[k] for k in traffic.DATA_KEYS})
    pipe = SpanPipeline(TokenPipeline(arch, shape, data), wl["check_steps"])
    mesh = mesh_of(wl)
    t1 = time.perf_counter()
    trainer = Trainer(model, mesh, shape, tcfg, data_pipeline=pipe)
    t2 = time.perf_counter()
    trainer.step_fn = SpanStep(trainer.step_fn)

    lay = cell.reference.layout(cfg)
    shapes = {k: tuple(v.shape) for k, v in tree_paths(model.param_shapes()).items()}
    want = {k: v[0] for k, v in lay.items()}
    if shapes != want:
        raise ValueError(f"the program's parameter layout differs from the "
                         f"benchmark's: {sorted(set(shapes.items()) ^ set(want.items()))}")
    from jax.sharding import NamedSharding
    pspecs = tree_paths(model.param_specs(mesh_info(mesh)))
    dtype = jnp.dtype(cfg["param_dtype"])
    init = rparams.make_init(lay, dtype, out_shardings={
        k: NamedSharding(mesh, pspecs[k]) for k in lay})
    opt_init = jax.jit(lambda: grad_sync.init_sync_state(
        trainer.plan, model.param_shapes(), trainer.ss),
        out_shardings=trainer.state_sharding)
    return Setup(cell, arch, trainer, pipe, lay, init, opt_init, dtype,
                 {"build_s": time.perf_counter() - t0 - (t2 - t1),
                  "trainer_s": t2 - t1})


def nest(flat: Dict[str, jax.Array]):
    from repro.utils.trees import tree_from_paths
    return tree_from_paths(flat)


def flat(tree) -> Dict[str, jax.Array]:
    from repro.utils.trees import tree_paths
    return tree_paths(tree)


def first_grads(s: Setup, opt_state, grad_norm: float):
    """Per leaf, the first gradient as the optimizer got it, worked out from
    its state after one step: AdamW's first moment is then
    ``(1 - b1) * clip * g`` (``clip`` from the step's reported global norm).
    A section of several leaves is one flat, padded vector in their order.
    Returns (norms, samples at ``rtrain.sample_index``)."""
    o = s.cell.workload["optimizer"]
    sections = [(sec.name, tuple(sec.leaf_paths)) for sec in s.trainer.plan.sections]
    sizes = {k: int(np.prod(v[0])) for k, v in s.lay.items()}
    idx = rtrain.sample_index(s.lay)

    def fn(state):
        sq, picked = {}, {}
        for name, paths in sections:
            m = state["sections"][name]["m"].astype(jnp.float32).reshape(-1)
            off = 0
            for p in paths:
                leaf = m[off:off + sizes[p]]
                sq[p] = jnp.sum(jnp.square(leaf))
                picked[p] = leaf[jnp.asarray(idx[p])]
                off += sizes[p]
        return sq, picked

    sq, picked = jax.jit(fn)(opt_state)
    if set(sq) != set(s.lay):
        raise ValueError(f"optimizer sections cover {sorted(sq)}, not every leaf")
    clip = min(1.0, o["grad_clip"] / max(grad_norm, 1e-9)) if o["grad_clip"] > 0 else 1.0
    scale = (1 - o["b1"]) * clip
    return ({k: math.sqrt(float(v)) / scale for k, v in sq.items()},
            {k: np.asarray(v) / scale for k, v in picked.items()})


def drive_check_steps(s: Setup, seed: int):
    """Weights from the seed, then the first ``check_steps`` steps through
    ``Trainer.train()``; returns (params, opt, readings of the program)."""
    tr, n = s.trainer, s.cell.workload["check_steps"]
    lo, hi = rparams.seed_words(seed)
    t0 = time.perf_counter()
    params = nest(s.init(lo, hi))
    opt = s.opt_init()
    jax.block_until_ready((params, opt))
    t1 = time.perf_counter()
    logged = len(tr.metrics_log)
    tr.cfg = dataclasses.replace(tr.cfg, steps=1)
    out = tr.train(params, opt, start_step=0)
    t2 = time.perf_counter()
    grads, samples = first_grads(s, out["opt"], tr.metrics_log[logged]["grad_norm"])
    tr.cfg = dataclasses.replace(tr.cfg, steps=n)
    out = tr.train(out["params"], out["opt"], start_step=1)
    changes = rtrain.change_norms(s.lay, lo, hi, s.dtype, flat(out["params"]))
    losses = [m["loss"] for m in tr.metrics_log[logged:logged + n]]
    s.times.update(init_s=t1 - t0, first_step_s=t2 - t1,
                   check_steps_s=time.perf_counter() - t2)
    return out["params"], out["opt"], rtrain.Readings(losses, grads, changes, samples)


@dataclass
class Window:
    steps: int
    seconds: float
    compiles: int
    host_input_s: List[float]
    #: host seconds of each step, from one ``batch_at`` to the next; the
    #: first from the window's start, the last to its end
    step_s: List[float]
    #: the trainer's own ``metrics_log`` entry of each step, in order
    counters: List[Dict[str, float]]
    trace: Optional[trace.Reduced] = None


class _CompileCounter:
    """Counts backend compilations while it is active."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.active, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event == self.EVENT:
            self.n += 1


def measure(s: Setup, params, opt, seconds: float, trace_dir: Optional[Path],
            counter: _CompileCounter) -> Window:
    """The window: ``Trainer.train()`` from the check steps on until the
    trainer's preemption flag, raised by ``SIGALRM`` after ``seconds``."""
    tr, start = s.trainer, s.cell.workload["check_steps"]
    tr.cfg = dataclasses.replace(tr.cfg, steps=s.cell.workload["optimizer"]["total_steps"])
    tr.install_preemption_handler(signals=(signal.SIGALRM,))
    done_before = len(s.pipeline.durations)
    logged = len(tr.metrics_log)
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        # no tracing of every Python call: it would slow the host loop that
        # the window measures; the benchmark's own spans stay
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        jax.block_until_ready(jnp.zeros(()) + 1)  # the session is up on the device
    counter.active = True
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            t0 = time.perf_counter()
            signal.setitimer(signal.ITIMER_REAL, seconds)
            out = tr.train(params, opt, start_step=start)
            t1 = time.perf_counter()
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        counter.active = False
        if trace_dir is not None:
            jax.profiler.stop_trace()
    marks = [t0, *s.pipeline.starts[done_before + 1:], t1]
    w = Window(out["step"] - start, t1 - t0, counter.n,
               s.pipeline.durations[done_before:], list(np.diff(marks)),
               tr.metrics_log[logged:])
    if trace_dir is not None:
        w.trace = trace.read(trace.find_xspace(str(trace_dir)))
        if w.trace.ops:
            lo = w.trace.window[0]
            longest = sorted(trace.gaps(w.trace.ops[trace.busiest(w.trace)],
                                        w.trace.window), key=lambda g: g[0] - g[1])[:3]
            print("longest idle gaps (start, length) s: " + " ".join(
                f"({(a - lo) / 1e9:.4f}, {(b - a) / 1e9:.4f})" for a, b in longest),
                file=sys.stderr)
    for x in jax.tree.leaves((out["params"], out["opt"])):
        x.delete()
    return w


def memory_peak_bytes(n: int) -> Optional[int]:
    stats = [d.memory_stats() for d in jax.devices()[:n]]
    peaks = [st["peak_bytes_in_use"] for st in stats if st and "peak_bytes_in_use" in st]
    return max(peaks) if peaks else None


@dataclass
class Reading:
    """What a per-layer metric's ``read`` gets."""

    trace: trace.Reduced
    steps: int
    host_input_s: List[float]
    cfg: dict
    mix: dict
    chips: int
    peak: dict
    #: the program's counters: per window step, in order, the numbers the
    #: trainer's step logged (``Trainer.metrics_log``: ``loss``,
    #: ``grad_norm``, ...), as the trainer fetched them; no fetch of its own
    counters: List[Dict[str, float]] = field(default_factory=list)


def reference_batches(cell: spec.Cell, seed: int) -> List[Dict[str, np.ndarray]]:
    return [traffic.batch_at(cell.mix, cell.cfg["vocab_size"], seed, t)
            for t in range(cell.workload["check_steps"])]


def compare(s: Setup, seed: int, prog: rtrain.Readings):
    """Run the reference and judge; returns (correct, checks)."""
    expected = reference_batches(s.cell, seed)
    delivered = [s.pipeline.kept.get(t, {}) for t in range(len(expected))]
    ref = rtrain.run(s.cell.reference, s.cell.cfg, s.cell.workload["optimizer"],
                     seed, expected)
    numbers = {**check.gaps(prog, ref), **check.batch_faults(delivered, expected)}
    return check.judge(numbers, {**s.cell.workload["limits"],
                                 "batch_mismatch": 0.0, "repeated_rows": 0.0})


def device_summary() -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)}


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool,
        t_start: float, out_dir: Path, hook: Optional[Callable] = None) -> dict:
    """One run; the result object the benchmark prints last.

    ``hook(setup)``, when given, may replace parts of the trainer before
    anything runs (the tests break the timed path with it)."""
    counter = _CompileCounter()
    t_imported = time.perf_counter()
    s = build(cell, seed)
    if hook is not None:
        hook(s)
    params, opt, prog = drive_check_steps(s, seed)
    setup_s = time.perf_counter() - t_start
    s.times.update(import_s=t_imported - t_start, setup_s=setup_s)
    print("setup_split " + " ".join(f"{k} {v!r}" for k, v in s.times.items()),
          flush=True)
    w = measure(s, params, opt, seconds,
                out_dir / "trace" / cell.name if traced else None, counter)
    del params, opt
    peak_mem = memory_peak_bytes(cell.chips)
    correct, checks = compare(s, seed, prog)
    dev = {**device_summary(), "memory_peak_bytes": peak_mem}
    tokens = w.steps * cell.mix["global_batch"] * cell.mix["seq_len"]
    pk = flops.peak(dev["kind"]) if dev["platform"] == "tpu" else None
    result = {"correct": correct, "attempted": w.steps, "failed": 0}
    if not traced:
        tps = tokens / w.seconds
        values = {"tokens_per_s": tps, "setup_s": setup_s}
        if pk is not None:
            values["mfu"] = 100.0 * tps * cell.reference.train_flops_per_token(
                cell.cfg, cell.mix["seq_len"]) / (cell.chips * pk["bf16_flops_per_s"])
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in values}
    else:
        r = Reading(w.trace, w.steps, w.host_input_s, cell.cfg, cell.mix,
                    cell.chips, pk, w.counters)
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(r)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        busy = [trace.busy_ns(ops) for ops in w.trace.ops.values()]
        dev["busy_s"] = statistics.mean(busy) / 1e9 if busy else 0.0
        dev["window_s"] = w.trace.window_ns / 1e9
        result["breakdown"] = trace.breakdown(w.trace) if w.trace.ops else {
            "device_ops": [], "idle_gaps": []}
    print(f"window steps {w.steps} seconds {w.seconds!r} compiles {w.compiles} "
          f"host_input_s_mean {statistics.mean(w.host_input_s) if w.host_input_s else 0!r}",
          file=sys.stderr)
    print("window step_ms " + " ".join(f"{1e3 * x:.2f}" for x in w.step_s),
          file=sys.stderr)
    result.update(metrics=metrics, device=dev, checks=checks)
    return result
