"""Training runtime: step factories (DFabric explicit-DP and GSPMD modes),
fault tolerance (checkpoint/restart, preemption, failure injection) and
straggler mitigation.

Step modes (DESIGN.md §4):
  * ``dfabric`` — shard_map with manual axes (pod, data); the model's TP
    axis stays auto/GSPMD.  Gradient sync + (optionally fused ZeRO-1)
    update run through the paper's hierarchical striped collectives.
  * ``gspmd``   — pure pjit; FSDP over 'data', TP over 'model', DP over
    'pod'.  Used for the two >300B archs whose parameters cannot be
    replicated within a pod.  The sharding assignment itself realizes the
    paper's striping: FSDP grads reduce-scatter over ICI, and the pod-axis
    all-reduce then carries only each chip's FSDP shard over DCN.
"""
from __future__ import annotations

import dataclasses
import signal
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.planner import Planner, SyncPlan
from repro.core.topology import fabric_from_mesh_sizes
from repro.utils import jax_compat
from repro.models.registry import Model
from repro.models.sharding import MeshInfo
from repro.obs.metrics import MetricsLogger
from repro.optim.adamw import AdamWConfig, adamw_update, init_moments
from repro.optim import grad_sync
from repro.optim.grad_sync import SyncSettings, sync_and_update
from repro.utils.trees import tree_paths


# ---------------------------------------------------------------------------
# Mesh helpers
# ---------------------------------------------------------------------------


#: DP mesh axes, slowest tier first (the order batch dims are laid out in);
#: "host" is the optional mid tier of a 3-tier fabric (rack-level CXL).
DP_MESH_AXES = ("pod", "host", "data")


def dp_axes_of(sizes) -> Tuple[str, ...]:
    return tuple(a for a in DP_MESH_AXES if a in sizes)


def fast_axes_of(sizes) -> Tuple[str, ...]:
    """Fast-tier DP axes ordered FASTEST first (the reduce-scatter order);
    the slowest tier ("pod") is excluded."""
    return tuple(a for a in ("data", "host") if a in sizes)


def mesh_info(mesh: Mesh, *, fsdp: bool = False,
              embed_tp: bool = True) -> MeshInfo:
    """``embed_tp``: shard the vocab tables over the TP axis."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return MeshInfo(sizes, tp_axis="model" if "model" in sizes else None,
                    fsdp_axis="data" if fsdp else None,
                    dp_axes=dp_axes_of(sizes), embed_tp=embed_tp)


def batch_sharding(mesh: Mesh, model: Model, mi: MeshInfo):
    return {k: NamedSharding(mesh, v)
            for k, v in model.batch_specs(mi).items()}


# ---------------------------------------------------------------------------
# DFabric explicit-DP step
# ---------------------------------------------------------------------------


def make_sync_plan(model: Model, mesh: Mesh, topo=None, *,
                   codec: Optional[str] = None, strategy: str = "auto",
                   bucket_bytes: int = 4 << 20,
                   embed_tp: bool = True,
                   pipeline: bool = True,
                   mid_codec: Optional[str] = None) -> Tuple[SyncPlan, SyncSettings]:
    """Plan the gradient sync of ``model`` on ``mesh``: the one place the
    mesh's axes are bound to the sync.  The planner prices on ``topo``
    (TwoTierTopology | FabricSpec; None: ``fabric_from_mesh_sizes`` of
    the mesh) and plans every schedule on the mesh's own fast DP axes,
    which the executor lowers as planned."""
    mi = mesh_info(mesh, embed_tp=embed_tp)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if topo is None:
        topo = fabric_from_mesh_sizes(sizes)
    fast_axes = fast_axes_of(sizes) or ("data",)
    fast_sizes = tuple(sizes.get(a, 1) for a in fast_axes)
    n_fast = int(np.prod(fast_sizes))
    n_slow = sizes.get("pod", 1)
    ss = SyncSettings(mode="zero1", fast_axes=fast_axes,
                      slow_axis="pod" if "pod" in sizes else None,
                      n_fast=n_fast, n_slow=n_slow,
                      model_axis="model" if "model" in sizes else None)
    shapes = tree_paths(model.param_shapes())
    specs = tree_paths(model.param_specs(mi))
    avoid = {p: frozenset(i for i, s in enumerate(sp) if s is not None)
             for p, sp in specs.items()}
    # the sync runs model-manual (nested shard_map): divisibility decisions
    # use the per-TP-shard local block shapes
    ntp = sizes.get("model", 1)

    def local_shape(path):
        sh = list(shapes[path].shape)
        for d, ax in enumerate(specs[path]):
            if ax is not None and d < len(sh):
                sh[d] //= ntp
        return tuple(sh)

    local = {p: local_shape(p) for p in shapes}
    planner = Planner(topo, fast_axes=tuple(zip(fast_axes, fast_sizes)),
                      codec=codec, strategy=strategy, pipeline=pipeline,
                      mid_codec=mid_codec)
    plan = planner.plan(shapes, bucket_bytes=bucket_bytes, avoid_dims=avoid,
                        local_shapes=local)
    return plan, ss


def make_dfabric_train_step(model: Model, mesh: Mesh, plan: SyncPlan,
                            ss: SyncSettings, opt_cfg: AdamWConfig,
                            lr_fn: Callable, *, microbatches: int = 1,
                            zero1: bool = True, donate: bool = True,
                            embed_tp: bool = True):
    """Returns (step_fn(params, sync_state, batch, step_idx) ->
    (params, sync_state, metrics), init_sync_state_fn, state_sharding).

    The model fwd/bwd runs with manual DP axes (pod [, host], data) and
    auto TP; the gradient sync runs inside a NESTED shard_map that also
    manualizes the TP axis — psum_scatter of TP-sharded gradients is then
    a purely local reduce-scatter instead of a full replication gather
    (§Perf iter. 6).
    """
    if not zero1:
        ss = dataclasses.replace(ss, mode="paper")
    arch = model.arch
    manual = set(ss.fast_axes) | ({ss.slow_axis} if ss.slow_axis else set())
    dp_axes = tuple(a for a in DP_MESH_AXES if a in manual)
    dp_spec = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    pshapes = model.param_shapes()
    state_specs = grad_sync.sync_state_specs(plan, pshapes, ss)

    mi = mesh_info(mesh, embed_tp=embed_tp)
    pspecs_model = model.param_specs(mi)
    if ss.model_axis is not None:
        in_state_specs = grad_sync.inner_state_specs(
            plan, tree_paths(pspecs_model), tree_paths(pshapes))

    def run_sync(params, grads, sync_state, lr):
        if ss.model_axis is None:
            return sync_and_update(params, grads, sync_state, plan, ss, lr,
                                   opt_cfg)
        fast_idx = grad_sync.flat_fast_index(ss)  # parent-manual axes
        inner = jax_compat.shard_map(
            lambda p, g, s, lr_, fi: sync_and_update(p, g, s, plan, ss,
                                                     lr_, opt_cfg, fast_idx=fi),
            in_specs=(pspecs_model, pspecs_model, in_state_specs, P(), P()),
            out_specs=(pspecs_model, in_state_specs, {"grad_norm": P()}),
            axis_names={ss.model_axis}, check_vma=False)
        return inner(params, grads, sync_state, lr, fast_idx)

    def step_body(params, sync_state, batch, step_idx):
        def loss_of(p, b):
            return model.loss(p, b)

        if microbatches > 1:
            def micro(acc, mb):
                l, g = jax.value_and_grad(loss_of)(params, mb)
                return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None
            mbatch = jax.tree.map(
                lambda a: a.reshape((microbatches, a.shape[0] // microbatches)
                                    + a.shape[1:]), batch)
            zero = (jnp.zeros(()),
                    jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype), params))
            (loss, grads), _ = lax.scan(micro, zero, mbatch)
            loss = loss / microbatches
            grads = jax.tree.map(lambda g: g / microbatches, grads)
        else:
            loss, grads = jax.value_and_grad(loss_of)(params, batch)

        loss = lax.pmean(loss, dp_axes if len(dp_axes) > 1 else dp_axes[0])
        lr = lr_fn(step_idx)
        new_params, new_state, metrics = run_sync(params, grads, sync_state, lr)
        metrics = dict(metrics)
        metrics["loss"] = loss
        metrics["lr"] = lr * jnp.ones(())
        return new_params, new_state, metrics

    batch_specs = {k: P(dp_spec, *([None] * 1)) for k in ("tokens", "labels")}
    if arch.is_encdec:
        batch_specs["frames"] = P(dp_spec, None, None)
    metric_specs = {"loss": P(), "grad_norm": P(), "lr": P()}

    fn = jax_compat.shard_map(step_body, mesh=mesh,
                              in_specs=(P(), state_specs, batch_specs, P()),
                              out_specs=(P(), state_specs, metric_specs),
                              axis_names=manual, check_vma=False)
    jit_kw = dict(donate_argnums=(0, 1)) if donate else {}
    step_fn = jax.jit(fn, **jit_kw)

    def init_state():
        return grad_sync.init_sync_state(plan, pshapes, ss)

    merged = grad_sync.merged_state_specs(plan, pshapes, pspecs_model, ss)
    state_sharding = jax.tree.map(lambda s: NamedSharding(mesh, s), merged)
    return step_fn, init_state, state_sharding


# ---------------------------------------------------------------------------
# GSPMD (FSDP) step
# ---------------------------------------------------------------------------


def zero_moment_specs(pshapes, pspecs, mesh: Mesh):
    """ZeRO-style optimizer-moment sharding for GSPMD steps: each moment is
    sharded on its largest dim divisible by a mesh axis not already used by
    the param spec (prefer 'data', then 'model')."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def spec_of(sds, pspec):
        used = {a for e in pspec for a in ((e,) if isinstance(e, str) else (e or ()))}
        entries = list(pspec) + [None] * (len(sds.shape) - len(pspec))
        for axis in ("data", "model"):
            if axis in used or axis not in sizes:
                continue
            n = sizes[axis]
            cands = [(d, s) for d, s in enumerate(sds.shape)
                     if entries[d] is None and s % n == 0]
            if cands:
                d = max(cands, key=lambda ds: ds[1])[0]
                entries[d] = axis
                used.add(axis)
        return P(*entries)

    return jax.tree.map(spec_of, pshapes, pspecs,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def make_gspmd_train_step(model: Model, mesh: Mesh, opt_cfg: AdamWConfig,
                          lr_fn: Callable, *, fsdp: bool = True,
                          microbatches: int = 1, donate: bool = True,
                          mi: Optional[MeshInfo] = None,
                          zero_opt: bool = False):
    mi = mi or mesh_info(mesh, fsdp=fsdp)
    pspecs = model.param_specs(mi)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    if zero_opt:
        mspecs = zero_moment_specs(model.param_shapes(), pspecs, mesh)
        mshard = jax.tree.map(lambda s: NamedSharding(mesh, s), mspecs)
    else:
        mshard = pshard
    oshard = {"m": mshard, "v": mshard,
              "step": NamedSharding(mesh, P())}
    bshard = batch_sharding(mesh, model, mi)

    def step(params, opt_state, batch, step_idx):
        def loss_of(p, b):
            return model.loss(p, b)
        if microbatches > 1:
            def micro(acc, mb):
                l, g = jax.value_and_grad(loss_of)(params, mb)
                return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None
            mbatch = jax.tree.map(
                lambda a: a.reshape((microbatches, a.shape[0] // microbatches)
                                    + a.shape[1:]), batch)
            zero = (jnp.zeros(()),
                    jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype), params))
            (loss, grads), _ = lax.scan(micro, zero, mbatch)
            loss = loss / microbatches
            grads = jax.tree.map(lambda g: g / microbatches, grads)
        else:
            loss, grads = jax.value_and_grad(loss_of)(params, batch)
        lr = lr_fn(step_idx)
        new_p, new_opt = adamw_update(params, grads, opt_state, lr, opt_cfg)
        from repro.optim.adamw import global_norm
        return new_p, new_opt, {"loss": loss, "grad_norm": global_norm(grads),
                                "lr": lr * jnp.ones(())}

    jit_kw = dict(donate_argnums=(0, 1)) if donate else {}
    step_fn = jax.jit(step,
                      in_shardings=(pshard, oshard, bshard, None),
                      out_shardings=(pshard, oshard, None),
                      **jit_kw)
    return step_fn, pshard, oshard, bshard


# ---------------------------------------------------------------------------
# Straggler watchdog (EWMA z-score on step times)
# ---------------------------------------------------------------------------


@dataclass
class StragglerWatchdog:
    """Detects slow steps; on a real fleet the mitigation hook triggers
    hot-spare swap / data rebalancing — here it records the event."""

    alpha: float = 0.2
    z_threshold: float = 3.0
    warmup: int = 5
    mean: float = 0.0
    var: float = 0.0
    n: int = 0
    events: List[Dict[str, Any]] = field(default_factory=list)
    mitigation_hook: Optional[Callable[[Dict[str, Any]], None]] = None

    def update(self, step: int, dt: float) -> Optional[Dict[str, Any]]:
        self.n += 1
        if self.n <= self.warmup:
            # prime the EWMA
            self.mean = dt if self.n == 1 else (1 - self.alpha) * self.mean + self.alpha * dt
            self.var = max(self.var, (dt - self.mean) ** 2)
            return None
        std = max(self.var ** 0.5, 1e-6, 0.05 * self.mean)
        z = (dt - self.mean) / std
        event = None
        if z > self.z_threshold:
            event = {"step": step, "dt": dt, "z": z, "mean": self.mean,
                     "action": "flag-straggler (hot-spare swap on real fleet)"}
            self.events.append(event)
            if self.mitigation_hook:
                self.mitigation_hook(event)
        else:
            self.mean = (1 - self.alpha) * self.mean + self.alpha * dt
            self.var = (1 - self.alpha) * self.var + self.alpha * (dt - self.mean) ** 2
        return event


class SimulatedFailure(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# Trainer
# ---------------------------------------------------------------------------


@dataclass
class TrainerConfig:
    steps: int = 100
    lr: float = 3e-4
    warmup: int = 10
    ckpt_every: int = 0  # 0 = no checkpointing
    ckpt_dir: Optional[str] = None
    ckpt_keep: int = 3
    log_every: int = 10
    microbatches: int = 1
    mode: str = "dfabric"  # dfabric | gspmd
    zero1: bool = True
    codec: Optional[str] = None
    pipeline: bool = True  # overlap slow-leg chunks with fast all-gathers
    fail_at_step: Optional[int] = None  # failure injection (tests)
    seed: int = 0
    metrics_path: Optional[str] = None  # JSONL sink (repro.obs.metrics)
    # jax.profiler trace of steps [A, B) into this directory (TensorBoard /
    # Perfetto); None = no trace.  ``profile_steps`` None = every step.
    profile_dir: Optional[str] = None
    profile_steps: Optional[Tuple[int, int]] = None


# ---------------------------------------------------------------------------
# Recompiles, seen from the loop
# ---------------------------------------------------------------------------

#: the jax.monitoring event of one backend compile (a load from the
#: persistent compilation cache included)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = 0  # backend compiles this process has made
_compile_listener = False


def _count_compile(event: str, duration: float, **kw) -> None:
    global _compiles
    if event == COMPILE_EVENT:
        _compiles += 1


def _watch_compiles() -> None:
    """Register the process's one compile listener (jax.monitoring keeps
    every listener for the life of the process)."""
    global _compile_listener
    if not _compile_listener:
        jax.monitoring.register_event_duration_secs_listener(_count_compile)
        _compile_listener = True


class Trainer:
    """End-to-end training driver with checkpoint/restart + preemption."""

    def __init__(self, model: Model, mesh: Mesh, shape: ShapeConfig,
                 cfg: TrainerConfig, topo=None,  # None: from the mesh
                 data_pipeline=None):
        from repro.checkpoint.manager import CheckpointManager
        from repro.data.pipeline import DataConfig, TokenPipeline

        self.model, self.mesh, self.shape, self.cfg = model, mesh, shape, cfg
        self.pipeline = data_pipeline or TokenPipeline(
            model.arch, shape, DataConfig(seed=cfg.seed))
        opt_cfg = AdamWConfig()
        from repro.optim.adamw import cosine_schedule
        lr_fn = cosine_schedule(cfg.lr, cfg.warmup, cfg.steps)
        self.mi = mesh_info(mesh, fsdp=(cfg.mode == "gspmd"))
        if cfg.mode == "dfabric":
            self.plan, self.ss = make_sync_plan(model, mesh, topo,
                                                codec=cfg.codec,
                                                pipeline=cfg.pipeline)
            self.step_fn, self._init_state, self.state_sharding = \
                make_dfabric_train_step(model, mesh, self.plan, self.ss,
                                        opt_cfg, lr_fn,
                                        microbatches=cfg.microbatches,
                                        zero1=cfg.zero1)
        else:
            self.plan = None
            self.step_fn, self.pshard, self.oshard, self.bshard = \
                make_gspmd_train_step(model, mesh, opt_cfg, lr_fn, fsdp=True,
                                      microbatches=cfg.microbatches)
        self.ckpt = (CheckpointManager(cfg.ckpt_dir, keep=cfg.ckpt_keep)
                     if cfg.ckpt_every and cfg.ckpt_dir else None)
        self.watchdog = StragglerWatchdog()
        self._preempted = False
        self.metrics_log: List[Dict[str, float]] = []
        # structured metrics: stdout lines as before, JSONL when
        # cfg.metrics_path is set (see repro.obs.metrics)
        self.metrics = MetricsLogger(path=cfg.metrics_path, run="train",
                                     mode=cfg.mode)
        if self.plan is not None:
            split = grad_sync.slow_split_counts(self.plan, self.ss)
            self.metrics.log("sync_plan", slow_chunks_rows=split["rows"],
                             slow_chunks_flat=split["flat"])

    # ---- preemption ------------------------------------------------------------
    def install_preemption_handler(self, signals=(signal.SIGTERM,)):
        def handler(signum, frame):
            self._preempted = True
        for s in signals:
            signal.signal(s, handler)

    # ---- init / restore -----------------------------------------------------------
    def init_state(self, key=None):
        key = key if key is not None else jax.random.key(self.cfg.seed)
        params = self.model.init(key)
        if self.cfg.mode == "dfabric":
            mi = mesh_info(self.mesh)
            pspecs = self.model.param_specs(mi)
            params = jax.device_put(params, jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), pspecs))
            opt = jax.device_put(self._init_state(), self.state_sharding)
        else:
            params = jax.device_put(params, self.pshard)
            opt = {"m": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
                   "v": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
                   "step": jnp.zeros((), jnp.int32)}
            opt = jax.device_put(opt, self.oshard)
        return params, opt, 0

    def try_restore(self):
        if self.ckpt is None:
            return None
        if self.cfg.mode == "dfabric":
            mi = mesh_info(self.mesh)
            pshard = jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                                  self.model.param_specs(mi))
            shardings = {"params": pshard, "opt": self.state_sharding}
        else:
            shardings = {"params": self.pshard, "opt": self.oshard}
        out = self.ckpt.restore(shardings=shardings)
        if out is None:
            return None
        step = int(out["data_state"]["step"])
        return out["params"], out["opt"], step

    # ---- the loop -------------------------------------------------------------------
    def train(self, params=None, opt=None, start_step: int = 0
              ) -> Dict[str, Any]:
        """Steps from ``start_step`` to ``cfg.steps`` (or a preemption).

        Each step is a ``jax.profiler.StepTraceAnnotation("train")`` holding
        one span per host action (``train.batch``, ``train.device_put``,
        ``train.dispatch``, ``train.fetch``: the wait for the device, and
        ``train.checkpoint``), on the device trace's clock.  A step that
        compiled is logged as a ``compile`` record and counted in
        ``compiles``."""
        _watch_compiles()
        restored = self.try_restore()
        if params is None:
            if restored is not None:
                params, opt, start_step = restored
            else:
                params, opt, start_step = self.init_state()
        mi = mesh_info(self.mesh)
        bshard = batch_sharding(self.mesh, self.model, mi) \
            if self.cfg.mode == "dfabric" else self.bshard

        step = start_step
        profiling = False
        prof_lo, prof_hi = self.cfg.profile_steps or (0, self.cfg.steps)
        try:
            while step < self.cfg.steps:
                if profiling and step >= prof_hi:
                    jax.profiler.stop_trace()
                    profiling = False
                if (self.cfg.profile_dir is not None and not profiling
                        and prof_lo <= step < prof_hi):
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0  # keep the host loop's pace
                    jax.profiler.start_trace(self.cfg.profile_dir,
                                             profiler_options=opts)
                    profiling = True
                with jax.profiler.StepTraceAnnotation("train", step_num=step):
                    t0 = time.perf_counter()
                    compiled_before = _compiles
                    with jax.profiler.TraceAnnotation("train.batch"):
                        host_batch = self.pipeline.batch_at(step)
                    with jax.profiler.TraceAnnotation("train.device_put"):
                        batch = {k: jax.device_put(v, bshard[k])
                                 for k, v in host_batch.items()}
                    with jax.profiler.TraceAnnotation("train.dispatch"):
                        params, opt, metrics = self.step_fn(params, opt, batch,
                                                            jnp.int32(step))
                    with jax.profiler.TraceAnnotation("train.fetch"):
                        metrics = {k: float(v) for k, v in metrics.items()}
                    dt = time.perf_counter() - t0
                    if _compiles > compiled_before:
                        self.metrics.log("compile", step=step,
                                         compiles=_compiles - compiled_before)
                        self.metrics.inc("compiles", _compiles - compiled_before)
                    self.watchdog.update(step, dt)
                    metrics.update(step=step, dt=dt)
                    self.metrics_log.append(metrics)
                    self.metrics.log("train_step", **metrics)
                    self.metrics.inc("steps")
                    self.metrics.gauge("loss", metrics["loss"])
                    if self.cfg.log_every and step % self.cfg.log_every == 0:
                        self.metrics.info(
                            f"step {step:5d} loss {metrics['loss']:.4f} "
                            f"gnorm {metrics['grad_norm']:.3f} dt {dt*1e3:.1f}ms")
                    step += 1
                    if self.ckpt and step % self.cfg.ckpt_every == 0:
                        with jax.profiler.TraceAnnotation("train.checkpoint"):
                            self.ckpt.save(step, {
                                "params": params, "opt": opt,
                                "data_state": self.pipeline.state_dict(step)})
                    if self.cfg.fail_at_step is not None and step >= self.cfg.fail_at_step:
                        raise SimulatedFailure(f"injected failure at step {step}")
                    if self._preempted:
                        if self.ckpt:
                            with jax.profiler.TraceAnnotation("train.checkpoint"):
                                self.ckpt.save(step, {
                                    "params": params, "opt": opt,
                                    "data_state": self.pipeline.state_dict(step)},
                                    blocking=True)
                        break
        finally:
            if profiling:
                jax.profiler.stop_trace()
            # emit the final 'summary' record and release the JSONL handle
            self.metrics.close()
            # drain the async checkpoint write even when a step raised: a
            # writer still running would race whoever restarts in ckpt_dir
            # (its new manager sweeps the half-written .tmp-step dir)
            if self.ckpt:
                self.ckpt.wait()
        return {"params": params, "opt": opt, "step": step,
                "metrics": self.metrics_log,
                "straggler_events": self.watchdog.events}
