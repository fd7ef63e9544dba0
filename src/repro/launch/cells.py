"""Per-(arch x shape) cell construction: settings, step functions and
``input_specs()`` ShapeDtypeStruct stand-ins for the dry-run.

No real allocation happens here: parameters, optimizer state, batches and
KV caches are all ``jax.ShapeDtypeStruct`` with attached shardings.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, ArchConfig, ShapeConfig, get_arch, shape_applicable
from repro.core.topology import TwoTierTopology
from repro.models.registry import Model, build_model
from repro.models.transformer import ModelSettings
from repro.optim import grad_sync
from repro.optim.adamw import AdamWConfig, cosine_schedule
from repro.runtime.train_loop import (make_dfabric_train_step,
                                      make_gspmd_train_step, make_sync_plan,
                                      mesh_info)

# archs whose optimizer state / params cannot be replicated within a pod —
# they run the GSPMD+FSDP step (DESIGN.md §4); everything else runs the
# explicit DFabric DDP/ZeRO-1 step.
FSDP_ARCHS = {"nemotron-4-340b", "jamba-1.5-large-398b"}


def cell_settings(arch: ArchConfig, shape: ShapeConfig, *,
                  attn_impl: str = "masked", remat: str = "full") -> ModelSettings:
    big = arch.name in FSDP_ARCHS or arch.d_model >= 8192
    return ModelSettings(
        param_dtype="bfloat16",
        compute_dtype="bfloat16",
        attn_impl=attn_impl,
        attn_block=1024,
        attn_chunk=1024 if shape.seq_len > 2048 else min(shape.seq_len, 1024),
        remat=remat if shape.kind == "train" else "none",
        scan_layers=True,
        loss_chunk=min(2048, shape.seq_len),
        max_seq=shape.seq_len,
    )


def cell_microbatches(arch: ArchConfig, shape: ShapeConfig, dp_total: int) -> int:
    if shape.kind != "train":
        return 1
    local_b = shape.global_batch // dp_total
    want = 8 if arch.name in FSDP_ARCHS else (4 if arch.d_model >= 5120 else 1)
    while want > 1 and local_b % want != 0:
        want //= 2
    return max(want, 1)


@dataclass
class Cell:
    arch: ArchConfig
    shape: ShapeConfig
    mesh: Mesh
    model: Model
    mode: str  # train | prefill | decode
    step_kind: str  # dfabric | gspmd | serve
    fn: Callable  # the function handed to jax.jit (already wrapped if shard_map)
    args: Tuple  # ShapeDtypeStructs
    donate: Tuple[int, ...] = ()

    def lower(self):
        f = self.fn
        with self.mesh:  # sharding constraints need the mesh context
            if hasattr(f, "lower"):  # already jit-wrapped (step factories)
                return f.lower(*self.args)
            return jax.jit(f, donate_argnums=self.donate).lower(*self.args)


def _sds(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(tuple(int(s) for s in shape), dtype,
                                sharding=NamedSharding(mesh, spec))


def _tree_sds(shapes_tree, specs_tree, mesh):
    return jax.tree.map(
        lambda sds, spec: _sds(sds.shape, sds.dtype, mesh, spec),
        shapes_tree, specs_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def build_cell(arch_name: str, shape_name: str, mesh: Mesh, *,
               topo: Optional[TwoTierTopology] = None,
               attn_impl: str = "masked",
               codec: Optional[str] = None,
               sync_strategy: str = "hier_striped",
               zero1: bool = True,
               microbatches: Optional[int] = None,
               seq_shard: bool = False,
               moe_groups: int = 1,
               loss_chunk: Optional[int] = None,
               context_parallel: bool = False,
               embed_tp: bool = True) -> Cell:
    arch = get_arch(arch_name)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(arch, shape)
    if not ok:
        raise ValueError(f"skip: {why}")
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    st = cell_settings(arch, shape, attn_impl=attn_impl)
    ntp = sizes.get("model", 1)
    # repeat-KV layout when heads are TP-sharded but the GQA group factors
    # don't divide the TP degree (nemotron/stablelm/jamba/chameleon at TP16)
    if (arch.n_heads % ntp == 0 and arch.n_kv_heads % ntp != 0
            and (arch.n_heads // arch.n_kv_heads) % ntp != 0):
        st = dataclasses.replace(st, gqa_repeat=True)
    if seq_shard:
        # GSPMD-mode activations are globally batched -> constrain B too;
        # dfabric-mode batch dims are manual (local) -> only the seq axis.
        gspmd_like = (arch.name in FSDP_ARCHS) or shape.kind != "train"
        baxes = tuple(a for a in ("pod", "data") if a in sizes) if gspmd_like else None
        st = dataclasses.replace(st, seq_axis="model", batch_axes=baxes)
    if moe_groups > 1:
        # NOTE (§Perf deepseek iter.2): explicit group x expert constraints on
        # the dispatch buffers REGRESSED 6x (XLA materializes the resharding);
        # grouped routing alone gives the win — leave buffer placement to XLA.
        st = dataclasses.replace(st, moe_groups=moe_groups)
    if loss_chunk:
        st = dataclasses.replace(st, loss_chunk=loss_chunk)
    model = build_model(arch, st)
    fsdp = arch.name in FSDP_ARCHS
    mi = mesh_info(mesh, fsdp=fsdp)
    dp_total = mi.dp_total

    if shape.kind == "train":
        mb = microbatches or cell_microbatches(arch, shape, dp_total)
        opt_cfg = AdamWConfig()
        lr_fn = cosine_schedule(3e-4, 100, 10000)
        if context_parallel:
            # context-parallel cell (§Perf): blocks replicated over the TP
            # axis, activations sequence-sharded, ZeRO opt-state sharding,
            # pure-GSPMD step
            st = dataclasses.replace(st, seq_axis="model",
                                     batch_axes=tuple(a for a in ("pod", "data")
                                                      if a in sizes))
            model = build_model(arch, st)
            mi_cp = mesh_info(mesh, fsdp=False)
            mi_cp.tp_scope = "embed_only"
            step_fn, pshard, oshard, bshard = make_gspmd_train_step(
                model, mesh, opt_cfg, lr_fn, fsdp=False, microbatches=mb,
                donate=False, mi=mi_cp, zero_opt=True)
            pshapes = model.param_shapes()
            pspecs = model.param_specs(mi_cp)
            params = _tree_sds(pshapes, pspecs, mesh)
            mspecs = jax.tree.map(lambda sh: sh.spec, oshard["m"])
            moments = jax.tree.map(
                lambda sds, spec: _sds(sds.shape, jnp.float32, mesh, spec),
                pshapes, mspecs,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
            opt = {"m": moments, "v": moments,
                   "step": jax.ShapeDtypeStruct((), jnp.int32,
                                                sharding=NamedSharding(mesh, P()))}
            batch = _batch_sds(model, shape, mesh, mi_cp)
            step_idx = jax.ShapeDtypeStruct((), jnp.int32,
                                            sharding=NamedSharding(mesh, P()))
            return Cell(arch, shape, mesh, model, "train", "gspmd_cp",
                        step_fn, (params, opt, batch, step_idx))
        if fsdp:
            step_fn, pshard, oshard, bshard = make_gspmd_train_step(
                model, mesh, opt_cfg, lr_fn, fsdp=True, microbatches=mb,
                donate=False)
            pshapes = model.param_shapes()
            pspecs = model.param_specs(mi)
            params = _tree_sds(pshapes, pspecs, mesh)
            moments = jax.tree.map(
                lambda sds, spec: _sds(sds.shape, jnp.float32, mesh, spec),
                pshapes, pspecs,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
            opt = {"m": moments, "v": moments,
                   "step": jax.ShapeDtypeStruct((), jnp.int32,
                                                sharding=NamedSharding(mesh, P()))}
            batch = _batch_sds(model, shape, mesh, mi)
            step_idx = jax.ShapeDtypeStruct((), jnp.int32,
                                            sharding=NamedSharding(mesh, P()))
            return Cell(arch, shape, mesh, model, "train", "gspmd",
                        step_fn, (params, opt, batch, step_idx))
        # dfabric explicit-DP
        plan, ss = make_sync_plan(model, mesh, topo, codec=codec,
                                  strategy=sync_strategy, embed_tp=embed_tp)
        step_fn, init_state, state_sharding = make_dfabric_train_step(
            model, mesh, plan, ss, opt_cfg, lr_fn, microbatches=mb,
            zero1=zero1, donate=False, embed_tp=embed_tp)
        pshapes = model.param_shapes()
        pspecs = model.param_specs(mesh_info(mesh, embed_tp=embed_tp))
        params = _tree_sds(pshapes, pspecs, mesh)
        sshapes = jax.eval_shape(init_state)
        sync_state = jax.tree.map(
            lambda sds, sh: jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=sh),
            sshapes, state_sharding,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        batch = _batch_sds(model, shape, mesh, mi)
        step_idx = jax.ShapeDtypeStruct((), jnp.int32,
                                        sharding=NamedSharding(mesh, P()))
        return Cell(arch, shape, mesh, model, "train", "dfabric",
                    step_fn, (params, sync_state, batch, step_idx))

    # ---- inference cells -------------------------------------------------------
    mi = mesh_info(mesh, fsdp=fsdp)
    pshapes = model.param_shapes()
    pspecs = model.param_specs(mi)
    params = _tree_sds(pshapes, pspecs, mesh)
    if shape.kind == "prefill" or shape.name == "prefill_32k":
        tokens = _sds((shape.global_batch, shape.seq_len), jnp.int32, mesh,
                      _dp_spec(mi, 2, shape.global_batch))
        args = [params, tokens]
        if arch.is_encdec:
            frames = _sds((shape.global_batch, arch.encoder.n_frames, arch.d_model),
                          jnp.bfloat16, mesh, _dp_spec(mi, 3, shape.global_batch))
            fn = lambda p, t, f: model.prefill(p, t, frames=f)
            args.append(frames)
        else:
            fn = lambda p, t: model.prefill(p, t)
        return Cell(arch, shape, mesh, model, "prefill", "serve", fn, tuple(args))

    # decode
    cshapes = model.cache_shapes(shape.global_batch, shape.seq_len,
                                 n_frames=arch.encoder.n_frames if arch.is_encdec else None)
    cspecs = model.cache_specs(mi, shape.global_batch, shape.seq_len,
                               n_frames=arch.encoder.n_frames if arch.is_encdec else None)
    cache = _tree_sds(cshapes, cspecs, mesh)
    tokens = _sds((shape.global_batch, 1), jnp.int32, mesh,
                  _dp_spec(mi, 2, shape.global_batch))
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))
    fn = lambda p, c, t, i: model.decode_step(p, c, t, i)
    return Cell(arch, shape, mesh, model, "decode", "serve", fn,
                (params, cache, tokens, pos), donate=(1,))


def _dp_spec(mi, ndim: int, batch: Optional[int] = None) -> P:
    dp = mi.dp_axes if len(mi.dp_axes) > 1 else (mi.dp_axes[0] if mi.dp_axes else None)
    if batch is not None and dp is not None and batch % mi.dp_total != 0:
        dp = None  # tiny-batch cell (long_500k): batch stays unsharded
    return P(dp, *([None] * (ndim - 1)))


def _batch_sds(model: Model, shape: ShapeConfig, mesh: Mesh, mi) -> Dict[str, Any]:
    arch = model.arch
    dp_total = mi.dp_total
    B = shape.global_batch
    spec = _dp_spec(mi, 2)
    batch = {"tokens": _sds((B, shape.seq_len), jnp.int32, mesh, spec),
             "labels": _sds((B, shape.seq_len), jnp.int32, mesh, spec)}
    if arch.is_encdec:
        batch["frames"] = _sds((B, arch.encoder.n_frames, arch.d_model),
                               jnp.bfloat16, mesh, _dp_spec(mi, 3))
    return batch


def input_specs(arch_name: str, shape_name: str, mesh: Mesh, **kw):
    """ShapeDtypeStruct stand-ins for every input of the cell's step
    (the brief's ``input_specs()`` entry point)."""
    return build_cell(arch_name, shape_name, mesh, **kw).args
