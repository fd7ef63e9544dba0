"""Compile rehearsals for a TPU v5e that is described, not attached.

Each Pallas kernel at a real model width, and the one-chip ``qwen2-0.5b``
DFabric train step at ``chip_smoke.py``'s batch, compiled by the TPU
compiler for ``v5e:2x2``: tiling, VMEM and HBM refusals show up here
before any chip time is spent.  Nothing runs, so nothing here says
anything about results or times.  The compiled text also shows that the
program's ``jax.named_scope``s (layers, optimizer, collective legs, on the
four-chip step too) and each kernel's name survive the TPU compiler,
which is what a device trace is read by.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library.
"""
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from conftest import REPO


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_call(name, one_chip):
    """(fn, argument shapes) of one kernel at a real width."""
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if name == "flash_attention":  # qwen2-0.5b: 14 q / 2 kv heads of 64
        from repro.kernels.flash_attention.kernel import flash_attention_fwd
        B, H, KV, S, hd = 1, 14, 2, 2048, 64
        return flash_attention_fwd, (sds((B, H, S, hd), jnp.bfloat16),
                                     sds((B, KV, S, hd), jnp.bfloat16),
                                     sds((B, KV, S, hd), jnp.bfloat16))
    if name == "wkv6":  # rwkv6-1.6b: 32 heads of 64
        from repro.kernels.wkv6.kernel import wkv6_fwd
        B, H, S, hd = 1, 32, 4096, 64
        return wkv6_fwd, (*(sds((B, H, S, hd)),) * 4, sds((H, hd)),
                          sds((B, H, hd, hd)))
    if name == "mamba_scan":  # jamba: d_inner 16384, d_state 16
        from repro.kernels.mamba_scan.kernel import mamba_scan_fwd
        B, S, di, ds = 1, 2048, 16384, 16
        return mamba_scan_fwd, (sds((B, S, di)), sds((B, S, di)),
                                sds((di, ds)), sds((B, S, ds)),
                                sds((B, S, ds)), sds((di,)),
                                sds((B, di, ds)))
    raise ValueError(name)


@pytest.mark.parametrize("name", ["flash_attention", "wkv6", "mamba_scan"])
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, args = _kernel_call(name, one_chip)
    text = jax.jit(lambda *a: fn(*a)).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    # the kernel's pallas_call name names its custom call, so a device
    # trace finds the kernel by name
    assert re.search(rf"%{name}(\.\d+)? = .*custom-call\(", text)


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    return cs


def _compile_train_step(topo, mesh_shape, B, S):
    """The DFabric train step of ``chip_smoke.py``'s model at batch x seq,
    compiled for the described chips of a ``mesh_shape`` mesh."""
    from repro.configs.base import ShapeConfig, get_arch
    from repro.launch.cells import cell_settings
    from repro.models.registry import build_model
    from repro.runtime.train_loop import (Trainer, TrainerConfig,
                                          batch_sharding, mesh_info)
    from repro.utils.jax_compat import make_mesh

    cs = _chip_smoke()
    arch = get_arch(cs.ARCH)
    shape = ShapeConfig("chip_smoke", S, B, "train")
    model = build_model(arch, cell_settings(arch, shape))
    n = int(np.prod(mesh_shape))
    mesh = make_mesh(mesh_shape, cs.MESH_AXES, devices=topo.devices[:n])
    tr = Trainer(model, mesh, shape, TrainerConfig(mode="dfabric"))

    def with_sharding(shapes, shardings):
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, shardings,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    mi = mesh_info(mesh)
    params = with_sharding(model.param_shapes(), jax.tree.map(
        lambda s: NamedSharding(mesh, s), model.param_specs(mi)))
    opt = with_sharding(jax.eval_shape(tr._init_state), tr.state_sharding)
    bsh = batch_sharding(mesh, model, mi)
    batch = {k: jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=bsh[k])
             for k in ("tokens", "labels")}
    step = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))
    return tr.step_fn.lower(params, opt, batch, step).compile()


@pytest.fixture(scope="module")
def one_chip_step(topo):
    cs = _chip_smoke()
    return _compile_train_step(topo, (1, 1, 1), cs.TRAIN["batch"], cs.TRAIN["seq"])


@pytest.fixture(scope="module")
def dp4_step(topo):
    """The four-chip step on a (pod, data) = (2, 2) mesh,
    one row per chip, at a shorter sequence."""
    return _compile_train_step(topo, (2, 2, 1), 4, 512)


def test_one_chip_train_step_compiles_for_v5e(one_chip_step):
    ma = one_chip_step.memory_analysis()
    # the compiler refuses a program over HBM; this pins what it accepted
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 16e9


def test_one_chip_train_step_stacks_no_score_tiles(one_chip_step):
    """The masked attention's backward recomputes its 1024 x 1024 score
    tiles: the compiled step writes none of them into a stack."""
    stacked = [ln for ln in one_chip_step.as_text().splitlines()
               if "dynamic-update-slice" in ln
               and re.match(r"\s*(ROOT )?%\S+ = \w+\[(\d+,)*1024,1024\]", ln)]
    assert not stacked, stacked[:3]


def _phase(op_name):
    if "rematted_computation" in op_name:
        return "remat"
    return "backward" if "transpose(" in op_name else "forward"


@pytest.mark.parametrize("scope,phases", [
    ("attention", {"forward", "remat", "backward"}),
    ("mlp", {"forward", "remat", "backward"}),
    ("lm_loss", {"forward", "remat", "backward"}),
    ("grad_sync", {"forward"}),
    ("adamw", {"forward"}),
])
def test_one_chip_train_step_carries_scopes(one_chip_step, scope, phases):
    """Each layer's scope reaches the compiled step's op_name metadata in
    each phase it runs in; the optimizer's two scopes never nest."""
    names = re.findall(r'op_name="([^"]*)"', one_chip_step.as_text())

    def has(o, sc):  # a path component, or wrapped: jvp(lm_loss)
        return re.search(rf"[/(]{sc}[/)]", o) is not None

    assert {_phase(o) for o in names if has(o, scope)} == phases
    assert not [o for o in names if has(o, "grad_sync") and has(o, "adamw")]


def test_dp4_train_step_relays_no_slow_chunks(dp4_step):
    """The slow leg cuts each ZeRO-1 shard's chunks from its leading rows:
    the compiled four-chip step copies no shard, chunk by chunk, into an
    ``f32[1, chunks, n]`` buffer."""
    relayed = [ln for ln in dp4_step.as_text().splitlines()
               if "dynamic-update-slice" in ln
               and re.match(r"\s*(ROOT )?%\S+ = f32\[1,\d+,\d+\]", ln)]
    assert not relayed, relayed[:3]


@pytest.mark.parametrize("leg,op", [("reduce_scatter", "reduce-scatter"),
                                    ("slow_chunk", "all-reduce"),
                                    ("all_gather", "all-gather")])
def test_dp4_train_step_carries_leg_scopes(dp4_step, leg, op):
    """Each DFabric leg's collectives carry the leg's scope inside
    ``grad_sync`` in the four-chip step, so each tier gets its own time."""
    text = dp4_step.as_text()
    found = [ln for ln in text.splitlines()
             if re.search(rf"\s{op}(-start)?\(", ln)
             and re.search(rf'op_name="[^"]*/grad_sync/(\S*/)?{leg}/', ln)]
    assert found, f"no {op} scoped grad_sync/{leg}"
