"""Compile rehearsals for a TPU v5e that is described, not attached.

Each Pallas kernel at a real model width, and the one-chip ``qwen2-0.5b``
DFabric train step at ``chip_smoke.py``'s batch, compiled by the TPU
compiler for ``v5e:2x2``: tiling, VMEM and HBM refusals show up here
before any chip time is spent.  Nothing runs, so nothing here says
anything about results or times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
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
    compiled = jax.jit(lambda *a: fn(*a)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_one_chip_train_step_compiles_for_v5e(topo):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    from repro.configs.base import ShapeConfig, get_arch
    from repro.launch.cells import cell_settings
    from repro.models.registry import build_model
    from repro.runtime.train_loop import (Trainer, TrainerConfig,
                                          batch_sharding, mesh_info)
    from repro.utils.jax_compat import make_mesh

    arch = get_arch(cs.ARCH)
    B, S = cs.TRAIN["batch"], cs.TRAIN["seq"]
    shape = ShapeConfig("chip_smoke", S, B, "train")
    model = build_model(arch, cell_settings(arch, shape))
    mesh = make_mesh((1, 1, 1), cs.MESH_AXES, devices=topo.devices[:1])
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
    compiled = tr.step_fn.lower(params, opt, batch, step).compile()
    ma = compiled.memory_analysis()
    # the compiler refuses a program over HBM; this pins what it accepted
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 16e9
