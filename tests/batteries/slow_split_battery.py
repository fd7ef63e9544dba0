"""Multi-device slow-leg cut battery (run via subprocess, 8 fake devices).

The sequential slow leg (``collectives._slow_group``) cuts chunk *i* of an
N-D shard as rows ``[i*d0/C, (i+1)*d0/C)`` when ``slow_split`` says
``"rows"``, and from the flattened shard otherwise.  On a (pod=2, data=4)
mesh, for C in {1, 2, 4, 8} with the issue order rotated,
``lower_reduce_scatter`` and non-pipelined ``lower_all_reduce``:

  * equal the flat cut (the same legs over the flattened shard) bitwise,
    and ``psum_scatter`` / ``psum`` bitwise;
  * do so too where C does not divide the leading dim (the flat fallback);
  * log exactly the legs ``CostModel.from_schedule`` prices;
  * keep the flat cut for a codec schedule;

and ``slow_split_counts`` of the ``qwen2-0.5b`` plan on the benchmark's
(pod, data, model) = (2, 2, 1) mesh is 56 rows and 1 flat, which is what
the ``Trainer`` logs in its ``sync_plan`` record.
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import CostModel, SyncConfig
from repro.core.collectives import (_slow_group, lower_all_reduce,
                                    lower_reduce_scatter, slow_split)
from repro.core.schedule import build_schedule
from repro.core.topology import TwoTierTopology
from repro.utils import jax_compat

mesh = jax_compat.make_mesh((2, 4), ("pod", "data"))
fabric = TwoTierTopology(num_pods=2, pod_shape=(4,)).fabric
cost = CostModel(fabric)
MEMBERS = P(("pod", "data"))
rng = np.random.default_rng(17)


def lowered(sched, x, scatter):
    """(this lowering, the flat cut, psum_scatter/psum) per member, and the
    legs the lowering logged."""
    dim = sched.scatter_dim
    log = []

    def f(xs):
        xs = xs[0]
        if scatter:
            out, _ = lower_reduce_scatter(sched, xs, leg_log=log)
        else:
            out, _ = lower_all_reduce(sched, xs, leg_log=log)
        shard = lax.psum_scatter(xs, "data", scatter_dimension=dim, tiled=True)
        flat, _ = _slow_group(sched.slow_legs, shard.reshape(-1), None,
                              sched.cfg)
        flat = flat.reshape(shard.shape)
        ref = lax.psum(shard, "pod")
        if not scatter:
            flat = lax.all_gather(flat, "data", axis=dim, tiled=True)
            ref = lax.all_gather(ref, "data", axis=dim, tiled=True)
        return out[None], flat[None], ref[None]

    g = jax.jit(jax_compat.shard_map(f, mesh=mesh, in_specs=MEMBERS,
                                     out_specs=(MEMBERS,) * 3))
    xs = jax.device_put(x, NamedSharding(mesh, MEMBERS))
    return [np.asarray(a) for a in g(xs)], log


# (per-member operand shape, scatter dim): the shard's leading dim is 16,
# which every C divides, then 6, which neither 4 nor 8 divides
for shape, dim in (((16, 8, 12), 1), ((6, 8, 20), 1)):
    x = rng.standard_normal((8,) + shape).astype(np.float32)
    shard = list(shape)
    shard[dim] //= 4
    for C in (1, 2, 4, 8):
        for scatter in (True, False):
            cfg = SyncConfig("hier_striped", chunks=C, pipeline=False)
            # rotated issue order: chunks go back by index, not position
            sched = build_schedule(fabric, cfg, shape, dim) \
                .with_lane_offset(C - 1)
            assert len(sched.slow_legs) == C and not sched.pipelined, \
                sched.describe()
            cut = slow_split(sched.slow_legs, tuple(shard))
            assert cut == ("rows" if shard[0] % C == 0 else "flat"), (shard, C)
            (out, flat, ref), log = lowered(sched, x, scatter)
            np.testing.assert_array_equal(out, flat)
            np.testing.assert_array_equal(out, ref)
            whole = x.sum(0)
            if scatter:  # member m holds data shard m % 4
                parts = np.split(whole, 4, axis=dim)
                whole = np.stack([parts[m % 4] for m in range(8)])
            np.testing.assert_allclose(out, np.broadcast_to(whole, out.shape),
                                       rtol=1e-5, atol=1e-5)
            priced = [lc.leg for lc in cost.from_schedule(sched).leg_charges]
            want = list(sched.down_legs + sched.slow_legs) if scatter \
                else list(sched.legs)
            assert log == want, (log, want)
            assert list(sched.legs) == priced, (sched.legs, priced)
            print(f"{'rs' if scatter else 'ar'} shard={tuple(shard)} C={C}: "
                  f"{cut} == flat cut == psum OK")

# ---- a codec keeps the flat cut ---------------------------------------------

shape, dim = (16, 8, 12), 1
cfg = SyncConfig("hier_striped", chunks=4, pipeline=False, codec="int8",
                 codec_block=128)
sched = build_schedule(fabric, cfg, shape, dim)
assert [l.codec for l in sched.slow_legs] == ["int8"] * 4
assert slow_split(sched.slow_legs, (16, 2, 12)) == "flat"
x = rng.standard_normal((8,) + shape).astype(np.float32)
(out, flat, _), _ = lowered(sched, x, True)
np.testing.assert_array_equal(out, flat)
print("int8 codec: flat cut OK")

# ---- the benchmark's dp4 plan ------------------------------------------------

from repro.configs.base import ShapeConfig, get_arch  # noqa: E402
from repro.launch.cells import cell_settings  # noqa: E402
from repro.models.registry import build_model  # noqa: E402
from repro.optim.grad_sync import slow_split_counts  # noqa: E402
from repro.runtime.train_loop import Trainer, TrainerConfig  # noqa: E402

arch = get_arch("qwen2-0.5b")
cell = ShapeConfig("dp4", 2048, 4, "train")
dp4 = jax_compat.make_mesh((2, 2, 1), ("pod", "data", "model"),
                           devices=jax.devices()[:4])
tr = Trainer(build_model(arch, cell_settings(arch, cell)), dp4, cell,
             TrainerConfig(mode="dfabric"))
counts = slow_split_counts(tr.plan, tr.ss)
assert counts == {"rows": 56, "flat": 1}, counts
rec = [r for r in tr.metrics.records if r["event"] == "sync_plan"]
assert len(rec) == 1 and rec[0]["slow_chunks_rows"] == 56 \
    and rec[0]["slow_chunks_flat"] == 1, rec
print(f"dp4 plan: {counts['rows']} slow legs by rows, {counts['flat']} flat OK")

print("ALL OK")
