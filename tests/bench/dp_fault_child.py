"""Child of ``test_bench_correct``: a tiny data-parallel cell on four CPU
devices, run soundly and with the exchange between chips left out.  Prints
``{"sound": correct, "no_exchange": correct}``."""
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
from bench_fixtures import tiny_cell  # noqa: E402


def no_exchange(s):
    """Each chip keeps its own shard of its own gradient: the reduce-scatter
    of the ZeRO-1 sync moves nothing between chips."""
    from jax import lax

    from repro.core.collectives import normalize_axes
    from repro.optim import grad_sync
    from repro.utils.jax_compat import axis_size

    def local_shard(x, fast_axis, slow_axis, cfg, scatter_dim=0, ef=None, **kw):
        n = 1
        for a in normalize_axes(fast_axis):
            n *= axis_size(a)
        blk = x.shape[scatter_dim] // n
        return lax.slice_in_dim(x, 0, blk, axis=scatter_dim), ef

    grad_sync.dfabric_reduce_scatter = local_shard


def main(root):
    from bench import cell as bcell

    out = {}
    for name, hook in (("sound", None), ("no_exchange", no_exchange)):
        cell = tiny_cell(Path(root) / name, chips=4, mesh=(2, 2, 1))
        res = bcell.run(cell, 3_000_000_023, 0.3, False, time.perf_counter(),
                        Path(root) / name / "out", hook=hook)
        print(name, res["checks"], file=sys.stderr)
        out[name] = res["correct"]
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1])
