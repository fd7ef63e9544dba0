"""Collective time per step that no other operation overlaps, on the chip
where it is largest (layer: grad sync, ``optim.grad_sync`` ->
``core.collectives``).  Nothing to read where no collective ran."""
from bench import trace


def read(r):
    t = r.trace
    per = {d: t.leaves.get(d, []) + t.async_ops.get(d, []) for d in t.ops}
    if not r.steps or not any(trace.is_collective(n) for ops in per.values()
                              for n, _, _ in ops):
        return None
    return max(trace.exposed_collective_ns(t.leaves.get(d, []), t.async_ops.get(d, []))
               for d in t.ops) / r.steps / 1e6
