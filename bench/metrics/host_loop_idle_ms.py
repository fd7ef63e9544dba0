"""Device idle time per step outside every ``train.batch`` span, on the
idlest chip: what the training loop's own actions leave the chip waiting
for (``device_put``, dispatch, the metrics' fetch, checkpoints, the
bookkeeping between steps) (layer: host loop).  Nothing to read where the
program opens no ``train.batch`` span."""
from bench import scopes, trace


def read(r):
    t = r.trace
    prog = scopes.program(r)
    batch = trace.union([(s, e) for _, s, e in prog.named("train.batch")]) if prog else []
    if not r.steps or not t.ops or not batch:
        return None
    idle = []
    for ops in t.ops.values():
        gaps = trace.gaps(ops, t.window)
        idle.append(trace.length(gaps) - trace.length(trace.intersect(gaps, batch)))
    return max(idle) / r.steps / 1e6
