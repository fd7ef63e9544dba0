"""Share of the traced window in which a chip runs no operation, on the
idlest chip (layer: device)."""
from bench import trace


def read(r):
    if not r.trace.ops or r.trace.window_ns <= 0:
        return None
    busy = min(trace.busy_ns(ops) for ops in r.trace.ops.values())
    return 100.0 * (1.0 - busy / r.trace.window_ns)
