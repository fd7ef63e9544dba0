"""Device time per step: the union of a chip's operations in the traced
window over the steps completed in it, on the busiest chip (layer: model
step, ``make_dfabric_train_step``)."""
from bench import trace


def read(r):
    if not r.steps or not r.trace.ops:
        return None
    return max(trace.busy_ns(ops) for ops in r.trace.ops.values()) / r.steps / 1e6
