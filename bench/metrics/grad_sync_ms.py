"""Device time per step of the operations the program scopes
``grad_sync`` (packing and unpacking, the collective legs, scaling, the
norm psums): its leaf operations and its collectives in flight on the
async line, total time whether or not other work overlaps it, on the
busiest chip (layer: grad sync).  Nothing to read without the scope."""
from bench import scopes


def read(r):
    return scopes.scope_ms(r, "grad_sync", with_async=True)
