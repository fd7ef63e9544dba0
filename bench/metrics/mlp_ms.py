"""Device time per step of the operations the program scopes ``mlp``
(ln2 through the feed-forward and its residual add; forward, remat and
backward), on the busiest chip (layer: MLP).  Nothing to read without the
scope."""
from bench import scopes


def read(r):
    return scopes.scope_ms(r, "mlp")
