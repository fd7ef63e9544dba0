"""Device time per step of the operations the program scopes
``attention`` (ln1 through q/k/v, the attention itself, the out
projection and its residual add; forward, remat and backward), on the
busiest chip (layer: attention).  Nothing to read without the scope."""
from bench import scopes


def read(r):
    return scopes.scope_ms(r, "attention")
