"""Device time per step of the operations the program scopes ``adamw``
(each leaf's AdamW update and the gradient clip), on the busiest chip
(layer: optimizer).  Nothing to read without the scope."""
from bench import scopes


def read(r):
    return scopes.scope_ms(r, "adamw")
