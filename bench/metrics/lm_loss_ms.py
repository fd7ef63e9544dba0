"""Device time per step of the operations the program scopes ``lm_loss``
(the LM-head matmul, logsumexp and gold gather over the loss chunks;
forward, remat and backward), on the busiest chip (layer: loss / LM
head).  Nothing to read without the scope."""
from bench import scopes


def read(r):
    return scopes.scope_ms(r, "lm_loss")
