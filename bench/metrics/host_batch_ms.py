"""Host time per step in the program's ``train.batch`` spans, which
``Trainer.train`` opens around the data pipeline's ``batch_at`` (layer:
host loop).  Nothing to read where the program opens no such span."""
from bench import scopes


def read(r):
    prog = scopes.program(r)
    spans = prog.named("train.batch") if prog is not None else []
    if not r.steps or not spans:
        return None
    return sum(e - s for _, s, e in spans) / r.steps / 1e6
