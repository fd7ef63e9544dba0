"""Host time per step spent building the batch: the benchmark's span around
the program's ``TokenPipeline.batch_at``, which ``Trainer.train`` calls
before each step while the device waits (layer: host loop)."""


def read(r):
    if not r.steps or not r.host_input_s:
        return None
    return 1e3 * sum(r.host_input_s) / r.steps
