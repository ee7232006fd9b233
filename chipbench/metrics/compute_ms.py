"""Forward time per served batch (launch to answers on the host): the
engine's ``compute_s`` over its ``batches``, both taken over the window."""


def read(run):
    return run.per_batch_ms("compute_s")
