"""Host extract time per served batch (2-hop closure, subgraph FRDC,
bucket pad): the engine's ``extract_s`` over its ``batches``, both taken
over the window."""


def read(run):
    return run.per_batch_ms("extract_s")
