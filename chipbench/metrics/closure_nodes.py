"""Mean real (unpadded) 2-hop closure nodes per served batch, extracted
again after the window from the batches the engine served. A count: it
repeats exactly for a seed."""


def read(run):
    c = run.closures
    return sum(nodes for nodes, _ in c) / len(c) if c else None
