"""Device time of one execution of the forward program the run serves
from, mean over the executions in the traced window."""


def read(run):
    t = run.program_seconds(run.forward_pattern)
    return 1e3 * sum(t) / len(t) if t else None
