"""Mean wall time of the engine ticks that answered from the full-graph
cache without refreshing it (the session's invalidation counter did not
move), from the benchmark's own tick spans."""


def read(run):
    t = run.tick_ms(refreshed=False)
    return sum(t) / len(t) if t else None
