"""Mean wall time of the ticks that raised the session's invalidation
counter: feature upload, calibrated full-graph forward and logits
copy-back (``CompiledGraphSession.sync``), plus that tick's lookup."""


def read(run):
    t = run.tick_ms(refreshed=True)
    return sum(t) / len(t) if t else None
