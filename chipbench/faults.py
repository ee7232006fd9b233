"""Faults planted in the timed path, to show that the comparison which
decides ``correct`` catches them. Each is a ``patch(engine, session,
store)`` for :func:`chipbench.run.run_cell`, applied after warm-up; none
is used by a benchmark run.
"""
from __future__ import annotations

import numpy as np


def one_answer_altered(engine, session, store) -> None:
    """The first answer of the window comes back negated."""
    deliver = engine._deliver
    state = {"done": False}

    def altered(inf, result):
        deliver(inf, result)
        if not state["done"]:
            q = inf.batch[0]
            q.logits = -np.asarray(q.logits)
            q.pred = int(np.argmax(q.logits))
            state["done"] = True

    engine._deliver = altered


def answers_shifted(engine, session, store) -> None:
    """Every batch hands each query the answer of the query before it."""
    deliver = engine._deliver

    def shifted(inf, result):
        deliver(inf, np.roll(np.asarray(result), 1, axis=0))

    engine._deliver = shifted


def half_batch_dropped(engine, session, store) -> None:
    """Only the first half of each batch gets its answer."""
    deliver = engine._deliver

    def half(inf, result):
        keep = max(len(inf.batch) // 2, 1)
        full = inf.batch
        inf.batch = full[:keep]
        try:
            deliver(inf, np.asarray(result)[:keep])
        finally:
            inf.batch = full

    engine._deliver = half


def refresh_skipped(engine, session, store) -> None:
    """A feature update moves the session's version, but the cached logits
    stay as they were: the refresh returns its state unchanged."""
    def sync():
        if session.feature_version != session.graph.version:
            session.feature_version = session.graph.version
            session._invalidations += 1

    session.sync = sync


FAULTS = {f.__name__: f for f in (one_answer_altered, answers_shifted,
                                  half_batch_dropped, refresh_skipped)}
