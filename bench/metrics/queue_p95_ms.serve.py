"""queue_p95_ms.serve: the 95th percentile over the window's requests of
the time from a request's due time to its dispatch (the service's
``t_dispatch``); a request never dispatched counts as infinite."""

from yardstick import quantile


def read(run):
    q = run.window.get("queue_ms")
    if q is None or len(q) == 0:
        return None
    return quantile(q, 0.95)
