"""batch_reqs.serve: requests per dispatched batch in the window, from the
service's ``completed`` and ``batches`` counters (one row per request)."""


def read(run):
    w = run.window
    if not w.get("batches"):
        return None
    return w["completed"] / w["batches"]
