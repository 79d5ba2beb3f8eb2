"""Requests completed in the window over the bucket slots dispatched, in %:
what the engine's bucket padding wastes."""


def read(view):
    r = view.records
    if not r.get("dispatched_slots"):
        return None
    return 100.0 * r["completed"] / r["dispatched_slots"]
