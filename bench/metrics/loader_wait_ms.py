"""Host time the training loop waits in ``next(loader)`` per step: the
benchmark's own span around the call, over the window's steps."""


def read(rec):
    steps = rec.get("steps")
    if not steps:
        return None
    return 1e3 * rec["spans_s"].get("loader.next", 0.0) / steps
