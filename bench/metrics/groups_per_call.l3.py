"""backends layer: step groups the runtime handed its backend per API
call in the window (``launch_stats()["groups"]`` delta; an exact count)."""


def read(rec):
    if rec.spans is None or rec.spans.calls == 0:
        return None
    return rec.groups / rec.spans.calls
