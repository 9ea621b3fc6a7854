"""runtime layer (``core/runtime.py``, ``core/task.py``): share (%) of the
window the host spent inside API calls but outside the backend's
``run_group`` (planning, gather, epilogue, write-back).  Host clock, from
the benchmark's spans."""


def read(rec):
    if rec.spans is None or rec.spans.calls == 0:
        return None
    return 100.0 * (rec.spans.call_s - rec.spans.group_s) / rec.window_s
