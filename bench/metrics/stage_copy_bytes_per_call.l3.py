"""backends layer: bytes the library still copied or cast on the host to
send a group's tiles to the device (its ``stage_copy_bytes`` counter:
tiles that could not go as they are) per API call in the window; an
exact count.  Silent where the library keeps no such counter."""
from bench import program


def read(rec):
    snap = program.snapshot()
    if snap is None or "stage_copy_bytes" not in snap["counters"]:
        return None
    return program.per_call(rec, "stage_copy_bytes")
