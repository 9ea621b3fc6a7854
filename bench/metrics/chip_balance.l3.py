"""runtime layer (``core/runtime.py``: how the dynamic scheduler spread a
call's work over the chips its devices are bound to): 100 x the fewest
k-steps any bound chip ran over the most, from the library's
``chip_steps.<i>`` counters, each registered at 0 when a run starts, so
a chip that ran nothing reads 0.  Silent where the library binds no
chip (one runtime device, or a library without the counters)."""
from bench import program


def read(rec):
    snap = program.snapshot()
    if snap is None:
        return None
    steps = [v for k, v in snap["counters"].items()
             if k.startswith("chip_steps.")]
    if not steps or max(steps) == 0:
        return None
    return 100.0 * min(steps) / max(steps)
