"""runtime layer (``core/runtime.py`` ``_run_threads``: a threads-mode
worker parked with nothing runnable, the dynamic runtime's stall and
imbalance): self time of the library's ``blasx.wait`` spans over the
window times the bound chips (its ``chip_steps.<i>`` counters), %.
Silent where the library binds no chip."""
from bench import program


def read(rec):
    snap = program.snapshot()
    if snap is None:
        return None
    chips = sum(1 for k in snap["counters"] if k.startswith("chip_steps."))
    if chips == 0:
        return None
    wait = snap["spans"].get("blasx.wait", {"self_s": 0.0})["self_s"]
    return 100.0 * wait / (rec.window_s * chips)
