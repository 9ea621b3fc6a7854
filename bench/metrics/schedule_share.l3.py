"""runtime layer (``api/context.py``, ``core/runtime.py``, ``core/task.py``:
argument checks, taskization, the scheduling loop, grouping steps,
backend selection, the sim time model): share (%) of the window in the
self time of the library's ``blasx.call``, ``blasx.plan``, ``blasx.run``,
``blasx.dispatch``, ``blasx.group`` and ``blasx.model`` spans."""
from bench import program


def read(rec):
    return program.self_share(rec, "blasx.call", "blasx.plan", "blasx.run",
                              "blasx.dispatch", "blasx.group",
                              "blasx.model")
