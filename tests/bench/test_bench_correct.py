"""What decides ``correct``, driven through a whole run at a small size on
the CPU (the harness's look for a chip skipped): sound runs pass, a run
whose timed path is broken underneath fails, and the lower-precision
control fails."""
import time

import numpy as np
import pytest

from bench import control, run
from bench.cell import load_peaks
from repro.backends.jax_backend import JaxBackend

CELLS = ["n8192.gemm", "n8192.chol_update", "n8192.gemm_fresh"]
PEAKS = load_peaks("TPU v5 lite")
SEED = 2 ** 31 + 99


def _run(cell, trace=False, tmp_path=None):
    return run.run_cell(cell, SEED, 1.0, trace, PEAKS, time.perf_counter(),
                        tmp_path)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(small_cell, name):
    res = _run(small_cell(name))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) >= {"setup_s"}


@pytest.mark.parametrize("name", ["n8192.chol_update", "n8192.gemm"])
def test_traced_run_reads_the_host_layers(small_cell, name, tmp_path):
    """On the CPU the trace has no device plane: the device metrics stay
    silent, the host spans and counters are read."""
    res = _run(small_cell(name), trace=True, tmp_path=tmp_path / "t")
    assert res["correct"]
    got = set(res["metrics"])
    assert not any(k.startswith(("device_idle", "matmul_roofline"))
                   for k in got)
    assert any(k.startswith("compiles_in_window") for k in got)
    assert any(k.startswith("backend_share") for k in got)
    assert res["device"]["window_s"] > 0


@pytest.fixture
def altered_answers(monkeypatch):
    """The first item of every step group comes back shifted by one: an
    answer altered where it is produced."""
    inner = JaxBackend.run_group

    def run_group(self, key, a_tiles, b_tiles):
        res = inner(self, key, a_tiles, b_tiles)
        res.products[0] = res.products[0] + np.float32(1.0)
        return res

    monkeypatch.setattr(JaxBackend, "run_group", run_group)


@pytest.mark.parametrize("name", CELLS)
def test_altered_answer_is_not_correct(small_cell, name, altered_answers):
    res = _run(small_cell(name))
    assert not res["correct"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("name", CELLS)
def test_control_in_lower_precision_is_not_correct(small_cell, name):
    cell = small_cell(name)
    got = control.readings(cell, SEED, 0.5, control.products(False))
    for key, limit in cell.limits.items():
        assert got["program"][key] <= limit
    ctrl = got["control"]["bf16x3"]
    assert any(ctrl[k] > limit for k, limit in cell.limits.items())
