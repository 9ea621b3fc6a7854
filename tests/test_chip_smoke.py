"""CPU rehearsal of ``chip_smoke.py``: its phases at tiny sizes (Pallas
in interpret mode), its refusal to run without a TPU, and its four-chip
phase on a 2x2 mesh of forced host devices."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

import chip_smoke
from repro.api import BlasxContext
from repro.core.runtime import RuntimeConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, TILE = 256, 128


def _pallas_config():
    return RuntimeConfig(n_devices=1, mode="sim", backend="pallas")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gemm_phase_rehearsal(dtype):
    with BlasxContext(_pallas_config(), tile=TILE) as ctx:
        out = chip_smoke.phase_gemm(ctx, N, dtype, seed=0)
    assert out["err"] <= chip_smoke.TOL[dtype]
    # every tile is full, so every flop of both calls ran on the kernel
    assert out["launch"]["engine_flops"] == {"pallas": 2 * 2 * N ** 3}


def test_chain_phase_rehearsal():
    with BlasxContext(_pallas_config(), tile=TILE) as ctx:
        out = chip_smoke.phase_chain(ctx, N, seed=0)
    assert out["err"] <= chip_smoke.TOL["float32"]
    # the triangular solve's update groups take the jax backend
    assert out["launch"]["engine_flops"].get("jax", 0) > 0


def test_serve_phase_rehearsal():
    out = chip_smoke.phase_serve(_pallas_config(), TILE, N, seed=0)
    assert out["requests"] == chip_smoke.SERVE_REQUESTS
    assert out["err"] <= chip_smoke.TOL["float32"]
    # both lanes served requests
    assert all(lane["engine_flops"].get("pallas", 0) > 0
               for lane in out["launch"])


def test_main_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok": true' not in out.out
    assert "no TPU" in out.err


def test_help_exits_before_jax_loads():
    code = textwrap.dedent("""
        import sys, chip_smoke
        try:
            chip_smoke.main(["--help"])
        except SystemExit as e:
            print("exit", e.code, "jax loaded:", "jax" in sys.modules)
    """)
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "--four-chips" in proc.stdout
    assert "exit 0 jax loaded: False" in proc.stdout


def test_four_chip_phase_on_a_cpu_mesh():
    """The --four-chips control flow on a 2x2 mesh of host devices (jax
    fixes the device count at start-up, hence the subprocess)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + env.get("XLA_FLAGS", "")).strip()
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(REPO, "src"), REPO])
    code = textwrap.dedent("""
        import jax, chip_smoke
        chip_smoke.phase_four_chips(jax.devices(), 256, seed=0)
    """)
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr}"
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("phase ")]
    result = json.loads(line[-1][len("phase "):])
    for mode in ("ring", "gspmd"):
        assert result[mode]["devices"] == 4
        assert result[mode]["err"] <= chip_smoke.TOL["bfloat16"]
    assert result["ring"]["collective_permutes"] > 0
