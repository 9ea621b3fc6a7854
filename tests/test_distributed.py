"""Distributed ring-GEMM tests.  jax locks the device count at first
init, so multi-device cases run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count set there."""
import os
import subprocess
import sys
import textwrap


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n_devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_devices} "
                        + env.get("XLA_FLAGS", "")).strip()
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=300)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    return proc.stdout


COMMON = """
import jax, numpy as np, jax.numpy as jnp
from repro.core import distributed as dist
mesh = jax.make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(0)
"""


def test_distributed_gemm_ring_matches_oracle():
    out = run_with_devices(COMMON + """
A = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32)
B = jnp.asarray(rng.standard_normal((128, 96)), jnp.float32)
want = np.asarray(A @ B)
for mode in ["ring", "gspmd"]:
    C = dist.distributed_gemm(A, B, mesh, mode=mode)
    err = np.abs(np.asarray(C) - want).max()
    assert err < 1e-3, (mode, err)
print("OK")
""")
    assert "OK" in out


def test_tp_matmul_column_row_roundtrip():
    out = run_with_devices(COMMON + """
x = jnp.asarray(rng.standard_normal((2, 32, 128)), jnp.float32)
w1 = jnp.asarray(rng.standard_normal((128, 256)), jnp.float32)
w2 = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
want = np.asarray(jnp.einsum('bsf,fd->bsd',
                  jnp.einsum('bsd,df->bsf', x, w1), w2))
for mode in ["ring", "gspmd"]:
    y = dist.tp_matmul(x, w1, mesh, kind="column", mode=mode)
    z = dist.tp_matmul(y, w2, mesh, kind="row", mode=mode)
    err = np.abs(np.asarray(z) - want).max()
    assert err < 5e-3, (mode, err)
print("OK")
""")
    assert "OK" in out


def test_ring_uses_collective_permute_not_allgather():
    """The BLASX overlap schedule must lower to neighbor ppermutes (the
    ICI 'P2P' path), not monolithic all-gathers."""
    out = run_with_devices(COMMON + """
A = jnp.zeros((64, 128), jnp.float32)
B = jnp.zeros((128, 96), jnp.float32)
ring = jax.jit(lambda a, b: dist.distributed_gemm(a, b, mesh, mode="ring"))
txt = ring.lower(A, B).compile().as_text()
n_perm = txt.count("collective-permute")
assert n_perm >= 2, f"expected ring ppermutes, found {n_perm}"
print("OK", n_perm)
""")
    assert "OK" in out


def test_ragged_shapes_pad_and_slice():
    """Regression: shapes not divisible by the ring size used to
    hard-error (``rows 3 not divisible by ring size 4``); the kernels
    now pad-and-slice internally, so real (ragged) serving shapes work
    at pod scale and still match the dense oracle."""
    out = run_with_devices(COMMON + """
# M=61 ragged vs the 2-wide row axis, K=99 ragged vs the 4-wide column
A = jnp.asarray(rng.standard_normal((61, 99)), jnp.float32)
B = jnp.asarray(rng.standard_normal((99, 96)), jnp.float32)
want = np.asarray(jnp.dot(A, B, preferred_element_type=jnp.float32))
for mode in ["ring", "gspmd"]:
    C = dist.distributed_gemm(A, B, mesh, mode=mode)
    assert C.shape == (61, 96), (mode, C.shape)
    err = np.abs(np.asarray(C) - want).max()
    assert err < 1e-3, (mode, err)
# seq=3 ragged vs the 4-wide ring (this exact shape used to raise)
x = jnp.asarray(rng.standard_normal((1, 3, 128)), jnp.float32)
w1 = jnp.asarray(rng.standard_normal((128, 256)), jnp.float32)
w2 = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
want = np.asarray(jnp.einsum('bsf,fd->bsd',
                  jnp.einsum('bsd,df->bsf', x, w1), w2))
for mode in ["ring", "gspmd"]:
    y = dist.tp_matmul(x, w1, mesh, kind="column", mode=mode,
                       batch_axis=None)
    assert y.shape == (1, 3, 256), (mode, y.shape)
    z = dist.tp_matmul(y, w2, mesh, kind="row", mode=mode,
                       batch_axis=None)
    assert z.shape == (1, 3, 128), (mode, z.shape)
    err = np.abs(np.asarray(z) - want).max()
    assert err < 5e-3, (mode, err)
print("OK")
""")
    assert "OK" in out


def test_ring_vs_gspmd_dtype_matrix():
    """Parity of every ring kernel against its gspmd twin across
    {f64, f32, bf16} on the forced-host 8-device mesh — the ring
    schedule may reorder the reduction but must stay within summation-
    order noise of the oracle, in every precision the library serves."""
    out = run_with_devices("""
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import distributed as dist
mesh = jax.make_mesh((2, 4), ("data", "model"))
ring = jax.make_mesh((8,), ("r",))
rng = np.random.default_rng(7)

TOL = {jnp.float64: 1e-5, jnp.float32: 1e-3, jnp.bfloat16: 1.0}
for dtype, tol in TOL.items():
    A = jnp.asarray(rng.standard_normal((64, 128)), dtype)
    B = jnp.asarray(rng.standard_normal((128, 96)), dtype)
    want = (np.asarray(A, np.float64) @ np.asarray(B, np.float64)
            ).astype(np.float32)
    # raw shard_map twins on the flat 8-ring
    ag = {}
    rs = {}
    for mode, (ag_fn, rs_fn) in dist.MODES.items():
        f = jax.shard_map(lambda a, b: ag_fn(a, b, "r"), mesh=ring,
                          in_specs=(P("r", None), P(None, "r")),
                          out_specs=P(None, "r"), check_vma=False)
        ag[mode] = np.asarray(f(A, B), np.float32)
        f = jax.shard_map(lambda a, b: rs_fn(a, b, "r"), mesh=ring,
                          in_specs=(P(None, "r"), P("r", None)),
                          out_specs=P("r", None), check_vma=False)
        rs[mode] = np.asarray(f(A, B), np.float32)
    for kind in (ag, rs):
        assert np.abs(kind["ring"] - want).max() < tol, (dtype, tol)
        assert np.abs(kind["ring"] - kind["gspmd"]).max() < tol, dtype
    # tp_matmul, both kinds, both modes (includes the padded-ragged
    # path: seq=30 is ragged vs the 4-wide model axis)
    x = jnp.asarray(rng.standard_normal((2, 30, 128)), dtype)
    w1 = jnp.asarray(rng.standard_normal((128, 256)), dtype)
    w2 = jnp.asarray(rng.standard_normal((256, 128)), dtype)
    x64 = np.asarray(x, np.float64)
    want = np.einsum('bsf,fd->bsd',
                     np.einsum('bsd,df->bsf', x64, np.asarray(w1, np.float64)),
                     np.asarray(w2, np.float64)).astype(np.float32)
    z = {}
    for mode in ["ring", "gspmd"]:
        y = dist.tp_matmul(x, w1, mesh, kind="column", mode=mode)
        z[mode] = np.asarray(
            dist.tp_matmul(y, w2, mesh, kind="row", mode=mode), np.float32)
        assert z[mode].shape == want.shape, (mode, z[mode].shape)
        assert np.abs(z[mode] - want).max() < 8 * tol, (dtype, mode)
    assert np.abs(z["ring"] - z["gspmd"]).max() < 8 * tol, dtype
    print("dtype ok", np.dtype(dtype).name)
print("OK")
""")
    assert "OK" in out
    for name in ("float64", "float32", "bfloat16"):
        assert f"dtype ok {name}" in out


def test_bf16_ring_numerics():
    out = run_with_devices(COMMON + """
A = jnp.asarray(rng.standard_normal((64, 128)), jnp.bfloat16)
B = jnp.asarray(rng.standard_normal((128, 96)), jnp.bfloat16)
C = dist.distributed_gemm(A, B, mesh, mode="ring")
want = np.asarray(jnp.dot(A.astype(jnp.float32), B.astype(jnp.float32)))
err = np.abs(np.asarray(C, np.float32) - want).max()
assert err < 1.0, err   # bf16 tolerance
print("OK")
""")
    assert "OK" in out
