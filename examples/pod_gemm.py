"""The paper's workload on a device mesh: out-of-core distributed GEMM
with the BLASX ring schedule (L2-cache/overlap insight on ICI).

Builds a 2-D mesh from the devices that exist and compares the ring
collective-matmul against the plain GSPMD lowering: same numerics,
collective-permute (neighbor) traffic instead of monolithic
all-gathers.  On a CPU host, ask XLA for several devices:

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/pod_gemm.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.api import BlasxContext
from repro.core import distributed as dist


def mesh_shape(n_devices: int):
    """(rows, cols) for the ("data", "model") mesh: two rows once there
    are at least four devices, one ring of all of them otherwise."""
    rows = 2 if n_devices >= 4 and n_devices % 2 == 0 else 1
    return rows, n_devices // rows


def main():
    mesh = jax.make_mesh(mesh_shape(len(jax.devices())), ("data", "model"))
    print(f"mesh {dict(mesh.shape)} on {jax.devices()[0].platform}")
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.standard_normal((512, 1024)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((1024, 768)), jnp.float32)
    # host-side oracle through the persistent-context API (the tiled
    # engine whose L2/overlap insight the ring schedule ports to ICI)
    with BlasxContext(tile=256) as ctx:
        want = np.array(ctx.gemm(np.asarray(A), np.asarray(B)).array(),
                        dtype=np.float32)

    for mode in ("gspmd", "ring"):
        f = jax.jit(lambda a, b, m=mode: dist.distributed_gemm(
            a, b, mesh, mode=m))
        compiled = f.lower(A, B).compile()
        out = compiled(A, B)
        err = np.abs(np.asarray(out) - want).max()
        txt = compiled.as_text()
        print(f"{mode:6s} max|err|={err:.2e} "
              f"all-gathers={txt.count('all-gather(')} "
              f"collective-permutes={txt.count('collective-permute')}")
    print("\nring mode: panels circulate the ICI ring (neighbor P2P, the "
          "paper's L2 tile cache) with the next hop issued before each "
          "matmul (the paper's stream overlap).")


if __name__ == "__main__":
    main()
