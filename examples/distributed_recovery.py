"""End-to-end distributed recovery through the execution-plan layer: one
large signal sharded over the model axis via the four-step FFT, driven by
the *same* solver drivers as a single-device run, with checkpoint/restart.

    PYTHONPATH=src python examples/distributed_recovery.py [--devices 8]
        [--method cpadmm|ista|fista] [--overlap K] [--tail jnp|pallas]

This is the paper's workload as a *cluster job*: the same launcher logic
runs on a 256-chip pod by swapping the mesh (launch/mesh.py).  The example
forces N fake host devices, lowers the sensing operator onto them with
``repro.ops.plan``, recovers a 64k-sample signal with
``solve_checkpointed`` (any ``--method`` — distributed CPISTA/FISTA ride
the same plan), kills itself halfway (simulated preemption), and restarts
from the checkpoint — identical result to an uninterrupted run.
"""

import argparse
import os

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--n1", type=int, default=256)
    ap.add_argument("--n2", type=int, default=256)
    ap.add_argument("--method", default="cpadmm",
                    choices=("cpadmm", "ista", "fista"),
                    help="every method runs distributed through the plan")
    ap.add_argument("--rfft", action="store_true",
                    help="half-spectrum transforms (half the wire bytes)")
    ap.add_argument("--overlap", type=int, default=1,
                    help="chunked-transpose overlap factor K (1 = monolithic)")
    ap.add_argument("--tail", default="jnp", choices=("jnp", "pallas"),
                    help="elementwise iteration tail: XLA-fused jnp ops or "
                         "the fused cpadmm_tail Pallas kernel")
    args = ap.parse_args()
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={args.devices}"
    ).strip()

import jax  # noqa: E402  (after XLA_FLAGS)
import jax.numpy as jnp  # noqa: E402

from repro.ckpt import checkpoint as ckpt  # noqa: E402
from repro.core import RecoveryProblem, solve_checkpointed  # noqa: E402
from repro.core.circulant import PartialCirculant, gaussian_circulant  # noqa: E402
from repro.data.synthetic import paper_regime, sparse_signal  # noqa: E402
from repro.dist.compat import make_mesh  # noqa: E402
from repro.ops import plan  # noqa: E402


def main():
    n1, n2 = args.n1, args.n2
    n = n1 * n2
    mesh = make_mesh((args.devices,), ("model",))
    m, k = paper_regime(n)
    print(f"n={n} over {args.devices} devices; m={m}, k={k}, "
          f"method={args.method}")

    x_true = sparse_signal(jax.random.PRNGKey(0), n, k)
    C = gaussian_circulant(jax.random.PRNGKey(1), n, normalize=True)
    omega = jnp.sort(jax.random.permutation(jax.random.PRNGKey(2), n)[:m])
    op = PartialCirculant(C, omega.astype(jnp.int32))
    prob = RecoveryProblem(op=op, y=op.matvec(x_true), x_true=x_true)

    # one call lowers the operator onto the mesh; the drivers are unchanged
    pl = plan(op, mesh, n1=n1, n2=n2, rfft=args.rfft,
              overlap=args.overlap, tail=args.tail)
    kw = dict(alpha=1e-4, rho=0.01, sigma=0.01, plan=pl, chunk=50)
    ckdir = "artifacts/dist_recovery_ckpt"
    import shutil

    shutil.rmtree(ckdir, ignore_errors=True)  # stale steps would win "latest"

    def report(step, state):
        ckpt.save(ckdir, step, jax.device_get(state))

    # --- run the first 100 iterations, checkpointing every chunk
    solve_checkpointed(prob, args.method, iters=100, save_cb=report, **kw)
    print("  -- simulated preemption after iter 100: restarting --")

    # --- restart from the latest checkpoint and run to 200
    from repro.core.solvers import make_stepper

    shape = jax.eval_shape(make_stepper(prob, args.method, **{
        k_: v for k_, v in kw.items() if k_ != "chunk"}).init)
    step_no, state = ckpt.restore(ckdir, None, shape)
    assert step_no == 100, step_no
    x_hat, mse = solve_checkpointed(
        prob, args.method, iters=200, save_cb=report,
        restore=(step_no, state), **kw,
    )

    # --- uninterrupted reference run: the restart must be bit-identical
    x_ref, _ = solve_checkpointed(prob, args.method, iters=200, **kw)
    identical = bool((x_hat == x_ref).all())
    print(f"restart-vs-uninterrupted bit-identical: {identical}")
    assert identical

    final = float(jnp.mean(mse))
    print(f"final MSE {final:.2e}  ({'OK' if final < 1e-4 else 'needs more iters'})")


if __name__ == "__main__":
    main()
