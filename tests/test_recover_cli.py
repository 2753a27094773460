"""launch/recover CLI: method/mesh flags routed through the plan API.

In-process invocations of ``repro.launch.recover.main`` at tiny sizes — the
fast-lane coverage for the production launcher (the 8-device forms run via
``--fake-devices`` as a script; here the 1-device mesh exercises the same
plan routing).
"""

import jax
import pytest

from repro.launch import recover


@pytest.fixture(autouse=True)
def _placed_compile_cache(monkeypatch, tmp_path):
    """main() configures the compile cache; a placed directory keeps it
    from re-pointing this test process's JAX config."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))


def test_tol_mode_with_mesh_plan(capsys):
    recover.main([
        "--n", "512", "--batch", "2", "--method", "fista", "--iters", "80",
        "--tol", "1e-3", "--mesh", "1", "--rfft",
    ])
    out = capsys.readouterr().out
    assert "mesh=1 (plan API)" in out
    assert "per-signal iterations" in out
    assert "per-signal MSE" in out


def test_checkpointed_mode_resumes(tmp_path, capsys):
    args = [
        "--n", "512", "--batch", "2", "--method", "cpadmm", "--iters", "60",
        "--chunk", "30", "--mesh", "1", "--ckpt-dir", str(tmp_path / "ck"),
    ]
    recover.main(args)
    first = capsys.readouterr().out
    assert "per-signal MSE" in first and "resumed" not in first
    recover.main(args)  # latest checkpoint (iter 60) is picked up
    assert "resumed from iteration 60" in capsys.readouterr().out


def test_local_backend_default(capsys):
    recover.main([
        "--n", "512", "--batch", "1", "--method", "ista", "--iters", "40",
        "--tol", "1e-2",
    ])
    out = capsys.readouterr().out
    assert "plan API" not in out and "per-signal iterations" in out


def test_deblur_workload_checkpointed_with_mesh_plan(tmp_path, capsys):
    """--deblur routes through build_deblur_plan on a (data, model) mesh and
    reports per-frame PSNR after the checkpointed solve."""
    recover.main([
        "--deblur", "--batch", "2", "--size", "16", "--blur-order", "3",
        "--iters", "40", "--chunk", "20", "--mesh", "1x1", "--rfft",
        "--ckpt-dir", str(tmp_path / "ck"),
    ])
    out = capsys.readouterr().out
    assert "deblurring batch=2 frames of 16x16" in out
    assert "mesh=1x1 (plan API)" in out
    assert "PSNR" in out and "normalized MSE" in out


def test_deblur_workload_tol_mode_local(capsys):
    recover.main([
        "--deblur", "--batch", "1", "--size", "16", "--iters", "40",
        "--tol", "1e-2",
    ])
    out = capsys.readouterr().out
    assert "per-signal iterations" in out and "PSNR" in out


def test_deblur_tv_prior_with_mesh_plan(capsys):
    """--prior tv builds a TVProx on the frame grid and threads it through
    build_deblur_plan onto the mesh path."""
    recover.main([
        "--deblur", "--batch", "2", "--size", "16", "--blur-kind", "gaussian",
        "--blur-order", "1.0", "--prior", "tv", "--iters", "40",
        "--chunk", "20", "--mesh", "1x1",
    ])
    out = capsys.readouterr().out
    assert "prior=tv" in out and "PSNR" in out


def test_prior_flag_local_sparse_recovery(capsys):
    for prior in ("nonneg-l1", "wavelet"):
        recover.main([
            "--n", "256", "--batch", "1", "--method", "ista", "--iters", "40",
            "--tol", "1e-2", "--prior", prior,
        ])
        out = capsys.readouterr().out
        assert f"prior={prior}" in out and "per-signal" in out


def test_make_prior():
    from repro.ops.prox import NonNegL1Prox, TVProx, WaveletProx

    assert recover.make_prior("l1", 256) is None
    assert isinstance(recover.make_prior("nonneg-l1", 256), NonNegL1Prox)
    assert isinstance(recover.make_prior("wavelet", 256), WaveletProx)
    assert recover.make_prior("tv", 256) == TVProx(shape=(16, 16))
    assert recover.make_prior("tv", 0, size=8) == TVProx(shape=(8, 8))
    with pytest.raises(SystemExit, match="square"):
        recover.make_prior("tv", 200)


def test_method_error_lists_valid_methods(capsys):
    with pytest.raises(SystemExit):
        recover.main(["--method", "newton", "--n", "512"])
    err = capsys.readouterr().err
    assert "cpadmm" in err and "ista" in err and "fista" in err


def test_bad_mesh_spec_rejected():
    op = None  # build_plan validates the spec before touching the operator
    with pytest.raises(ValueError, match="--mesh"):
        recover.build_plan(op, "2x2x2")


def test_build_plan_shapes():
    from repro.core import partial_gaussian_circulant
    from repro.ops import ExecutionPlan

    op = partial_gaussian_circulant(jax.random.PRNGKey(0), 512, 256)
    pl = recover.build_plan(op, None)
    assert isinstance(pl, ExecutionPlan) and not pl.is_distributed
    pl = recover.build_plan(op, "1", rfft=True)
    assert pl.is_distributed and pl.rfft and pl.batch_axis is None
    pl = recover.build_plan(op, "1x1")
    assert pl.is_distributed and pl.batch_axis == "data"
