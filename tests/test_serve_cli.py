"""launch/serve CLI: the serving launcher's flags, in-process at tiny sizes.

Fast-lane coverage for ``repro.launch.serve.main`` — the 8-device forms run
via ``--fake-devices`` as a script; here local engines and a 1-device mesh
exercise the same dispatch routing.
"""

import pytest

from repro.launch import serve


@pytest.fixture(autouse=True)
def _placed_compile_cache(monkeypatch, tmp_path):
    """main() configures the compile cache; a placed directory keeps it
    from re-pointing this test process's JAX config."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))


def test_serve_local_with_static_comparison(capsys):
    serve.main([
        "--n", "256", "--requests", "5", "--slots", "2", "--rate", "500",
        "--max-iters", "300", "--compare-static",
    ])
    out = capsys.readouterr().out
    assert "serving 5 requests, n=256" in out
    assert "continuous:" in out and "signals/s" in out
    assert "recycled" in out
    assert "static baseline:" in out
    assert "continuous vs static:" in out


def test_serve_mesh_plan_with_deadlines(capsys):
    serve.main([
        "--n", "256", "--requests", "3", "--slots", "2", "--rate", "500",
        "--max-iters", "200", "--mesh", "1", "--rfft",
        "--deadline-slack", "60", "--priorities", "0", "1",
    ])
    out = capsys.readouterr().out
    assert "mesh=1 (plan API)" in out
    assert "expired 0" in out  # 60s slack: nothing expires at this size
    assert "buckets 1" in out
