"""``chip_smoke.py`` and the bring-up guards it relies on, on the CPU.

The smoke itself only runs on a TPU; here its phases run at toy sizes in
interpret mode (the same parity checks), and the script must refuse to run
without a chip or without the package next to it.
"""
import os
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402


def _run_smoke(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_smoke_refuses_without_tpu():
    proc = _run_smoke(ROOT, os.path.join(ROOT, "chip_smoke.py"))
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_smoke_refuses_without_the_package(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    proc = _run_smoke(str(tmp_path), str(tmp_path / "chip_smoke.py"))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_smoke_phases_at_toy_size():
    suite = dict(family="azure", n_instances=2, n_items=60, seed=2026)
    events, verdict = cs.phase_suite(
        "pallas_interpret", suite=suite, policies=("best_fit_linf", "rcp"),
        block_events=16)
    assert events > 0 and "host oracle" in verdict
    stream = dict(cs.STREAM, n_items=300, chunk_events=128, item_rows=64,
                  max_bins=32)
    events, verdict = cs.phase_stream("pallas_interpret", cfg=stream,
                                      block_events=32)
    assert events == 600 and "bit-identical" in verdict
    events, verdict = cs.phase_serve("pallas_interpret",
                                     cfg=dict(cs.SERVE, n=120))
    assert events == 240


def test_smoke_check_raises():
    with pytest.raises(cs.SmokeFailure, match="lost"):
        cs._check(False, "lost")


def test_quantized_instance_is_fp32_exact():
    import numpy as np

    from repro.data import make_azure_like_suite
    q = cs.quantized(make_azure_like_suite(1, 50, 3)[0])
    for a in (q.sizes * 64, q.arrivals / 64, q.departures / 64):
        assert np.array_equal(a, np.round(a))
    assert (q.departures > q.arrivals).all()


@pytest.fixture
def cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_compile_cache_fixed_checkout_path(cache_config, monkeypatch):
    from repro import compile_cache
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.enable()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable() == path        # stable across calls


def test_compile_cache_leaves_env_dir_alone(cache_config, monkeypatch,
                                            tmp_path):
    from repro import compile_cache
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None   # set by JAX itself


def test_hosts_refused_on_tpu(monkeypatch, tmp_path):
    from repro.sweep import __main__ as sweep_cli
    monkeypatch.setattr(sweep_cli, "_platform", lambda: "tpu")
    with pytest.raises(SystemExit, match="shard"):
        sweep_cli.main(["--hosts", "2", "--store", str(tmp_path)])


@pytest.mark.parametrize("impl,interpret", [
    ("pallas", False), ("pallas_interpret", True),
    ("auto", jax.default_backend() != "tpu")])
def test_blocked_kernel_interprets_only_when_asked(impl, interpret):
    from repro.kernels import ops
    assert ops._interpret(impl) is interpret
    assert ops.resolved_select_impl(impl, block=True) == \
        ("pallas_interpret" if interpret else "pallas")


def test_blocked_kernel_has_no_jnp_twin():
    from repro.kernels import ops
    with pytest.raises(ValueError, match="no jnp twin"):
        ops._interpret("jnp")


def test_native_dispatch_ladder_never_interprets():
    from repro.serving.dispatch import BlockDispatcher
    disp = BlockDispatcher("best_fit_linf", max_bins=8, max_items=16,
                           impl="pallas")
    assert [impl for _, impl in disp._rungs()] == ["pallas", "pallas"]
