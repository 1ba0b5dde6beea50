"""Rehearse ``chip_smoke.py`` on the CPU at a tiny scale.

Each phase function runs here as it would on the chip, only smaller; the
four-device phase runs in a CPU subprocess with four virtual devices.
``main()`` itself must refuse the CPU: exit nonzero and print no ``ok``
line, also from a directory that holds nothing but the script.
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fields(out: str, phase: str) -> str:
    return "\n".join(l for l in out.splitlines() if l.startswith(phase + " "))


def test_whole_graph_phase(smoke, capsys):
    assert smoke.whole_graph(scale=0.1)
    out = _fields(capsys.readouterr().out, "whole_graph")
    assert "trace_count=1" in out and "use_kernels=False" in out
    assert "max_rel_err=" in out and "compile_s=" in out


def test_whole_graph_phase_fails_outside_tolerance(smoke, capsys):
    """A zero tolerance cannot be met by float32 arithmetic in a different
    order, so the phase reports failure instead of passing silently."""
    assert not smoke.whole_graph(scale=0.1, tol=0.0)
    capsys.readouterr()


def test_serving_phase(smoke, capsys):
    assert smoke.serving(n_requests=4, f_in=32, slots=2, sizes=(20, 40))
    out = _fields(capsys.readouterr().out, "serving")
    assert "answered=4" in out and "p50_wave_wall_s=" in out
    assert "deadline_hits=" in out and "bitwise_eq_naive=True" in out


def test_sharded_phase_on_four_cpu_devices():
    code = textwrap.dedent(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location("cs", {SCRIPT!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        assert cs.sharded(n_devices=4, n_requests=6, f_in=32, slots=4,
                          sizes=(20, 40))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    sharded = _fields(out.stdout, "sharded")
    assert "wave_device_sets=[[0, 1, 2, 3]" in sharded
    assert "bitwise_eq_unsharded=True" in sharded
    assert "bitwise_eq_unsharded=True" in _fields(out.stdout, "resized")


def _no_ok_line(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if json.loads(line).get("ok"):
                return False
        except (ValueError, AttributeError):
            pass
    return True


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_cpu(smoke, capsys, argv):
    assert smoke.main(argv) != 0
    assert _no_ok_line(capsys.readouterr().out)


def test_script_alone_fails(tmp_path):
    """Without the rest of the repo the script cannot run, and says so by
    its exit code rather than an ``ok`` line."""
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode != 0
    assert _no_ok_line(out.stdout)


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
def test_compile_cache_path(monkeypatch, env_dir):
    """``$JAX_COMPILATION_CACHE_DIR`` wins and nothing is set in code;
    otherwise the cache sits at the fixed ``<checkout>/.jax_cache``."""
    import jax
    from repro import jax_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv(jax_cache.ENV_VAR, raising=False)
    else:
        monkeypatch.setenv(jax_cache.ENV_VAR, env_dir)
    try:
        path = jax_cache.configure_compile_cache()
        if env_dir is None:
            assert path == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
        else:
            assert path == env_dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert jax_cache.cache_entries(os.path.join(REPO, "no-such-dir")) == 0
