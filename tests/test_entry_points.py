"""The entry points' contracts: the chip smoke refuses to run off a TPU, the
compile cache is placed only from an entry point, and a serve job that does
not finish makes the launcher exit non-zero."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _cpu_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"), **extra)
    return env


def test_chip_smoke_fails_without_a_tpu():
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         cwd=ROOT, env=_cpu_env(), capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_importing_repro_places_no_compile_cache():
    code = ("import jax, repro.launch.train, repro.launch.serve, "
            "repro.runtime.train_loop, repro.runtime.serve_loop, "
            "repro.pipelines; print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         env=_cpu_env(), capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "None"


def test_compile_cache_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_compile_cache_default_dir_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()


def test_serve_driver_exits_nonzero_when_job_not_done(monkeypatch):
    from repro.core.plane import ManagementPlane
    from repro.launch import serve
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(ManagementPlane, "run_until_done",
                        lambda self, ids, max_ticks=200: False)
    monkeypatch.setattr(sys, "argv", ["serve", "--driver", "--requests", "1"])
    with pytest.raises(SystemExit) as e:
        serve.main()
    assert e.value.code not in (0, None)
