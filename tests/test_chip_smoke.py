"""What the chip entry points promise without a chip.

``chip_smoke.py`` needs a TPU; here only its failure contract is checked: on
the CPU, and as a lone file with none of the repository beside it, it exits
non-zero and prints nothing on stdout (no JSON result line). The compile
cache the entry points share is placed from outside when the environment
says so, and at a fixed path in the checkout otherwise.
"""

import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(script, cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, script], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_smoke_fails_without_a_tpu(tmp_path):
    out = _run(SMOKE, tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU" in out.stderr


def test_smoke_fails_alone(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SMOKE, lone)
    out = _run(str(lone), tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""


def test_compile_cache_placement(monkeypatch):
    import jax

    from repro.launch import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.use_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert updates == [("jax_compilation_cache_dir", path)]

    updates.clear()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.use_compile_cache() == "/elsewhere"
    assert updates == []  # JAX reads the variable itself
