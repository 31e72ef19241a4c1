"""What can be said about ``chip_smoke.py`` without a chip: it refuses to
run off one, importing the package takes no device, and the compile cache
goes where the deployment says (PR 21)."""

import json
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_script, *, script=False):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = ([sys.executable, code_or_script] if script
           else [sys.executable, "-c", code_or_script])
    return subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=300)


def test_chip_smoke_fails_off_chip_before_building_a_model():
    proc = _run("chip_smoke.py", script=True)
    assert proc.returncode != 0, proc.stdout[-2000:]
    assert "needs a TPU" in proc.stderr
    lines = proc.stdout.strip().splitlines()
    # no phase ran, so no phase line and no result line was printed
    assert not any(l.startswith("{") and "phase" in json.loads(l)
                   for l in lines), lines
    assert not (lines and json.loads(lines[-1]).get("ok")), lines[-1]


def test_importing_the_package_initialises_no_backend():
    """A process that imports ``paddle_tpu`` (every launcher and lane
    parent does) must not take the chip: a chip belongs to one process."""
    proc = _run("import paddle_tpu, paddle_tpu.distributed.launch\n"
                "import jax._src.xla_bridge as xb\n"
                "assert not xb._backends, list(xb._backends)\n")
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_importing_the_package_imports_no_kernel():
    """``import paddle_tpu`` is part of every start (``setup_s``): the
    Pallas kernels, Pallas itself and the serving engine load when a
    program first needs them, not with the package."""
    proc = _run("import sys, paddle_tpu\n"
                "late = [m for m in sys.modules if m.startswith(("
                "'jax.experimental.pallas', 'jax._src.pallas', "
                "'paddle_tpu.ops.pallas', 'paddle_tpu.inference.serving'))]\n"
                "assert not late, late\n")
    assert proc.returncode == 0, proc.stderr[-2000:]


@pytest.mark.parametrize("from_env", [True, False],
                         ids=["env_set", "env_unset"])
def test_compile_cache_directory_rule(from_env, monkeypatch, tmp_path,
                                      compile_cache_restored):
    """``JAX_COMPILATION_CACHE_DIR`` set: that directory, and no other is
    ever configured. Unset: the one fixed path inside the checkout."""
    import paddle_tpu as paddle

    if from_env:
        want = str(tmp_path / "placed_from_outside")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    else:
        want = os.path.join(ROOT, ".jax_cache")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    configured = []
    real_update = jax.config.update

    def spy(name, value):
        if name == "jax_compilation_cache_dir":
            configured.append(value)
        return real_update(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    got = paddle.jit.enable_persistent_cache()
    assert got == want == paddle.jit.persistent_cache_dir()
    assert jax.config.jax_compilation_cache_dir == want
    assert configured and set(configured) == {want}
    assert os.path.isdir(want)
