"""``chip_smoke.py`` on the CPU: its phase functions at smoke size (the
control flow and every check, minus the TPU-only kernel check), and its
refusal to run without a TPU or outside a checkout."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg():
    return get_config("yi-6b", smoke=True)


def test_train_phase(smoke, cfg):
    out = smoke.train_phase(cfg, seq=32, batch=2, steps=5,
                            expect_kernels=False)
    assert len(out["losses"]) == 5
    assert out["exchange_bytes"] > 0


def test_serve_phase(smoke, cfg):
    out = smoke.serve_phase(cfg, requests=3, slots=2, prompt_lens=(8, 24),
                            max_new=4, expect_kernels=False)
    assert out["tokens"] == [4, 4, 4]


def test_kernel_check_fails_when_a_kernel_is_missing(smoke):
    compiled = jax.jit(lambda x: x * 2).lower(jnp.ones(8)).compile()
    assert smoke.pallas_kernels(compiled) == set()
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_kernels(compiled, ("dequant_matmul",), "probe")


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def _has_result(stdout):
    for line in stdout.splitlines():
        try:
            if "ok" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


@pytest.mark.parametrize("args", [(), ("--chips", "4")])
def test_exits_nonzero_without_a_tpu(args):
    r = _run(ROOT, *args)
    assert r.returncode != 0
    assert not _has_result(r.stdout)
    assert "no TPU" in r.stderr


def test_exits_nonzero_outside_a_checkout(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    r = _run(str(tmp_path))
    assert r.returncode != 0
    assert not _has_result(r.stdout)


def test_four_worker_phase_on_four_cpu_devices(cfg):
    """The ``--chips 4`` phase on four simulated CPU devices."""
    code = (
        "import sys; sys.path[:0] = [%r, %r]\n"
        "import chip_smoke\n"
        "from repro.configs import get_config\n"
        "out = chip_smoke.four_worker_phase("
        "get_config('yi-6b', smoke=True), seq=32)\n"
        "print(out['master_max_abs_diff'])\n"
    ) % (ROOT, os.path.join(ROOT, "src"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    assert "ok: masters agree" in r.stdout
