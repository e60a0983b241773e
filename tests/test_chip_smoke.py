"""chip_smoke.py on the CPU.

The script's phases run here at ``--reduced`` size with the Pallas kernels
interpreted (the same code the chip runs at full width, Mosaic-compiled);
the script itself refuses a CPU backend and a directory that is not a
checkout; and its four-chip comparison runs on four virtual CPU devices.
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")
# the CPU-sized cut of the smoke configuration (widths and depth reduced,
# the sequence and batch shortened); the phases' own flags stay as they are
SIZE = ("--reduced", "--seq-len", "64", "--batch-per-worker", "2")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load()


@pytest.mark.parametrize("phase", ["a_muon", "b_adamw", "c_all_kernels"])
def test_train_phase_runs_reduced_on_cpu(smoke, phase, tmp_path, capsys):
    rec = smoke.train_phase(phase, smoke.PHASES[phase], str(tmp_path), SIZE)
    assert len(rec["train_loss"]) == len(rec["eval_loss"]) == 2
    assert rec["compile_s"] > 0
    # the phase line is printed before any check can fail
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('{"phase"')]
    assert json.loads(lines[-1])["phase"] == phase
    if phase == "c_all_kernels":
        assert set(rec["kernel_err"]) == {
            "flash_fwd", "flash_dq", "flash_dk", "flash_dv", "quantize",
            "dequantize", "ns_matmul", "outer_update", "ssd_fwd", "ssd_dx",
            "ssd_ddt", "ssd_dB", "ssd_dC", "ssd_ddA"}
        assert max(v for k, v in rec["kernel_err"].items()
                   if k.startswith("ssd_")) <= smoke.KERNEL_TOL


def test_serve_phase_runs_reduced_on_cpu(smoke):
    rec = smoke.serve_phase(("--reduced",))
    assert rec["requests"] == 4 and rec["tokens"] == 4 * 32
    assert rec["kernel_err"]["paged_decode"] <= smoke.KERNEL_TOL


def _run_script(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_cpu_backend():
    res = _run_script(REPO)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "no TPU" in res.stderr


def test_fails_outside_a_checkout(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    res = _run_script(str(tmp_path))
    assert res.returncode != 0
    assert '"ok"' not in res.stdout


CACHE_CHILD = r"""
import jax
from repro.launch.compile_cache import use_compilation_cache
use_compilation_cache()
print(jax.config.jax_compilation_cache_dir)
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins when set; otherwise the cache is the
    fixed, gitignored .jax_cache at the root of the checkout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    res = subprocess.run([sys.executable, "-c", CACHE_CHILD], cwd=tmp_path,
                         env=env, capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr[-3000:]
    want = str(tmp_path) if from_env else os.path.join(REPO, ".jax_cache")
    assert res.stdout.strip().splitlines()[-1] == want
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().splitlines()


FOUR_CHIP_CHILD = r"""
import importlib.util, json, sys
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
rec = smoke.four_chip_compare(sys.argv[2], tuple(sys.argv[3:]))
print(json.dumps({"rel_diff": rec["rel_diff"], "tol": rec["tol"]}))
"""


def test_four_chip_comparison_on_virtual_devices(tmp_path):
    """K=4 workers one per device (--mesh 4x1x1) against K=4 vmapped on
    one device, on four virtual CPU devices; the comparison itself asserts
    the placement and the loss agreement."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    res = subprocess.run(
        [sys.executable, "-c", FOUR_CHIP_CHILD, SCRIPT, str(tmp_path), *SIZE],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    for diffs in out["rel_diff"].values():
        assert len(diffs) == 2 and max(diffs) <= out["tol"]
