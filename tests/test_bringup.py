"""What the chip bring-up (PR 21) made load-bearing, checked on the CPU:
where the compile cache goes, that nothing hides a missing TPU, and that
the entry scripts refuse to run without one."""

import os
import subprocess
import sys
import types

import pytest

from distributed_groth16_tpu.ops import limb_kernels as lk
from distributed_groth16_tpu.utils import cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _FakeJax(types.SimpleNamespace):
    """Stands in for the jax module: records every config.update."""

    def __init__(self):
        self.updates = {}
        super().__init__(config=types.SimpleNamespace(
            update=lambda name, value: self.updates.__setitem__(name, value)
        ))


def test_cache_dir_left_to_jax_when_placed_from_outside(monkeypatch):
    monkeypatch.delenv("DG16_NO_JAX_CACHE", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    fake = _FakeJax()
    assert cache.setup_compile_cache(fake) == "/some/dir"
    # jax reads the variable itself: the package sets NO directory
    assert "jax_compilation_cache_dir" not in fake.updates
    assert "jax_persistent_cache_min_compile_time_secs" in fake.updates


def test_cache_dir_is_fixed_when_not_placed(monkeypatch):
    monkeypatch.delenv("DG16_NO_JAX_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    seen = []
    for flags in ("", "--xla_force_host_platform_device_count=8"):
        monkeypatch.setenv("XLA_FLAGS", flags)
        fake = _FakeJax()
        seen.append(cache.setup_compile_cache(fake))
        assert fake.updates["jax_compilation_cache_dir"] == seen[-1]
    # no machine tag, version prefix, pid or time: one path per checkout
    assert seen == [os.path.join(ROOT, ".jax_cache")] * 2


def test_no_jax_cache_still_disables(monkeypatch):
    monkeypatch.setenv("DG16_NO_JAX_CACHE", "1")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    fake = _FakeJax()
    assert cache.setup_compile_cache(fake) == ""
    assert fake.updates == {
        "jax_enable_compilation_cache": False,
        "jax_compilation_cache_dir": None,
    }


def test_use_pallas_propagates_backend_error(monkeypatch):
    import jax

    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", boom)
    with pytest.raises(RuntimeError, match="initialize backend"):
        lk.use_pallas()


@pytest.mark.parametrize(
    "argv", [["chip_smoke.py"], ["chip_smoke.py", "--four-chip"]],
)
def test_entry_scripts_refuse_to_run_without_a_tpu(argv):
    """JAX_PLATFORMS=cpu: non-zero exit, no result line, nothing compiled."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_LOG_COMPILES="1")
    r = subprocess.run(
        [sys.executable, *argv], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120,
    )
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert r.stdout.strip() == ""
    assert "Compiling" not in r.stderr and "Finished tracing" not in r.stderr


def test_chip_smoke_alone_refuses_to_run(tmp_path):
    """A directory holding chip_smoke.py and nothing else of the repo:
    non-zero exit, a one-line reason, no result line."""
    import shutil

    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode != 0
    assert "package is not beside this script" in r.stderr
    assert r.stdout.strip() == ""


def test_chip_smoke_result_line_has_the_contract_keys_only():
    import importlib.util
    import json

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    line = smoke.result_line(smoke.device_as_jax_reports([dev]))
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
