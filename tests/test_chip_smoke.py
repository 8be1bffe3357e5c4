"""chip_smoke.py off the chip: it refuses the CPU, and its phases run
end to end at reduced size (one CPU device, and four virtual ones for
the four-chip phase). Also the pieces it relies on: the device-kind
preset table and the compilation-cache placement."""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import pytest

from repro.configs import (DEVICE_KIND_PRESETS, PRESET_CATALOG, get_arch,
                           preset_for_device, reduced)

ROOT = pathlib.Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


def _env(**extra):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


def _run(args, cwd=ROOT, timeout=300, **env):
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, cwd=cwd, env=_env(**env),
                          timeout=timeout)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_cpu():
    r = _run([str(SMOKE)], JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_refuses_without_the_repo(tmp_path):
    shutil.copy(SMOKE, tmp_path / SMOKE.name)
    r = subprocess.run([sys.executable, SMOKE.name], capture_output=True,
                       text=True, cwd=tmp_path, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu",
                                PYTHONPATH=""))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_train_and_serve_phases_reduced(smoke):
    cfg = reduced(get_arch(smoke.ARCH))
    tr = smoke.train_phase(cfg, batch=2, seq=64, steps=3)
    assert len(tr["losses"]) == 3 and tr["compile_s"] > 0
    sv = smoke.serve_phase(cfg, n_requests=3, prompt_len=16, new_tokens=4)
    assert sv["completed"] == 3
    assert sv["decode_err"] <= smoke.DECODE_TOL["atol"]


def test_check_raises_smoke_failure(smoke):
    smoke.check(True, "fine")
    with pytest.raises(smoke.SmokeFailure, match="broken"):
        smoke.check(False, "broken")


def test_four_chip_phase_on_virtual_devices():
    code = textwrap.dedent(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(SMOKE)!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        from repro.configs import get_arch, reduced
        r = cs.four_chip_phase(reduced(get_arch(cs.ARCH)), batch=4,
                               seq=64, steps=2)
        assert r["params"]["unsplit_large"] == []
        assert "all-gather" in r["collectives"]
        print("FOUR_OK", r["losses"])
    """)
    r = _run(["-c", code], JAX_PLATFORMS="cpu",
             XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "FOUR_OK" in r.stdout, r.stdout[-2000:]


# --- device presets ----------------------------------------------------------

@pytest.mark.parametrize("kind", sorted(DEVICE_KIND_PRESETS))
def test_device_kind_table_names_catalog_presets(kind):
    dev = SimpleNamespace(platform="tpu", device_kind=kind)
    assert preset_for_device(dev) in PRESET_CATALOG


def test_v5e_kind_prices_v5e():
    dev = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert preset_for_device(dev) == "tpu-v5e"


def test_cpu_rehearses_the_v5e_target():
    dev = SimpleNamespace(platform="cpu", device_kind="cpu")
    assert preset_for_device(dev) == "tpu-v5e"


@pytest.mark.parametrize("platform,kind", [("tpu", "TPU v9 hyper"),
                                           ("gpu", "NVIDIA B200")])
def test_unknown_device_kind_raises(platform, kind):
    dev = SimpleNamespace(platform=platform, device_kind=kind)
    with pytest.raises(KeyError, match="no device preset"):
        preset_for_device(dev)


# --- compilation cache -------------------------------------------------------

CACHE_PROBE = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.launch.cache import enable_compilation_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print("CACHE", enable_compilation_cache())
    print("CONFIG", jax.config.jax_compilation_cache_dir)
    jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
""")


def test_cache_goes_where_the_environment_says(tmp_path):
    env = _env(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    r = subprocess.run([sys.executable, "-c", CACHE_PROBE], cwd=tmp_path,
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert f"CACHE {tmp_path}" in r.stdout
    assert f"CONFIG {tmp_path}" in r.stdout
    assert any(tmp_path.iterdir()), "nothing cached"


def test_cache_defaults_to_the_checkout():
    probe = CACHE_PROBE.rsplit("jax.jit", 1)[0]    # place it, compile nothing
    r = _run(["-c", probe], JAX_PLATFORMS="cpu")
    assert r.returncode == 0, r.stderr[-2000:]
    want = ROOT / ".jax_cache"
    assert f"CACHE {want}" in r.stdout and f"CONFIG {want}" in r.stdout
    ignored = subprocess.run(
        ["git", "check-ignore", "-q", str(want / "entry")], cwd=ROOT)
    assert ignored.returncode in (0, 128)   # 128: not a git checkout
