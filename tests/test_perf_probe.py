"""perf_probe hygiene + measured-bandwidth probe.

The module used to set XLA_FLAGS at import time, which poisoned any
process that merely collected it (pytest, benchmarks.run).  It now
sets the flag inside main(); these tests pin that, and exercise the
measured per-level bandwidth estimate + overlap sanity pairing on a
small fake mesh in a subprocess.
"""
import os
import subprocess
import sys
import textwrap


def _env(**extra):
    e = dict(os.environ)
    e.pop("XLA_FLAGS", None)
    e["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    e.update(extra)
    return e


def _run(code, **extra_env):
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=_env(**extra_env),
                       timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_import_leaves_environment_untouched():
    """Importing the probe must not mutate XLA_FLAGS (tier-1 pytest
    collection imports it; the 512-device flag would leak into every
    later jax initialization in the same process)."""
    out = _run("""
        import os
        assert "XLA_FLAGS" not in os.environ
        import repro.launch.perf_probe
        assert "XLA_FLAGS" not in os.environ, os.environ["XLA_FLAGS"]
        print("OK")
    """)
    assert "OK" in out


def test_cli_help_runs_without_env_setup():
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.perf_probe", "--help"],
        capture_output=True, text=True, env=_env(), timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "--measure-bw" in r.stdout and "--device" in r.stdout


def test_measure_level_bandwidth_and_overlap_sanity():
    out = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax
        from repro.launch.perf_probe import (measure_level_bandwidth,
                                             overlap_sanity)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1, 2, 2), ("pod", "data", "model"))
        m = measure_level_bandwidth(mesh, size_mib=0.25, repeats=2)
        assert set(m) == {"pod", "data", "model"}
        assert m["pod"]["achieved_bytes_per_s"] is None      # span 1
        for ax in ("data", "model"):
            assert m[ax]["ways"] == 2
            assert m[ax]["bytes_moved"] > 0
            assert m[ax]["achieved_bytes_per_s"] > 0
        rows = overlap_sanity(m, "a100-80g", mesh.size)
        assert rows, rows
        # innermost mesh axis pairs with the innermost (fastest) level
        assert rows[0]["axis"] == "model"
        for r in rows:
            assert r["spec_bytes_per_s"] > 0
            assert r["achieved_over_spec"] is not None
        print("OK")
    """)
    assert "OK" in out
