"""chip_smoke.py's control flow, on the CPU: the explicit tiny flag runs
every phase (the mesh phases included, over the virtual host devices) and
exits 0; without the flag a CPU-only process stops at the gate."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args, **env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, SMOKE, *args], env=env,
                          capture_output=True, text=True, timeout=600)


def test_tiny_flag_runs_all_phases_on_cpu(tmp_path):
    cache = tmp_path / "cache"
    out = _run("--tiny-cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert lines[0].startswith("NOT A CHIP RUN")
    for phase in ("phase A", "phase mesh dp4", "phase mesh dp2xtp2",
                  "phase B", "phase C", "phase D"):
        assert any(ln.startswith(phase) for ln in lines), phase
    # phase D's toy decoder holds a grouped-query layer with a window
    assert any("D: the grouped window layer (kv_heads=2 window=16) "
               "dispatched to xla_dense" in ln for ln in lines)
    # and, since ISSUE 34, a full layer that turns half of each head, both
    # with a gate on every head
    assert any("D: the half-turned full layer (6 heads over 2) dispatched "
               "to xla_dense" in ln for ln in lines)
    assert any("D: two layers gate their heads, 4 and 6 of them" in ln
               for ln in lines)
    assert json.loads(lines[-1]) == {
        "ok": True, "tiny_cpu": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 4}}
    # the compile cache went where the environment said, nowhere else
    assert f"compile cache: {cache} " in out.stdout
    assert any(cache.iterdir())


def test_without_the_flag_a_cpu_stops_at_the_gate():
    out = _run()
    assert out.returncode != 0
    assert out.stdout.strip() == ""  # no result of any kind
    assert "platform is 'cpu'" in out.stderr
