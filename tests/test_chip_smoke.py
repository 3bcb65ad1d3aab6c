"""``chip_smoke.py``: its phases hold on the CPU at a small size, and the
script itself refuses to run anywhere but on a TPU."""

import importlib.util
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
ROWS = 10_000  # smallest size at which the smoke's join still probes sparse codes


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_phases_pass_on_cpu(capsys):
    _load().run(ROWS)
    out = capsys.readouterr().out
    for phase in ("plane", "session", "delta", "hot", "fleet"):
        assert f"[{phase}] " in out
    assert "probe_exact=True" in out
    assert "join_ops_on_device=1" in out


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py"), "--rows", "100"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert "[device] platform=cpu" in out.stdout
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr
