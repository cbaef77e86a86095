"""chip_smoke.py: it refuses to run without a TPU, and its reduced rehearsal
(``--smoke``: smoke config, interpret-mode kernels) passes every check on
the CPU and ends with the one-line JSON result."""
import json
import os
import subprocess
import sys

SCRIPT = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, SCRIPT, *args], env=env,
                          capture_output=True, text=True, timeout=600)


def test_chip_smoke_refuses_without_tpu():
    r = _run()
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout


def test_chip_smoke_cpu_rehearsal():
    r = _run("--smoke")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "vs float32 reference" in r.stdout and "int8 KV" in r.stdout
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": 1}}
