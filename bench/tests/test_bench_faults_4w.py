"""The training cell's traffic module with four workers at a size the CPU holds,
on four virtual CPU devices (a fresh process: the device count is fixed
when JAX starts): a sound run is correct, and one with the exchange
between workers left out is not. The four-chip cell itself waits for a
later benchmark PR (PERF.md, Open questions); this keeps the harness's
and the reference's multi-worker path checked."""
import json
import os
import subprocess
import sys

from bench.tests import tiny

SCRIPT = r"""
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
from bench.tests import tiny
fault = sys.argv[1]
if fault == "no_exchange":
    from repro.dist import collectives
    collectives.exchange_rows_tiered = lambda rows, tiers: rows
out = tiny.run(tiny.cell("yi6b-train-1chip", workers=4))
print(json.dumps([c["ok"] for c in out["checks"]]))
"""


def _run(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SCRIPT.format(root=tiny.ROOT, src=os.path.join(tiny.ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", code, fault], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_four_workers_sound_and_without_exchange():
    assert all(_run("none"))
    assert not all(_run("no_exchange"))
