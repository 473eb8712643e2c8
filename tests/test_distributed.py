"""Multi-process integration: 2 processes x 4 CPU devices, one 8-way mesh.

SURVEY.md §4.3: the same shuffle tests must cross a real host/process
boundary. Collectives run over Gloo between the two processes — the DCN
analogue — while everything else is byte-identical to the single-process
path.
"""

import os
import socket
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_two_process_shuffle(tmp_path):
    port = _free_port()
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_PLATFORMS", "XLA_FLAGS")}
    env.update({
        "PYTHONPATH": str(REPO),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
    })
    spill = str(tmp_path / "mp_ckpt")
    procs = [
        subprocess.Popen(
            [sys.executable, str(REPO / "tests" / "mp_worker.py"),
             str(pid), "2", str(port), spill],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for pid in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("multi-process workers timed out:\n" + "\n".join(outs))
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out}"
        assert f"MPOK proc={pid} mesh=8" in out, out
        assert f"MPCKPT proc={pid} ok" in out, out
