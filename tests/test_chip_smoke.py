"""``chip_smoke.py`` refuses a machine without a TPU, and its phases
run end to end at a tiny size on the CPU (reduced ResNet, Pallas
kernels in interpret mode), so the script cannot rot between chip runs.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_cpu_only_machine_fails_in_phase_one():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok": true' not in out.stdout
    for line in out.stdout.splitlines():
        assert not line.startswith("{"), line    # no verdict line at all
    with pytest.raises(json.JSONDecodeError):
        json.loads(out.stdout.splitlines()[-1])


@pytest.mark.slow
def test_phases_rehearse_at_tiny_size_on_cpu():
    from repro.configs.resnet import RESNET18

    chip_smoke.phase_kernels(4096 + 17, 0, impl="pallas_interpret",
                             compiled_check=False)
    workload = chip_smoke.build_workload(RESNET18.reduced(), 0)
    dev, host = chip_smoke.phase_session(
        workload, 0, engine="pallas_interpret",
        expect_impl="pallas_interpret")
    assert len(dev) == len(host) == 3
    chip_smoke.phase_host_runtimes(workload, 0, host[0], dev[0])
