"""chip_smoke.py off the chip: the --tiny rehearsal runs every phase and
claims nothing, and without --tiny a CPU run ends at the device phase.

Each case is a CPU child (conftest.py has already put JAX_PLATFORMS=cpu,
eight virtual devices and a disabled disk cache into the environment the
children inherit). The real run needs the chip: `chiprun -- python3
chip_smoke.py`.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*args):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("args,phases", [
    (("--tiny",), ["device", "train", "static", "generate",
                  "generate_looped", "generate_expert"]),
    (("--tiny", "--chips", "4"), ["device", "mesh"]),
], ids=["one_chip_phases", "mesh_phase_only"])
def test_tiny_rehearsal_runs_its_phases_and_claims_nothing(args, phases):
    proc = _smoke(*args)
    assert proc.returncode == 0, proc.stdout[-1500:] + proc.stderr[-1500:]
    lines = proc.stdout.strip().splitlines()
    assert lines[0].startswith("REHEARSAL")
    # the phases the option selects all ran, and no other
    assert json.loads(lines[-1]) == {"rehearsal": True, "phases": phases}
    passed = [l.split("]")[0][1:] for l in lines if "] passed in " in l]
    assert passed == phases[1:]
    # a rehearsal never prints the result line the chip run ends with
    assert '"ok"' not in proc.stdout


def test_without_tiny_a_cpu_run_ends_at_the_device_phase():
    proc = _smoke()
    assert proc.returncode != 0
    assert "[device] FAILED" in proc.stdout
    assert "[train]" not in proc.stdout
    assert '"ok"' not in proc.stdout
