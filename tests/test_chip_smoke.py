"""``chip_smoke.py``'s phases at a tiny size on the CPU.

The script itself only ever reports from a TPU; here its phase functions
run on 2 stations and 0.3 s of sky with interpret-mode kernels, so a
broken phase shows up in tier-1 before a chip call is spent on it. The
interpret-mode run also proves the ``tpu_custom_call`` check can tell an
interpreted kernel from a compiled one.
"""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def stations():
    return chip_smoke.make_stations(2, 0.3)


@pytest.mark.parametrize("route", ["default", "use_kernels", "kernel", "megakernel"])
def test_route_phase_on_cpu(stations, route):
    config, extra = chip_smoke.route_configs()[route]
    rec = chip_smoke.run_route(route, stations, config, extra)
    assert rec["stations"] == 2 and rec["events"] > 0 and rec["windows"] > 0
    assert rec["bit_identical"] == {
        "scan": True, **({"scan[fixed]": True} if extra else {})
    }, rec["mismatches"]
    assert rec["tp"] > 0 and 0.0 < rec["accuracy"] <= 1.0
    # 2 sessions fit the first 4-slot tier; each shape compiled once.
    assert list(rec["step_compiles"]) == [4] and not rec["repeated_compiles"]
    if route != "default":
        assert rec["tpu_custom_call"] is False  # interpreted on the CPU
        assert not rec["ok"]


def test_four_chip_phase_on_virtual_devices(subproc):
    out = subproc(f"""
import json, sys
sys.path.insert(0, {str(REPO)!r})
import jax, chip_smoke
rec = chip_smoke.run_four_chips(chip_smoke.make_stations(2, 0.3), jax.devices()[:4])
print(json.dumps(rec))
""", device_count=4)
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["ok"], rec
    assert rec["shard_devices"] == [[0], [1], [2], [3]]
    assert rec["mesh_devices"] == [0, 1, 2, 3]


def test_main_refuses_off_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok": true' not in capsys.readouterr().out


def test_script_alone_fails(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
