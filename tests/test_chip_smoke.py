"""``chip_smoke.py`` on the CPU: its phases at a small size, and its
refusal to report a result without a TPU."""
import importlib.util
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL = {"n": 3000, "nnz": 60000, "block": 16}


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu(chip_smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_one_chip_phases_small(chip_smoke, capsys):
    with chip_smoke.CompileTally() as tally:
        chip_smoke.smoke_one_chip(tally, **SMALL)
    out = capsys.readouterr().out
    assert "served == direct bitwise for all 16 requests" in out
    assert tally.count > 0


_FOUR_DEVICES = textwrap.dedent(
    """
    import importlib.util, os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    spec = importlib.util.spec_from_file_location("chip_smoke", "chip_smoke.py")
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    with cs.CompileTally() as tally:
        cs.smoke_four_chips(tally, n=3000, nnz=60000, block=16)
    print("FOUR_OK")
    """
)


def test_four_chip_phase_on_four_host_devices():
    res = subprocess.run(
        [sys.executable, "-c", _FOUR_DEVICES],
        capture_output=True,
        text=True,
        timeout=600,
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"},
        cwd=ROOT,
    )
    assert "FOUR_OK" in res.stdout, res.stdout + res.stderr
    for exchange in ("replicated", "selective", "overlap:2"):
        assert f"[shard_map {exchange}] ok" in res.stdout
