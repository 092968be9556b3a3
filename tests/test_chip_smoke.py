"""``chip_smoke.py`` off the chip.

Without a TPU the script must fail before it prints a result, and it must
fail in a directory that holds nothing else of the repository.  Its phases
run here at a tiny scale on the CPU — the script's own checks against the
numpy references, the rebind, the QueryServer mix and the streamed session —
so the logic the chip run depends on is exercised on every test run."""
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env.pop("PYTHONPATH", None)  # the script finds the package itself
    return env


def _assert_no_result(out):
    assert out.returncode != 0, out.stdout[-2000:]
    assert '"ok"' not in out.stdout, out.stdout[-2000:]


def test_chip_smoke_fails_without_a_tpu():
    out = subprocess.run(
        [sys.executable, SCRIPT], capture_output=True, text=True,
        env=_cpu_env(), cwd=ROOT, timeout=300,
    )
    _assert_no_result(out)
    assert "needs a TPU" in out.stderr


def test_chip_smoke_fails_without_the_repository(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], capture_output=True, text=True,
        env=_cpu_env(), cwd=tmp_path, timeout=300,
    )
    _assert_no_result(out)


@pytest.mark.parametrize("chips", [1, 4])
def test_chip_smoke_phases_on_cpu(chips):
    """The smoke's phases at SF 0.002 on CPU devices (4 virtual ones for
    the sharded phase, in a subprocess so this process keeps one)."""
    code = textwrap.dedent(
        f"""
        import sys
        sys.path.insert(0, {ROOT!r})
        import chip_smoke as cs
        from repro.data import tpch

        db = tpch.generate(scale=0.002, seed=1).tables()
        refs = cs.References(db)
        if {chips} == 1:
            cs.default_phase(db, refs)
        else:
            cs.sharded_phase(db, refs, {chips})
        print("PHASE_DONE")
        """
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=_cpu_env(XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}"),
        cwd=ROOT, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PHASE_DONE" in out.stdout
    assert "matches_reference=False" not in out.stdout
    if chips == 1:
        assert out.stdout.count("served request") == 12
        assert "bitwise_equal_resident=True" in out.stdout
    else:
        assert out.stdout.count("lineitem on device") == chips
