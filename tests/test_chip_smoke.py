"""chip_smoke.py's phases at toy sizes on the CPU, and its refusal to run
without a TPU.

The phases are the script's own functions; the test steers only the
platform the script expects (``chip_smoke.PLATFORM``), so the kernels
run in interpret mode and the tpu_custom_call check is not enforced.
"""

import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.core.gan import ConvGanConfig, MLPGanConfig  # noqa: E402

TOY_MLP = chip_smoke.MlpCell(
    mlp=MLPGanConfig(data_dim=784, z_dim=8, g_hidden=16, d_hidden=16),
    users=16, cohort=4, rounds_per_jit=2, windows=2, batch=8,
    samples_per_class=8, requests=((0, 1), (1, 3), (2, 5)))
TOY_CONV = chip_smoke.ConvCell(
    conv=ConvGanConfig(image_size=16, channels=1, z_dim=8, base_filters=4),
    users=2, rounds_per_jit=2, windows=2, batch=4, samples_per_class=4)


@pytest.fixture
def on_cpu(monkeypatch):
    monkeypatch.setattr(chip_smoke, "PLATFORM", "cpu")


def test_one_chip_phases_pass_at_toy_size(on_cpu, capsys):
    failed = chip_smoke.run_phases(chip_smoke.one_chip_phases(TOY_MLP,
                                                              TOY_CONV))
    out = capsys.readouterr().out
    assert failed == [], out
    for phase in ("mlp_federation", "conv_federation", "kernels", "serve"):
        assert f"[{phase}] ok" in out
    assert "check=topk_mask==federated.topk_mask" in out
    assert "replay_bitwise=" in out


def test_mlp_phase_catches_wrong_participation(on_cpu, monkeypatch):
    # last_round must advance for exactly the scheduled users
    monkeypatch.setattr(chip_smoke, "expected_staleness",
                        lambda schedules, users: [0] * users)
    with pytest.raises(AssertionError, match="last_round"):
        chip_smoke.mlp_federation(TOY_MLP)


def test_failed_phase_skips_its_dependents(capsys):
    def boom():
        raise RuntimeError("phase broke")

    failed = chip_smoke.run_phases([("a", boom, ()),
                                    ("b", lambda r: r, ("a",)),
                                    ("c", lambda: 1, ())])
    out = capsys.readouterr().out
    assert failed == ["a", "b"]
    assert "[a] FAILED" in out and "[b] SKIPPED" in out and "[c] ok" in out


def test_main_refuses_a_host_without_tpu(capsys):
    with pytest.raises(SystemExit, match="needs a TPU"):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_script_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert '"ok": true' not in r.stdout


def test_four_chip_phase_on_forced_cpu_devices():
    code = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, {ROOT!r})
        import chip_smoke as cs
        from repro.core.gan import MLPGanConfig
        cs.PLATFORM = "cpu"
        cell = cs.SpmdCell(mlp=MLPGanConfig(data_dim=784, z_dim=8,
                                            g_hidden=16, d_hidden=16),
                           users=16, rounds=4, windows=2, batch=8,
                           samples_per_class=8)
        sys.exit(len(cs.run_phases(cs.four_chip_phases(cell))))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "rows_per_device={0: 1, 1: 1, 2: 1, 3: 1}" in r.stdout
    assert "within_tolerance=True" in r.stdout
