"""Dry-run machinery tests: roofline parsing units (fast) + one real
multi-pod cell lower+compile (slow, subprocess for the 512-device env)."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.launch import roofline as rl

REPO = Path(__file__).resolve().parents[1]

HLO_SAMPLE = """
  %ag = bf16[16,4096,896]{2,1,0} all-gather(%p0), replica_groups={{0,1,2,3}}, dimensions={1}
  %ar = f32[128,512]{1,0} all-reduce(%x), replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add
  %rs = bf16[8,256]{1,0} reduce-scatter(%y), replica_groups={{0,1}}, dimensions={0}
  %a2a = u8[64,1024]{1,0} all-to-all(%z), replica_groups={{0,1,2,3}}, dimensions={0}
  %cp = bf16[4,4]{1,0} collective-permute(%w), source_target_pairs={{0,1},{1,2}}
  %ag1 = bf16[2,2]{1,0} all-gather(%q), replica_groups={{0}}, dimensions={0}
"""


def test_parse_collectives_kinds_and_bytes():
    stats = rl.parse_collectives(HLO_SAMPLE, n_devices=8)
    assert stats.count_by_kind == {
        "all-gather": 1, "all-reduce": 1, "reduce-scatter": 1,
        "all-to-all": 1, "collective-permute": 1}  # P=1 ag skipped
    ag = 16 * 4096 * 896 * 2 * 3 / 4
    ar = 128 * 512 * 4 * 2 * 7 / 8
    rs = 8 * 256 * 2 * 1
    a2a = 64 * 1024 * 1 * 3 / 4
    cp = 4 * 4 * 2
    assert abs(stats.bytes_by_kind["all-gather"] - ag) < 1
    assert abs(stats.bytes_by_kind["all-reduce"] - ar) < 1
    assert abs(stats.bytes_by_kind["reduce-scatter"] - rs) < 1
    assert abs(stats.bytes_by_kind["all-to-all"] - a2a) < 1
    assert abs(stats.bytes_by_kind["collective-permute"] - cp) < 1


def test_shape_bytes_tuple_and_fp8():
    assert rl._shape_bytes("(bf16[4,4], f8e4m3fn[256])") == 32 + 256
    assert rl._shape_bytes("u8[100]") == 100


def test_roofline_terms_math():
    # synthetic: 1 TFLOP, 1 GB hbm, 100 MB links on 4 chips
    class C:
        @staticmethod
        def cost_analysis():
            return {"flops": 1e12, "bytes accessed": 1e9}

        @staticmethod
        def as_text():
            return "%ar = f32[12500000]{0} all-reduce(%x), replica_groups={{0,1,2,3}}"
    roof = rl.analyze(C(), 4, model_flops=2e12)
    assert abs(roof.compute_s - 1e12 / rl.PEAK_FLOPS) < 1e-9
    assert abs(roof.memory_s - 1e9 / rl.HBM_BW) < 1e-9
    assert roof.useful_ratio == 2e12 / 4e12


@pytest.mark.slow
def test_one_multipod_cell_compiles():
    """End-to-end: qwen2-0.5b train_4k on the 512-chip multi-pod mesh,
    under a full registry spec with per-layer overrides (first/last two
    layers TP-uncompressed) — the spec grammar must thread through the
    production launcher and compile."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env.pop("XLA_FLAGS", None)  # dryrun sets its own 512-device flag
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "qwen2-0.5b",
         "--shape", "train_4k", "--mesh", "multi", "--mode", "check",
         "--policy", "tp=taco:jnp,skip_first=2,skip_last=2"],
        env=env, capture_output=True, text=True, timeout=1200,
        cwd=str(REPO))
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "0 errors" in proc.stdout


def test_dryrun_appends_flags_and_pins_cpu():
    """The dry-run is a CPU rehearsal tool: importing it keeps the
    caller's XLA_FLAGS (appending its 512-device world) and pins JAX to
    the CPU even where no platform was chosen."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["XLA_FLAGS"] = "--xla_cpu_enable_fast_math=false"
    env["PYTHONPATH"] = str(REPO / "src")
    code = ("import os, jax, repro.launch.dryrun; "
            "print(os.environ['XLA_FLAGS']); "
            "print(jax.devices()[0].platform, len(jax.devices()))")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    flags, devices = proc.stdout.strip().splitlines()[-2:]
    assert flags.startswith("--xla_cpu_enable_fast_math=false ")
    assert "--xla_force_host_platform_device_count=512" in flags
    assert devices == "cpu 512"
