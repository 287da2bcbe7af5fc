"""chip_smoke.py off the chip: it refuses to report without a TPU, and each
of its phases runs end to end at a tiny size on the CPU."""
import dataclasses
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np

from repro import configs

ROOT = Path(__file__).resolve().parents[1]


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


chip_smoke = _load()


def _tiny_moe():
    """The smoke qwen3-moe, cut like the chip run to one layer period."""
    cfg = configs.get_smoke_config("qwen3-moe-30b-a3b")
    return dataclasses.replace(cfg, num_layers=len(cfg.block_pattern))


def test_main_exits_nonzero_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_cut_config_keeps_published_widths():
    full = configs.get_config("qwen3-moe-30b-a3b")
    cfg, cuts = chip_smoke.qwen3_moe_cut()
    for name in ("d_model", "num_heads", "num_kv_heads", "head_dim",
                 "num_experts", "top_k", "moe_d_ff"):
        assert getattr(cfg, name) == getattr(full, name), name
    assert cfg.num_layers == 1 and cfg.vocab_size == 18992
    assert len(cuts) == 2


def test_plan_phase_tiny():
    out = chip_smoke.plan_phase(ranks=16, log=lambda _: None)
    assert set(out) == {"numpy_s", "pallas_compiled_s", "jit_s",
                        "interpreted"}
    assert out["interpreted"]           # the CPU interprets the f32 kernel


def test_train_phase_tiny():
    losses = chip_smoke.train_phase(_tiny_moe(), seq_len=64, global_batch=2,
                                    steps=2, log=lambda _: None)
    assert len(losses) == 2 and np.all(np.isfinite(losses))


def test_replan_preserves_function_and_optimizer_on_two_devices():
    """The four-chip phase on two virtual CPU devices: a replan inside
    train_loop, then one whose loss, expert weights, router and AdamW
    moments are checked on a fixed batch (replan_phase raises on any
    mismatch)."""
    code = (
        "import json, dataclasses, importlib.util\n"
        "from repro import configs\n"
        f"spec = importlib.util.spec_from_file_location('cs', "
        f"{str(ROOT / 'chip_smoke.py')!r})\n"
        "cs = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(cs)\n"
        "cfg = configs.get_smoke_config('qwen3-moe-30b-a3b')\n"
        "cfg = dataclasses.replace(cfg, num_layers=1)\n"
        "out = cs.replan_phase(cfg, n_chips=2, seq_len=512, global_batch=8,"
        " steps=3, hbm_budget_bytes=16e9, log=lambda _: None)\n"
        "print(json.dumps(out))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["tokens_max_mean_after"] < out["tokens_max_mean_before"]
