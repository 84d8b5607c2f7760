"""chip_smoke.py's phases at smoke size on the CPU, and its refusal to run
without a TPU.  The chip itself is exercised only by running the script
there; these tests keep its code paths and checks working in between."""
import os
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke
from repro import configs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.update(extra)
    return env


@pytest.fixture(scope="module")
def smoke_cfg():
    return configs.get_smoke_config(chip_smoke.ARCH)


def test_serve_phase_checks_logits_tokens_and_cache(smoke_cfg):
    res = chip_smoke.phase_serve(smoke_cfg, seed=0, batch=2, prompt_len=16,
                                 new_tokens=4, check_batch=2)
    assert res.tokens.shape == (2, 4)
    assert np.isfinite(res.last_logits).all()
    assert res.compile_s > 0 and res.decode_step_s > 0


def test_cache_check_agrees_in_float32(smoke_cfg):
    err = chip_smoke.check_cache(smoke_cfg, seed=1, batch=2, prompt_len=24)
    assert err <= chip_smoke.CACHE_ATOL


def test_calibrate_phase_records_interpret_mode():
    prof, rep = chip_smoke.phase_calibrate(chip_smoke.ARCH, reps=1)
    assert prof.interpret is True                    # CPU: Pallas interprets
    assert set(prof.phases) == {"attn_mixer", "moe_ffn"}
    assert len(rep.steps) == 2
    assert rep.cold_degradation >= rep.steady_degradation > 1.0


def test_ep_phase_on_four_virtual_devices():
    code = ("import chip_smoke; from repro import configs; "
            "chip_smoke.phase_ep(configs.get_smoke_config(chip_smoke.ARCH), "
            "n_dev=4, tokens_per_dev=32, seed=0)")
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=600,
        env=_cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.count("wi_gate shard on") == 4
    assert "|scheduled - plain| max 0.000e+00" in r.stdout


def test_cli_refuses_without_a_tpu():
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                       capture_output=True, text=True, timeout=600,
                       env=_cpu_env())
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "'cpu'" in r.stderr
