"""The ``qwen3_share_decode`` and ``granite_prefill`` cells end to end on
the CPU at small sizes: sound runs, planted faults, the float8 control
and the traced records."""
import jax
import pytest

from benchmarks.chip.tests import share_cases as sc

CELLS = ("qwen3_share_decode", "granite_prefill")


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    jax.config.update("jax_enable_compilation_cache", False)
    monkeypatch.setattr("benchmarks.chip.run.enable_compile_cache",
                        lambda: None)


@pytest.fixture(autouse=True)
def blocks_of_32(monkeypatch):
    """Held-expert blocks of 32 tokens: the small prefill (4 x 20 tokens)
    runs three."""
    monkeypatch.setattr("repro.models.moe.HELD_BLOCK_TOKENS", 32)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    res = sc.run_small(workload)
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == {"served_gap", "prefill_err", "decode_err"}
    assert set(res["metrics"]) == {"gen_tok_s", "itl_p95_ms", "setup_s"}
    assert res["attempted"] >= 4 and res["failed"] == 0


@pytest.mark.parametrize("workload,fault", [
    ("qwen3_share_decode", "token_altered"),
    ("qwen3_share_decode", "no_qk_norm"),
    ("qwen3_share_decode", "expert_dropped"),
    ("qwen3_share_decode", "offset_shifted"),
    ("granite_prefill", "token_altered"),
])
def test_fault_is_caught(monkeypatch, workload, fault):
    sc.plant(monkeypatch, fault)
    res = sc.run_small(workload)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_decode_only_precision_cut_is_caught(monkeypatch, workload):
    """A float8 KV cache in decode alone leaves prefill as it was; the
    last decode step's logits show it.  Four whole rounds, as below."""
    sc.plant(monkeypatch, "decode_cache_fp8")
    got = sc.readings_of_rounds(workload, 4)
    assert got["prefill_err"] <= sc.LIMITS["prefill_err"], got
    assert got["decode_err"] > sc.LIMITS["decode_err"], got


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_each_check(workload):
    """The float8 reference in the program's place reads above every
    limit that the program's runs pass.  Four whole rounds, not a timed
    window, so that the sampled requests do not hang on the host's speed."""
    got = sc.readings_of_rounds(workload, 4, control=True)
    for name, limit in sc.LIMITS.items():
        assert got[name] <= limit < got[f"control_{name}"], (name, got)


def test_short_answers_warm_up_within_the_round():
    """Four tokens a request: warm-up stops at the round's last token and
    its round is not among the finished requests."""
    drv = sc.driver("granite_prefill")
    drv.setup()
    assert drv.finished == [] and drv.finished_logits == []
    got = drv.window(0.2)
    assert len(drv.finished) == len(drv.finished_logits) >= 1
    assert got["metrics"]["gen_tok_s"] > 0


def test_traced_records_hold_engagement():
    """The traced slice's records count the rows the held experts get per
    layer in prefill and in the first decode step."""
    drv = sc.driver("qwen3_share_decode")
    drv.setup()
    rec = drv.traced()
    cfg, B, P = drv.cfg, drv.B, drv.P
    pre, dec = rec["held_rows_prefill"], rec["held_rows_decode"]
    assert len(pre) == len(dec) == cfg.n_layers
    assert all(len(r) == cfg.experts_held for r in pre + dec)
    for rows in pre:                      # each token picks distinct experts
        assert 0 < sum(rows) <= B * P * min(cfg.top_k, cfg.experts_held)
    for rows in dec:
        assert sum(rows) <= B * cfg.top_k
    assert 0 < rec["held_share"] < 1
    assert rec["programs"]["decode"] and rec["decode_fills"][0] == P + 1


@pytest.mark.parametrize("workload", CELLS)
def test_refuses_without_a_tpu(workload):
    import os
    import subprocess
    import sys

    root = sc.CHIP.parents[1]
    out = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", workload,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=f"{root / 'src'}{os.pathsep}{root}"))
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert "{" not in out.stdout
