"""The harness end to end on the CPU: refusal, sound runs, planted faults
and the float8 control, at small sizes."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip.tests import cases

ROOT = cases.CHIP.parents[1]
ENV = dict(os.environ, JAX_PLATFORMS="cpu",
           PYTHONPATH=f"{ROOT / 'src'}{os.pathsep}{ROOT}")


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    jax.config.update("jax_enable_compilation_cache", False)
    monkeypatch.setattr("benchmarks.chip.run.enable_compile_cache",
                        lambda: None)


@pytest.mark.parametrize("workload", ["granite_decode", "qwen3moe_ep4"])
def test_refuses_without_a_tpu(workload):
    out = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", workload,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert "{" not in out.stdout


def test_refuses_outside_a_checkout(tmp_path):
    """Only BENCHMARK.json and the benchmark's own files: no program."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(cases.CHIP, tmp_path / "benchmarks" / "chip")
    out = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "granite_decode", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(ENV, PYTHONPATH=""), capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0 and "{" not in out.stdout


def granite(limit=cases.GRANITE_LIMIT):
    return cases.run_small("granite_decode", cases.granite_config(),
                           cases.granite_traffic(limit), 1)


def test_granite_sound_run_is_correct():
    res = granite()
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"gen_tok_s", "itl_p95_ms", "setup_s"}
    assert res["attempted"] >= 4 and res["failed"] == 0
    assert list(res)[-1] == "checks"


def test_granite_token_altered_is_caught(monkeypatch):
    """One decode step's token, in every request, is changed where the
    program produces it."""
    from repro.launch import serve

    orig = serve.make_steps

    def broken(cfg, s_max):
        prefill, decode = orig(cfg, s_max)

        def bad(params, token, caches):
            nxt, logits, caches = decode(params, token, caches)
            length = jax.tree.leaves(caches)[-1].reshape(-1)[0]
            nxt = jnp.where(length == 20, (nxt + 1) % cfg.vocab_size, nxt)
            return nxt, logits, caches

        return prefill, jax.jit(bad, donate_argnums=(2,))

    monkeypatch.setattr(serve, "make_steps", broken)
    res = granite()
    assert not res["correct"], res["checks"]


def test_granite_control_fails():
    """The float8 reference in the program's place reads above the limit
    that the program's runs pass."""
    from benchmarks.chip import run
    from benchmarks.chip.common import Context

    ctx = Context(workload="granite_decode", config=cases.granite_config(),
                  traffic=cases.granite_traffic(cases.GRANITE_LIMIT),
                  seed=cases.SEED, devices=jax.devices()[:1])
    run._paths()
    drv = run.load_file_module(cases.CHIP / "drivers" / "serve_rounds.py",
                               "driver_serve_rounds").Driver(ctx)
    drv.setup()
    drv.window(0.2)
    drv.release()
    got = drv.readings(control=True)
    assert got["served_gap"] <= cases.GRANITE_LIMIT < got["control_gap"]


def ep_case(case: str) -> dict:
    env = dict(ENV, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-m", "benchmarks.chip.tests.cases",
                          case], cwd=ROOT, env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_ep_sound_run_is_correct():
    res = ep_case("sound")
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"ep_step_ms", "setup_s"}
    assert res["device"]["count"] == 4


@pytest.mark.parametrize("fault", ["no_exchange", "half_batch",
                                   "token_altered"])
def test_ep_fault_is_caught(fault):
    res = ep_case(fault)
    assert not res["correct"], res["checks"]


def test_ep_control_fails():
    res = ep_case("control")
    assert res["ep_row_err"] <= cases.EP_LIMIT < res["control_row_err"]


def test_reported_metrics_follow_the_benchmark():
    from benchmarks.chip import run
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    got = {n: ([m["name"] for m in run.reported(bench, c, False)],
               [m["name"] for m in run.reported(bench, c, True)])
           for n, c in cells.items()}
    assert got["granite_decode"][0] == ["gen_tok_s", "itl_p95_ms", "setup_s"]
    assert got["qwen3moe_ep4"][1] == ["idle_share.ep", "a2a_exposed_ms",
                                      "mfu.ep"]
    for name, (_, per_layer) in got.items():
        for m in per_layer:
            assert (cases.CHIP / "metrics" / f"{m}.py").is_file()


def test_seed_draws_are_fixed():
    from benchmarks.chip import weights as W
    a = W.leaf(W.base_key(cases.SEED), "blocks/l0/moe/wo", (2, 3, 4),
               jnp.bfloat16)
    b = jax.jit(lambda k: W.leaf(k, "blocks/l0/moe/wo", (2, 3, 4),
                                 jnp.bfloat16))(W.base_key(cases.SEED))
    assert np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
