"""Required work and the peak table (``benchmarks/chip/work.py``)."""
import json

import pytest

from benchmarks.chip import work
from benchmarks.chip.tests.cases import CHIP

GRANITE = json.loads((CHIP / "configs" / "granite-moe-1b-a400m.json").read_text())
QWEN = json.loads((CHIP / "configs" / "qwen3-moe-235b-a22b-ep4.json").read_text())


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no peaks"):
        work.peaks_for("TPU v99")
    assert work.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("key,value", [("capacity_factor", 4.0),
                                       ("max_position_embeddings", 768)])
def test_work_ignores_capacity_and_cache_length(key, value):
    changed = dict(GRANITE, **{key: value})
    assert work.decode_step_work(changed, 64, 300) == \
        work.decode_step_work(GRANITE, 64, 300)
    q = dict(QWEN, **{key: value})
    assert work.ep_step_work(q, 256, 4) == work.ep_step_work(QWEN, 256, 4)


def test_decode_bytes_hand_sum():
    # 24 layers of (attention 3,145,728 + norms 2,048 + router 32,768 +
    # 32 experts x 1,572,864) weights, the unembedding 1024 x 49155 and the
    # final norm, 64 embedding rows; KV 49,152 B per token per fill.
    layer = 3_145_728 + 2_048 + 32_768 + 32 * 1_572_864
    weights = 24 * layer + 1024 * 49155 + 1024 + 64 * 1024
    fill = 300
    want = 2 * weights + 64 * fill * 49_152 + 64 * 49_152
    got = work.decode_step_work(GRANITE, 64, fill)["bytes"]
    assert got == pytest.approx(want, rel=1e-6)
    # All weights but the embedding table: 2.67 GB; the filled cache at
    # S_max 768 would be the issue's 2.42 GB.
    assert 2 * (weights - 64 * 1024) == pytest.approx(2.669e9, rel=1e-3)
    assert 64 * 768 * 49_152 == pytest.approx(2.416e9, rel=1e-3)


def test_ep_hand_sum():
    got = work.ep_step_work(QWEN, 256, 4)
    # 4 layers x (32 local experts x 3 x 4096 x 1536 = 4.83 GB in bf16,
    # the router 4096 x 128, the chip's 256 tokens in and out).
    per_layer = 32 * 3 * 4096 * 1536 + 4096 * 128 + 2 * 256 * 4096
    assert got["bytes"] == pytest.approx(2 * 4 * per_layer, rel=1e-9)
    assert 2 * 4 * 32 * 3 * 4096 * 1536 == pytest.approx(4.83e9, rel=1e-3)
    pairs = 256 * 8
    assert got["flops"] == pytest.approx(
        4 * (pairs * 6 * 4096 * 1536 + 256 * 2 * 4096 * 128))


def test_least_time_takes_the_binding_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time_s(1000, 5, peaks) == 10.0
    assert work.least_time_s(10, 500, peaks) == 50.0
