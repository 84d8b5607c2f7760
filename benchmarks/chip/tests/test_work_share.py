"""Required work of the share's decode step, its grouped matmuls and of a
prefill (``work_share.py``, ``work_prefill.py``), summed by hand."""
import json

import pytest

from benchmarks.chip import work, work_prefill, work_share
from benchmarks.chip.tests.cases import CHIP

SHARE = json.loads((CHIP / "configs" / "qwen3-moe-235b-a22b-ep16.json")
                   .read_text())
GRANITE = json.loads((CHIP / "configs" / "granite-moe-1b-a400m.json")
                     .read_text())


def test_held_load_at_the_cell():
    # 64 tokens x 8 choices, 8 of 128 experts held: 32 rows expected; a
    # held expert is missed by all 512 choices with (1 - 8/128)^64.
    got = work_share.held_load(SHARE, 64)
    assert got["rows"] == pytest.approx(32.0)
    assert got["experts"] == pytest.approx(8 * (1 - (120 / 128) ** 64))
    assert got["experts"] == pytest.approx(7.871, abs=1e-3)


def test_gmm_work_hand_sum():
    h = work_share.held_load(SHARE, 64)
    # 8 layers x (7.87 experts x 3 x 4096 x 1536 weights, 32 rows in and
    # out: gate and up read 4096 and write 1536 each, down reads 1536 and
    # writes 4096), bf16.
    nbytes = 2 * 8 * (h["experts"] * 3 * 4096 * 1536
                      + 32 * (2 * (4096 + 1536) + 1536 + 4096))
    got = work_share.gmm_work(SHARE, 64)
    assert got["bytes"] == pytest.approx(nbytes, rel=1e-12)
    assert got["bytes"] == pytest.approx(2.38e9, rel=1e-2)
    assert got["flops"] == pytest.approx(8 * 32 * 6 * 4096 * 1536)


def test_share_decode_bytes_hand_sum():
    # Per layer: q, k, v, o (4096 x 8192 twice, 4096 x 512 twice), two
    # norms, the q/k norms, the 128-wide router and the held experts hit;
    # the head 4096 x 151936, the final norm, 64 embedding rows; KV 16 KiB
    # per token per fill over 8 layers.
    h = work_share.held_load(SHARE, 64)
    attn = 2 * 4096 * 8192 + 2 * 4096 * 512
    layer = attn + 2 * 4096 + 2 * 128 + 4096 * 128 \
        + h["experts"] * 3 * 4096 * 1536
    weights = 8 * layer + 4096 * 151936 + 4096 + 64 * 4096
    kv = 8 * 2 * 4 * 128
    fill = 2176.0
    want = 2 * (weights + 64 * fill * kv + 64 * kv)
    got = work_share.share_decode_work(SHARE, 64, fill)
    assert got["bytes"] == pytest.approx(want, rel=1e-12)
    # The issue's sum at the mean fill: about 7.1 GB, 8.7 ms at 819 GB/s.
    assert got["bytes"] == pytest.approx(7.1e9, rel=0.02)
    flops = 64 * (8 * (2 * attn + 4 * 64 * 128 * fill + 2 * 4096 * 128)
                  + 2 * 4096 * 151936) + 8 * 32 * 6 * 4096 * 1536
    assert got["flops"] == pytest.approx(flops, rel=1e-12)


def test_prefill_hand_sum():
    # granite, one 2048-token prompt: every weight once (all 32 experts
    # hit), 2048 embedding rows, the cache written (24 layers x 8 heads x
    # 64 x 2 per token); flops at the mean causal fill of 1024.5.
    attn = 2 * 1024 * 1024 + 2 * 1024 * 512
    hit = work.experts_hit(32, 8, 2048)
    assert hit == pytest.approx(32.0)
    weights = 24 * (attn + 2 * 1024 + 1024 * 32 + hit * 3 * 1024 * 512) \
        + 1024 * 49155 + 1024 + 2048 * 1024
    want = 2 * (weights + 2048 * 24 * 2 * 8 * 64)
    got = work_prefill.prefill_work(GRANITE, 1, 2048)
    assert got["bytes"] == pytest.approx(want, rel=1e-12)
    per_token = 24 * (2 * attn + 4 * 16 * 64 * 1024.5 + 2 * 1024 * 32
                      + 8 * 6 * 1024 * 512)
    assert got["flops"] == pytest.approx(2048 * per_token + 2 * 1024 * 49155,
                                         rel=1e-12)


def test_gmm_roofline_times_the_kernels_and_their_staged_weights():
    """The reader times each ``%gmm`` call and the op that makes its last
    (weight) operand, and no other custom call."""
    from benchmarks.chip.run import HERE, load_file_module

    m = load_file_module(HERE / "metrics" / "gmm_roofline.share_decode.py",
                         "metric_gmm_roofline_share_decode")
    text = (
        '  %gmm.19 = bf16[512,1536]{1,0} custom-call(%gte.1, %pad.7, '
        '/*index=5*/%fusion.245, %ds_fusion.7), custom_call_target='
        '"tpu_custom_call", metadata={op_name="a"}\n'
        '  %custom-call.3 = s32[8]{0} custom-call(%p.1), custom_call_target='
        '"AssumeGatherIndicesInBound"\n')
    assert m.kernel_ops(text) == {"%gmm.19", "%ds_fusion.7"}
