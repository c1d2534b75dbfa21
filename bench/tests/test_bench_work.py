"""Work counts at published widths against hand-worked values."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench.harness import spec, work
from bench.harness.readings import decode_roofline

YI = spec.load_json(spec.BENCH_DIR / "configs" / "yi6b-dsa.json")
MINI = spec.load_json(spec.BENCH_DIR / "configs" / "minitron4b.json")


def test_indexer_decode_yi():
    # 2*Hi*Di*ctx = 2*16*64*10000; ctx*Di*2 B keys + Hi*Di*2 B query
    # + Hi*4 B head weights + ctx*4 B fp32 scores
    assert work.indexer_decode(YI, 10000) == (
        20_480_000, 1_280_000 + 2_048 + 64 + 40_000)


def test_gqa_decode_minitron():
    # 4*H*d*ctx = 4*24*128*2000; K and V: 2*ctx*KVH*d*2 B; q and out
    assert work.gqa_decode(MINI, 2000) == (
        24_576_000, 8_192_000 + 12_288)


@pytest.mark.parametrize("cfg,want", [
    # 32 * (attn 37_748_736 + swiglu 135_266_304 + indexer 4_521_984)
    # + LM head 4096*64000
    (YI, 5_943_328_768),
    # 32 * (attn 25_165_824 + relu2 56_623_104) + LM head 3072*256000
    (MINI, 3_403_677_696)])
def test_params_per_token(cfg, want):
    assert work.params_per_token(cfg) == want


def test_token_flops_yi_past_top_k():
    # 2*params + 32 layers * (4*H*d*min(ctx, 2048) + 2*Hi*Di*ctx), ctx 10000
    want = 2 * 5_943_328_768 + 32 * (4 * 32 * 128 * 2048
                                     + 2 * 16 * 64 * 10000)
    assert work.token_flops(YI, 9999) == want == 13_615_759_360


def test_token_flops_minitron_all_keys():
    want = 2 * 3_403_677_696 + 32 * 4 * 24 * 128 * 1000
    assert work.token_flops(MINI, 999) == want


@pytest.mark.parametrize("cfg", [YI, MINI])
@pytest.mark.parametrize("start,n", [(0, 256), (1900, 256), (8192, 45)])
def test_span_is_sum_of_tokens(cfg, start, n):
    want = sum(work.token_flops(cfg, p) for p in range(start, start + n))
    assert work.span_flops(cfg, start, n) == pytest.approx(want, rel=1e-12)


def test_decode_roofline_hand_worked():
    """One decode call of two rows at positions 99 and 199 (ctx 100, 200)
    whose GQA kernel ran 1 ms in all: bytes bound at the v5e peaks."""
    trace = {"window": [0, 10_000_000],
             "devices": [{"modules": [["jit__decode_fn", 0, 5_000_000]],
                          "ops": [["_gqa_kernel", 1_000_000, 400_000],
                                  ["_gqa_kernel", 2_000_000, 600_000],
                                  ["fusion.1", 3_000_000, 100_000]]}],
             "host": []}
    run = SimpleNamespace(trace=trace, cfg=MINI,
                          decode_calls=[np.array([99, 199])],
                          peaks={"bf16_flops": 197e12,
                                 "hbm_bytes_per_s": 819e9})
    f = b = 0
    for ctx in (100, 200):
        f += 32 * 4 * 24 * 128 * ctx
        b += 32 * (2 * ctx * 8 * 128 * 2 + 2 * 24 * 128 * 2)
    want = 100 * max(f / 197e12, b / 819e9) / 1e-3
    got = decode_roofline(run, lambda n: n == "_gqa_kernel", work.gqa_decode)
    assert got == pytest.approx(want)
    assert b / 819e9 > f / 197e12          # decode attention is bytes-bound
