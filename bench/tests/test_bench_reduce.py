"""The trace reduction on a hand-made trace with worked values, and on
the small trace in ``data/``: the first 100 ms of a traced rollout run
on one TPU v5e, written by ``bench/tools/record_trace.py --small`` with
the numbers this test pins."""
import pytest

from bench.harness import reduce, spec

HAND = {"window": [100, 1100],
        "devices": [{
            "ops": [["a", 50, 100], ["b", 120, 80], ["_gqa_kernel", 300, 100],
                    ["fusion.3", 350, 100], ["c", 1050, 100]],
            "modules": [["jit__decode_fn", 280, 200],
                        ["jit__prefill_fn", 1000, 200]]}],
        "host": [["step", 0, 2000], ["sample", 190, 120]]}


def test_busy_union_and_idle_share():
    # [100,200] + [300,450] + [1050,1100] of a 1000 ns window
    assert reduce.busy_ns(HAND) == 300
    assert reduce.busy_s(HAND) == pytest.approx(300e-9)
    assert reduce.window_s(HAND) == pytest.approx(1000e-9)
    assert reduce.idle_share(HAND) == pytest.approx(0.7)
    assert reduce.gaps(HAND) == [(200, 300), (450, 1050)]


def test_kernel_and_program_time():
    secs, n = reduce.op_time(HAND, lambda o: o == "_gqa_kernel",
                             lambda m: "_decode_fn" in m)
    assert (secs, n) == (pytest.approx(100e-9), 1)
    # nothing of the kernel ran inside a prefill program
    assert reduce.op_time(HAND, lambda o: o == "_gqa_kernel",
                          lambda m: "_prefill_fn" in m)[1] == 0
    # the prefill program runs past the window's end: not counted
    assert reduce.module_calls(HAND, lambda m: "_prefill_fn" in m) == (0, 0)
    assert reduce.module_calls(HAND, lambda m: "_decode_fn" in m) == \
        (pytest.approx(200e-9), 1)


def test_breakdown():
    assert reduce.top_ops(HAND)[:2] == [["_gqa_kernel", 1e-7],
                                        ["fusion", 1e-7]]
    idle = dict(reduce.idle_by_host(HAND))
    assert idle == {"step": pytest.approx(600e-9),
                    "sample": pytest.approx(100e-9)}


SMALL = spec.BENCH_DIR / "tests" / "data" / "trace_small.json"


def test_small_trace():
    tr = spec.load_json(SMALL)
    busy, win = reduce.busy_s(tr), reduce.window_s(tr)
    assert 0 < busy <= win
    assert sum(v for _, v in reduce.idle_by_host(tr, k=100)) == \
        pytest.approx(win - busy, rel=1e-9)
    meta = tr["expect"]
    assert busy == pytest.approx(meta["busy_s"], rel=1e-9)
    secs, n = reduce.module_calls(tr, lambda m: "_decode_fn" in m)
    assert n == meta["decode_calls"]
    assert secs == pytest.approx(meta["decode_s"], rel=1e-9)
