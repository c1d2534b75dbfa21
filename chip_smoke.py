#!/usr/bin/env python3
"""Serve yi-6b at its published width on one TPU, end to end.

    python chip_smoke.py

One process builds yi-6b (32 layers, d_model 4096, 32 heads / 4 KV heads,
d_ff 11008, vocab 64000, DSA indexer 16x64, top-k 2048) with random bf16
weights drawn on the device from a seed, puts
``AsyncFrontend(ContinuousEngine(...), max_restarts=0)`` in front of it
(paged KV pool, radix prefix cache, Pallas paged-attention kernels) and
submits a few greedy requests:

* one prompt longer than DSA's top-k, so sparse selection drops tokens;
* two short prompts, one of which is later extended by a fourth request
  that shares a prefix ending mid-block (a radix-cache hit and a COW fork).

Checks: every request ends ``ok`` with ``max_new`` tokens, no request
fault, no engine restart, the prefix cache and the fork were exercised,
and the engine's first token of one request agrees with a forward pass of
the same weights through the gather (``ref``) attention path.  The last
stdout line is a JSON object naming the device; any failed check exits
nonzero.  There is no CPU or interpret-mode path: without a TPU the
script exits nonzero before building anything.
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# first-token agreement between the engine (Pallas kernels, bf16) and the
# gather path (bf16).  Both run the same bf16 weights through 32 layers in
# a different operation order, so logits differ by bf16 rounding (a relative
# 2^-8 per rounding, compounded over the layers).  On logits of order 1 a
# difference of 0.15 in log-probability allows that noise and is far
# below what a wrong kernel produces (a different mask or missing block
# moves log-probabilities by whole units).
LOGPROB_TOL = 0.15

# published widths: yi-6b and gemma2-2b GQA (H, KVH, d, window, softcap),
# GLM-5 MLA (H, lora, rope) with its MTP verify span (num_predict + 1),
# and yi-6b's DSA indexer (Hi, Di).  A 65-token span is not a whole number
# of query tiles for yi-6b GQA or MLA, so their padded tiles run too.
KERNELS = dict(gqa={"yi_6b": (32, 4, 128, 0, 0.0),
                    "gemma2_2b": (8, 4, 256, 4096, 50.0)},
               mla=(64, 512, 64), indexer=(16, 64), prefill_len=65,
               verify_len=4)

FULL = dict(long_len=2600, short_len=40, shared_len=24, max_new=16,
            prefill_chunk=256, max_len=4096, num_blocks=600, max_batch=4,
            block_size=16)


# kernel vs gather oracle, both from the same bf16 inputs: the kernels
# accumulate in f32 but feed the softmax weights to the MXU in one bf16
# pass (a relative 2^-8 each), which the oracle, run at highest matmul
# precision, does not.  Outputs are of order 1; a wrong mask or a skipped
# block moves them by tenths or more.
KERNEL_TOL = 3e-2

# one pool geometry for every kernel check: ragged rows at an empty
# sequence, both sides of a block boundary, past gemma2's 4096-token
# window, and at the table's last position
KERNEL_POOL = dict(bs=16, mb=384, lens=(0, 15, 16, 4500, 6143))


def check_kernels(*, gqa, mla, indexer, prefill_len: int, verify_len: int,
                  bs: int, mb: int, lens, seed: int = 0, log=print) -> list:
    """Run the six paged-attention kernels through their dispatch
    (``impl=None``) against the gather oracles (``impl="ref"``) on random
    bf16 pools; returns the failed checks.

    gqa: {name: (H, KVH, d, window, softcap)}; mla: (H, lora, rope);
    indexer: (Hi, Di).  Spans start at ``lens`` clipped so they fit."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.paged_attention import ops

    B = len(lens)
    rng = np.random.default_rng(seed)
    bf16 = jnp.bfloat16
    normal = lambda *s: jnp.asarray(rng.standard_normal(s), bf16)  # noqa
    tables = jnp.asarray(rng.permutation(B * mb).reshape(B, mb), jnp.int32)
    nb = B * mb + 1
    lens_a = jnp.asarray(lens, jnp.int32)
    failures = []

    def starts(S):
        return jnp.asarray([min(n, mb * bs - S) for n in lens], jnp.int32)

    def compare(name, fn, *args, live=None, **kw):
        got = np.asarray(fn(*args, impl=None, **kw), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(fn(*args, impl="ref", **kw), np.float32)
        if live is not None:
            got, want = np.where(live, got, 0.0), np.where(live, want, 0.0)
        err = float(np.max(np.abs(got - want) / (1.0 + np.abs(want))))
        ok = np.isfinite(got).all() and err <= KERNEL_TOL
        log(f"kernel {name}: shape {got.shape}, max rel err {err:.2e}"
            f"{'' if ok else '  FAILED'}")
        if not ok:
            failures.append(f"kernel {name}: max rel err {err:.2e} > "
                            f"{KERNEL_TOL}")

    for name, (H, KVH, d, window, softcap) in gqa.items():
        kp, vp = normal(nb, bs, KVH, d), normal(nb, bs, KVH, d)
        kw = dict(window=window, softcap=softcap)
        compare(f"decode_gqa[{name}]", ops.paged_gqa_attend,
                normal(B, 1, H, d), kp, vp, tables, lens_a, **kw)
        compare(f"prefill_gqa[{name}] S={prefill_len}", ops.paged_gqa_prefill,
                normal(B, prefill_len, H, d), kp, vp, tables,
                starts(prefill_len), **kw)
    H, L, R = mla
    cp, krp = normal(nb, bs, L), normal(nb, bs, R)
    scale = (L + R) ** -0.5
    compare("decode_mla", ops.paged_mla_attend, normal(B, 1, H, L),
            normal(B, 1, H, R), cp, krp, tables, lens_a, scale=scale)
    for S in (prefill_len, verify_len):
        compare(f"prefill_mla S={S}", ops.paged_mla_prefill,
                normal(B, S, H, L), normal(B, S, H, R), cp, krp, tables,
                starts(S), scale=scale)
    Hi, Di = indexer
    kip = normal(nb, bs, Di)
    w = jax.nn.softmax(jnp.asarray(rng.standard_normal((B, prefill_len, Hi)),
                                   jnp.float32), -1)
    pos = np.arange(mb * bs)
    compare("indexer_decode", ops.paged_indexer_scores, normal(B, Hi, Di),
            w[:, 0], kip, tables, lens_a,
            live=pos[None] <= np.asarray(lens)[:, None])
    st = starts(prefill_len)
    qpos = np.asarray(st)[:, None] + np.arange(prefill_len)[None]
    compare(f"indexer_prefill S={prefill_len}", ops.paged_indexer_prefill,
            normal(B, prefill_len, Hi, Di), w, kip, tables, st,
            live=pos[None, None] <= qpos[..., None])
    return failures


def build_params(cfg, seed: int, dtype):
    """Random weights drawn on the device, in ``dtype``, under jit."""
    import jax
    from repro.models import get_model
    model = get_model(cfg)
    return jax.jit(lambda k: model.init(k, cfg, dtype)[0])(
        jax.random.key(seed))


def ref_first_logprobs(cfg, params, prompt, block_size: int):
    """Log-softmax of the next-token logits after ``prompt``, through the
    gather (``ref``) attention path on a fresh pool."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.paging import blocks_for
    from repro.models import get_model
    model = get_model(cfg)
    dtype = jax.tree.leaves(params)[0].dtype
    n = blocks_for(len(prompt), block_size)
    pool, _ = model.init_paged_cache(cfg, n + 1, block_size, dtype)
    table = jnp.arange(n, dtype=jnp.int32)[None]
    fwd = jax.jit(lambda p, t, c: model.prefill(
        p, t, cfg, c, block_tables=table,
        cache_index=jnp.zeros((1,), jnp.int32), paged_impl="ref")[0])
    logits = fwd(params, jnp.asarray(prompt, jnp.int32)[None], pool)
    row = np.asarray(logits[0, -1], np.float64)
    z = row - row.max()
    return z - np.log(np.exp(z).sum())


def serve_and_check(cfg, params, *, long_len: int, short_len: int,
                    shared_len: int, max_new: int, prefill_chunk: int,
                    max_len: int, num_blocks: int, max_batch: int,
                    block_size: int, seed: int = 0, log=print) -> list:
    """Drive the serving main path once; returns the failed checks."""
    import numpy as np
    from repro.serving import AsyncFrontend, ContinuousEngine

    assert cfg.dsa is not None and long_len > cfg.dsa.top_k, \
        "the long prompt must overflow DSA's top-k"
    rng = np.random.default_rng(seed)
    tok = lambda n: rng.integers(3, cfg.vocab_size, size=n)   # noqa: E731
    a = tok(short_len)
    prompts = {"long": tok(long_len), "a": a, "c": tok(short_len),
               # shares a's first shared_len tokens (mid-block): radix hit
               # plus COW fork, then a suffix as long as the short prompts
               "b": np.concatenate([a[:shared_len], tok(short_len)])}
    eng = ContinuousEngine(
        cfg, params, max_batch=max_batch, block_size=block_size,
        num_blocks=num_blocks, max_len=max_len, seed=seed,
        prefill_chunk=prefill_chunk, capture_logprobs=True,
        true_logprobs=True)
    fe = AsyncFrontend(eng, max_restarts=0)
    failures, done = [], {}

    def run(names):
        handles = {k: fe.submit(prompts[k], max_new=max_new) for k in names}
        for k, h in handles.items():
            try:
                done[k] = fe.result(h, timeout=1800)
            except Exception as e:   # noqa: BLE001 - any failure is a result
                failures.append(f"request {k}: {e!r}")

    t0 = time.perf_counter()
    try:
        run(["long", "a", "c"])
        run(["b"])                       # after a retired: its prefix cached
    finally:
        fe.close(timeout=60)
    log(f"serve: {time.perf_counter() - t0:.1f} s wall, "
        f"{eng.stats['compiles']} engine compiles")
    for k, r in done.items():
        log(f"request {k}: prompt {len(prompts[k])} tokens, status "
            f"{r.status}, {len(r.out)} tokens out: {list(map(int, r.out))}")
        if r.status != "ok" or len(r.out) != max_new:
            failures.append(f"request {k}: status {r.status}, "
                            f"{len(r.out)}/{max_new} tokens")
    stats = eng.stats
    for cond, what in [
            (stats["request_faults"] == 0,
             f"request_faults == {stats['request_faults']}"),
            (fe.restarts == 0, f"restarts == {fe.restarts}"),
            (fe.crashed is None, f"serve loop crashed: {fe.crashed!r}"),
            (stats["cached_tokens"] >= shared_len,
             f"cached_tokens == {stats['cached_tokens']} < {shared_len}"),
            (stats["cow_forks"] >= 1, f"cow_forks == {stats['cow_forks']}")]:
        if not cond:
            failures.append(what)
    if "a" in done and len(done["a"].out):
        ref = ref_first_logprobs(cfg, params, prompts["a"], block_size)
        t, lp = int(done["a"].out[0]), float(done["a"].out_logprobs[0])
        gap_lp = abs(lp - ref[t])
        gap_top = ref.max() - ref[t]
        log(f"first token of a: {t}; engine logprob {lp:.4f}, ref "
            f"{ref[t]:.4f} (|diff| {gap_lp:.4f}); ref argmax "
            f"{int(ref.argmax())}, {gap_top:.4f} above token {t}")
        if gap_lp > LOGPROB_TOL or gap_top > LOGPROB_TOL:
            failures.append(
                f"first token of a disagrees with the ref forward: "
                f"|logprob diff| {gap_lp:.4f}, ref argmax margin "
                f"{gap_top:.4f} (tol {LOGPROB_TOL})")
    return failures


def main() -> int:
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              f"this script runs only on the chip", file=sys.stderr)
        return 1
    import jax.numpy as jnp
    from jax import monitoring
    from repro.configs import get_config
    from repro.kernels.paged_attention.ops import (resolve_impl,
                                                   resolve_prefill_impl)
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    print(f"device: {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {cache_dir}")
    impls = (resolve_impl(None), resolve_prefill_impl(None))
    if impls != ("pallas", "pallas"):
        print(f"chip_smoke: paged attention resolved to {impls}, not the "
              f"Pallas kernels", file=sys.stderr)
        return 1

    t0 = time.perf_counter()
    failures = check_kernels(**KERNELS, **KERNEL_POOL)
    print(f"kernels: {time.perf_counter() - t0:.1f} s")

    cfg = get_config("yi_6b")
    t0 = time.perf_counter()
    params = build_params(cfg, seed=0, dtype=jnp.bfloat16)
    jax.block_until_ready(params)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"params: {nbytes} bytes bf16, built in "
          f"{time.perf_counter() - t0:.1f} s")
    failures += serve_and_check(cfg, params, **FULL)
    mem = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {mem.get('peak_bytes_in_use')}; "
          f"backend compiles: {len(compiles)} in {sum(compiles):.1f} s")
    if failures:
        for f in failures:
            print(f"FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
