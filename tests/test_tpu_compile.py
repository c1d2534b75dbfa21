"""AOT compiles of the paged-attention kernels for a described TPU v5e.

Interpret mode runs the kernels' Python on the CPU and never asks Mosaic
(the TPU kernel compiler) whether it accepts their block shapes or fits
their VMEM.  These tests compile each kernel — and the scanned decode step
that carries a donated pool — for one chip of a ``v5e:2x2`` topology that
the installed compiler can describe without the chip being attached.
Nothing runs; a refusal raises at ``.compile()``.

Widths are the published ones: yi-6b GQA (KVH 4, G 8, d 128) and its DSA
indexer (16 heads x 64), gemma2-2b GQA (KVH 4, G 2, d 256, window 4096,
softcap 50), and GLM-5 MLA (64 heads, lora 512, rope 64) for a prefill
chunk and for the MTP verify span (S = num_predict + 1).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_smoke_config
from repro.kernels.paged_attention import ops
from repro.kernels.paged_attention.kernel import (
    indexer_span_scores, paged_decode_gqa, paged_decode_mla,
    paged_indexer_scores_kernel)
from repro.kernels.paged_attention.prefill import (paged_prefill_gqa,
                                                   paged_prefill_mla)
from repro.models import get_model
from repro.utils import tree_bytes

os.environ.setdefault("TPU_LOG_DIR", "disabled")

BS = 16          # pool block size the engine serves with
MB = 256         # table width: 4096 tokens per sequence
B = 4            # decode batch
NB = B * MB + 1  # pool blocks incl. the trash block
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 - any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip are written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, sharding, *shapes, static=None):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn, static_argnames=static).lower(*args).compile()


_GQA = {"yi_6b": dict(kvh=4, g=8, d=128, window=0, softcap=0.0),
        "gemma2_2b": dict(kvh=4, g=2, d=256, window=4096, softcap=50.0)}


@pytest.mark.parametrize("arch", sorted(_GQA))
def test_paged_decode_gqa_compiles(one_chip, arch):
    c = _GQA[arch]
    pool = ((NB, BS, c["kvh"], c["d"]), BF16)
    _compile(lambda q, k, v, t, n: paged_decode_gqa(
        q, k, v, t, n, window=c["window"], softcap=c["softcap"]),
        one_chip, ((B, c["kvh"], c["g"], c["d"]), BF16), pool, pool,
        ((B, MB), jnp.int32), ((B,), jnp.int32))


# a whole chunk, and a chunk's ragged remainder (padded to whole tiles)
_SPANS = pytest.mark.parametrize("S", [256, 131], ids=["chunk", "ragged"])


@_SPANS
@pytest.mark.parametrize("arch", sorted(_GQA))
def test_paged_prefill_gqa_compiles(one_chip, arch, S):
    c = _GQA[arch]
    pool = ((NB, BS, c["kvh"], c["d"]), BF16)
    _compile(lambda q, k, v, t, st: paged_prefill_gqa(
        q, k, v, t, st, groups=c["g"], window=c["window"],
        softcap=c["softcap"]),
        one_chip, ((1, c["kvh"], S * c["g"], c["d"]), BF16), pool, pool,
        ((1, MB), jnp.int32), ((1,), jnp.int32))


H_MLA, LORA, ROPE = 64, 512, 64


def test_paged_decode_mla_compiles(one_chip):
    _compile(lambda ql, qr, c, kr, t, n: paged_decode_mla(
        ql, qr, c, kr, t, n, scale=0.07),
        one_chip, ((B, H_MLA, LORA), BF16), ((B, H_MLA, ROPE), BF16),
        ((NB, BS, LORA), BF16), ((NB, BS, ROPE), BF16),
        ((B, MB), jnp.int32), ((B,), jnp.int32))


@pytest.mark.parametrize("batch,S", [(1, 64), (1, 65), (B, 4)],
                         ids=["chunk64", "ragged65", "mtp_verify"])
def test_paged_prefill_mla_compiles(one_chip, batch, S):
    _compile(lambda ql, qr, c, kr, t, st: paged_prefill_mla(
        ql, qr, c, kr, t, st, heads=H_MLA, scale=0.07),
        one_chip, ((batch, S * H_MLA, LORA), BF16),
        ((batch, S * H_MLA, ROPE), BF16),
        ((NB, BS, LORA), BF16), ((NB, BS, ROPE), BF16),
        ((batch, MB), jnp.int32), ((batch,), jnp.int32))


HI, DI = 16, 64      # yi-6b DSA indexer


def test_paged_indexer_decode_compiles(one_chip):
    _compile(paged_indexer_scores_kernel, one_chip,
             ((B, HI, DI), BF16), ((B, HI), jnp.float32),
             ((NB, BS, DI), BF16), ((B, MB), jnp.int32), ((B,), jnp.int32))


@_SPANS
def test_paged_indexer_prefill_compiles(one_chip, S):
    _compile(lambda q, w, k, t, st: indexer_span_scores(
        q, w, k, t, st, heads=HI), one_chip,
        ((1, S * HI, DI), BF16), ((1, S * HI), jnp.float32),
        ((NB, BS, DI), BF16), ((1, MB), jnp.int32), ((1,), jnp.int32))


# ---------------------------------------------------------------------------
# scan-invariant pool: decode must not round-trip the pool through the scan
# ---------------------------------------------------------------------------

def _scanned_cfg():
    # first_k_dense=0: every layer rides the layer lax.scan
    return get_smoke_config("yi_6b").replace(
        d_model=128, num_heads=2, num_kv_heads=2, head_dim=32, d_ff=256,
        vocab_size=256, dsa=None, num_layers=2, first_k_dense=0)


def test_decode_step_donated_pool_no_oPool_copy(one_chip, monkeypatch):
    """Regression for the scan-copy hazard: with the pool donated, the
    compiled decode step's TEMP allocation must be independent of pool
    capacity and far below the pool size, and the pool must alias its
    output.  The old stacked-xs/ys layout materialized the whole pool as
    fresh scan outputs (temp growing with the pool) every step regardless
    of the in-place attention kernel.  Compiled for v5e with the decode
    dispatch steered onto the Pallas kernel the chip runs."""
    monkeypatch.setattr(
        ops, "_resolve", lambda impl: "ref" if impl == "ref" else "pallas")
    cfg = _scanned_cfg()
    model = get_model(cfg)
    B, bs = 2, 8

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda k: model.init(k, cfg)[0], jax.random.key(0)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,   # noqa: E731
                                          sharding=one_chip)

    def compiled_for(mb):
        pool = on_chip(model.init_paged_cache(cfg, B * mb + 1, bs,
                                              abstract=True)[0])
        step = jax.jit(lambda p, t, c, bt, ln: model.decode_step(
            p, t, cfg, c, ln, block_tables=bt), donate_argnums=(2,))
        c = step.lower(params, i32(B, 1), pool, i32(B, mb), i32(B)).compile()
        assert "tpu_custom_call" in c.as_text()     # the Pallas kernel
        return c.memory_analysis(), tree_bytes(pool)

    big, pool_bytes = compiled_for(128)
    small, _ = compiled_for(8)
    # temp must not grow with pool capacity (16x more blocks, same temp)...
    assert big.temp_size_in_bytes <= small.temp_size_in_bytes + 4096, \
        (small.temp_size_in_bytes, big.temp_size_in_bytes)
    # ...and stays far below the pool a scan round-trip would materialize
    assert big.temp_size_in_bytes < pool_bytes / 4, \
        (big.temp_size_in_bytes, pool_bytes)
    # and the donated pool is the step's output buffer, end to end
    assert big.alias_size_in_bytes >= pool_bytes, \
        (big.alias_size_in_bytes, pool_bytes)
