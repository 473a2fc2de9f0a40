"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: each kernel is lowered with ``interpret=False`` and compiled
by the TPU compiler for a chip that is described, not attached, at
smollm-360m's real shapes (batch 4 x seq 2048; d_model 960, 15 heads of 64
over 5 kv heads, d_ff 2560, vocab 49152).  That catches what interpret mode
cannot: block shapes Mosaic refuses, unsupported casts or PRNG calls, and
kernels that overflow VMEM.

The topology is described inside a module fixture (never at import), since
only one process at a time may load the TPU library; every compile happens
in this process with the persistent compilation cache off, whose entries
for a described chip could not be read back.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.gd import GDRounding
from repro.core.rounding import spec
from repro.kernels import flash_attention as FA
from repro.kernels.fused_update import fused_qupdate_prng_p
from repro.kernels.qmatmul import qmatmul_prng_p

TOKENS = 4 * 2048
D, KV_D, FF, VOCAB = 960, 320, 2560, 49152
BH, BKV, HD = 4 * 15, 4 * 5, 64
N_PARAMS = 361_821_120          # smollm-360m, tied embeddings
SR8 = spec("binary8", "sr")
ATTN = FA.AttnSpecs(SR8, SR8, SR8)
ATTN_KW = dict(scale=HD ** -0.5, n_heads=15, n_kv=5, q_block=1024,
               kv_block=1024, interpret=False)


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


F32, U32, I32 = jnp.float32, jnp.uint32, jnp.int32
SEED = ((2,), U32)


@pytest.mark.parametrize("m,k,n", [
    (TOKENS, D, D),             # q / o projections
    (TOKENS, D, KV_D),          # k / v projections
    (TOKENS, D, FF),            # gate / up
    (TOKENS, FF, D),            # down
    (TOKENS, D, VOCAB),         # lm head
    (D, TOKENS, FF),            # a weight-gradient GEMM (K = tokens)
])
def test_qmatmul_prng_compiles(chip, m, k, n):
    _compile(chip, lambda a, b, s: qmatmul_prng_p(
        a, b, s, "binary8", "sr", interpret=False),
        ((m, k), F32), ((k, n), F32), SEED)


def test_fused_qupdate_prng_compiles_whole_model(chip):
    cfg = GDRounding(grad=spec("bfloat16", "rn"),
                     mul=spec("bfloat16", "sr"),
                     sub=spec("bfloat16", "signed_sr_eps", 0.1),
                     sub_v="grad")
    _compile(chip, lambda x, g, s: fused_qupdate_prng_p(
        x, g, 0.05, s, cfg, interpret=False),
        ((N_PARAMS,), F32), ((N_PARAMS,), F32), SEED)


def test_flash_fwd_compiles(chip):
    _compile(chip, lambda q, k, v, s: FA.flash_fwd_p(
        q, k, v, s, ATTN, **ATTN_KW),
        ((BH, 2048, HD), F32), ((BKV, 2048, HD), F32),
        ((BKV, 2048, HD), F32), ((BH, 6), U32))


@pytest.mark.parametrize("kernel", ["dq", "dkv"])
def test_flash_bwd_compiles(chip, kernel):
    res = [((BH, 2048), F32)] * 3
    ops = [((BH, 2048, HD), F32), ((BKV, 2048, HD), F32),
           ((BKV, 2048, HD), F32), ((BH, 2048, HD), F32)] + res
    if kernel == "dq":
        _compile(chip, lambda *a: FA.flash_bwd_dq_p(
            *a, SR8, SR8, **ATTN_KW), *ops, ((BH, 4), U32))
    else:
        _compile(chip, lambda *a: FA.flash_bwd_dkv_p(
            *a, SR8, SR8, SR8, **ATTN_KW), *ops, ((BH, 6), U32))


def test_flash_decode_paged_packed_compiles(chip):
    # 8 requests of up to 1056 tokens in 64-token pages of e4m3 codes
    b, kv, g, page, n_max, pages = 8, 5, 3, 64, 17, 8 * 17 + 1
    _compile(chip, lambda q, kp, vp, s, ln, tb: FA.flash_decode_paged_p(
        q, kp, vp, s, ln, tb, ATTN, scale=HD ** -0.5, n_kv=kv,
        kv_fmt="e4m3", interpret=False),
        ((b * kv, g, HD), F32), ((pages * kv, page, HD), jnp.uint8),
        ((pages * kv, page, HD), jnp.uint8), ((b * kv, 6), U32),
        ((b,), I32), ((b, n_max), I32))


def test_wire_codec_bits_stay_lane_dense(chip, monkeypatch):
    """The e4m3-SR gradient wire draws counter bits for a whole leaf; for
    the stacked FFN weight (32 x 960 x 2560) those bits must not pass
    through a (..., 2) array, whose minor dimension the TPU pads to 128
    lanes (a 20 GB buffer for this leaf)."""
    from repro.dist import codecs
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    codec = codecs.get_wire_codec("e4m3-sr")
    shape = (32, D, FF)
    args = [jax.ShapeDtypeStruct(shape, F32, sharding=chip),
            jax.ShapeDtypeStruct((2,), U32, sharding=chip)]
    compiled = jax.jit(lambda g, w: codec.quantize(
        g, bits=codecs.codec_bits(codec, w, g.shape))).lower(*args).compile()
    leaf_bytes = 4 * 32 * D * FF
    assert compiled.memory_analysis().temp_size_in_bytes <= 4 * leaf_bytes
