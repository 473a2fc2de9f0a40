"""Operation counts against XLA's own count of plain-jnp equivalents."""
import jax
import jax.numpy as jnp
import pytest

from bench import flops, kernels
from bench.reference import dense_llama as ref
from bench.tests import tiny


def cost(fn, *args):
    c = jax.jit(fn).lower(*args).compile().cost_analysis()
    c = c[0] if isinstance(c, (list, tuple)) else c
    return c["flops"], c["bytes accessed"]


@pytest.mark.parametrize("m,n,k", [(64, 96, 32), (128, 128, 256)])
def test_qmatmul_count_matches_xla(m, n, k):
    call = kernels.Call("qmatmul", [("u32", (2,)), ("f32", (m, k)),
                                    ("f32", (k, n))], [("f32", (m, n))])
    xf, xb = cost(jnp.dot, jnp.ones((m, k)), jnp.ones((k, n)))
    assert kernels.call_flops(call) == pytest.approx(xf)
    # the kernel also reads its two seed words
    assert call.bytes == pytest.approx(xb + 8)


def test_attention_flops_are_the_kept_part_of_xlas_count():
    bh, s, d = 3, 64, 16
    q = jnp.ones((bh, s, d))
    qk, _ = cost(lambda q, k: jnp.einsum("hqd,hkd->hqk", q, k), q, q)
    p = jnp.ones((bh, s, s))
    pv, _ = cost(lambda p, v: jnp.einsum("hqk,hkd->hqd", p, v), p, q)
    full = flops.attention_flops("fwd", bh, s, s, d, d, causal=False)
    assert full == pytest.approx(qk + pv)
    causal = flops.attention_flops("fwd", bh, s, s, d, d, causal=True)
    assert causal == pytest.approx(full * (s + 1) / (2 * s))
    win = flops.attention_flops("fwd", bh, s, s, d, d, causal=True,
                                window=8)
    assert win < causal
    assert flops.causal_pairs(4, 4, 0, 2) == 1 + 2 + 2 + 2


def test_model_flops_per_token_match_xla_on_the_matmuls():
    # one layer: XLA counts a scan's body once, however often it runs
    cfg = tiny.config(sliding_window=0, num_hidden_layers=1)
    params = ref.init_params(cfg, 0)
    seq = 32
    toks = jnp.zeros((seq,), jnp.int32)
    # the reference's forward with attention products counted over all
    # pairs: XLA counts masked pairs too, so subtract the masked part
    fwd, _ = cost(lambda p, t: ref.sequence_logits(cfg, p, t), params, toks)
    per_tok = ref.train_flops_per_token(cfg, seq) / 3.0
    nh, hd = cfg["num_attention_heads"], cfg["head_dim"]
    masked = 2 * 2 * nh * hd * (seq * seq - flops.causal_pairs(seq, seq))
    masked *= cfg["num_hidden_layers"]
    # XLA also counts elementwise work (norms, softmax, rope): the
    # matmul count must stay below its total and above its GEMM part
    gemm_only = per_tok * seq
    assert gemm_only <= fwd - masked
    assert gemm_only >= 0.5 * (fwd - masked)


def test_kernel_signatures_and_counts():
    hlo = ('  %a.1 = f32[64,32]{1,0} custom-call(%s, %x, %w), custom_call_'
           'target="tpu_custom_call", operand_layout_constraints={u32[2]{0}'
           ', f32[64,16]{1,0}, f32[16,32]{1,0}}, frontend_attributes={}\n'
           '  %b.2 = (f32[6,128,16]{2,1,0}, f32[6,1,128]{2,1,0}, f32[6,1,12'
           '8]{2,1,0}) custom-call(%s, %q, %k, %v), custom_call_target="tpu'
           '_custom_call", operand_layout_constraints={u32[6,6]{1,0}, f32[6'
           ',128,16]{2,1,0}, f32[2,128,16]{2,1,0}, f32[2,128,16]{2,1,0}}, b'
           'ackend_config={}\n')
    calls = kernels.custom_calls(hlo)
    assert calls["a.1"].kind == "qmatmul"
    assert kernels.call_flops(calls["a.1"]) == 2 * 64 * 32 * 16
    assert calls["a.1"].bytes == 4 * (2 + 64 * 16 + 16 * 32 + 64 * 32)
    assert calls["b.2"].kind == "flash_fwd"
    assert kernels.call_flops(calls["b.2"]) == pytest.approx(
        flops.attention_flops("fwd", 6, 128, 128, 16, 16, causal=True))
