"""Pallas flash-attention kernel parity tests — REAL TPU ONLY.

The CPU suite (conftest forces the virtual CPU platform) skips these; run
manually on the TPU env: ``python -m pytest tests/test_flash_attention_tpu.py
-q -p no:cacheprovider --noconftest`` or via the verify drive. Parity target:
the XLA reference formulation, bf16 tolerances.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.ops.pallas.flash_attention as F

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="pallas kernels run on TPU only")


@pytest.mark.parametrize("causal", [True, False])
# S=512 takes the single-block straight-line kernels (seq == block); S=1024
# exercises the multi-block online-softmax loop and its causal block-skip
# bounds — keep BOTH paths covered.
@pytest.mark.parametrize("S", [512, 1024])
def test_flash_fwd_bwd_parity(causal, S):
    rng = np.random.RandomState(0)
    B, H, D = 2, 4, 64
    q = jnp.array(rng.randn(B, S, H, D), jnp.bfloat16)
    k = jnp.array(rng.randn(B, S, H, D), jnp.bfloat16)
    v = jnp.array(rng.randn(B, S, H, D), jnp.bfloat16)
    g = jnp.array(rng.randn(B, S, H, D), jnp.bfloat16)

    def f_pallas(q, k, v):
        out = F._flash_custom_vjp(q, k, v, causal)
        return jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32))

    def f_ref(q, k, v):
        out = F._xla_attention(q, k, v, is_causal=causal)
        return jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32))

    out_p = jax.jit(lambda q, k, v: F._flash_custom_vjp(q, k, v, causal))(
        q, k, v).astype(jnp.float32)
    out_r = F._xla_attention(q, k, v, is_causal=causal).astype(jnp.float32)
    assert float(jnp.max(jnp.abs(out_p - out_r))) < 0.03

    gp = jax.jit(jax.grad(f_pallas, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(f_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gp, gr):
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
        rel = float(jnp.max(jnp.abs(a - b))) / max(
            1e-6, float(jnp.max(jnp.abs(b))))
        assert rel < 0.02, rel


class TestPackedLayout:
    """Packed flat-layout kernels ([B,S,H*D], 128//D heads per cell) must
    match the blocked [B*H,S,D] kernels they replace on eligible shapes."""

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("H,D", [(12, 64), (4, 128), (6, 64)])
    def test_packed_vs_blocked_parity(self, causal, H, D):
        rng = np.random.RandomState(1)
        B, S = 2, 512
        q = jnp.array(rng.randn(B, S, H, D), jnp.bfloat16)
        k = jnp.array(rng.randn(B, S, H, D), jnp.bfloat16)
        v = jnp.array(rng.randn(B, S, H, D), jnp.bfloat16)
        g = jnp.array(rng.randn(B, S, H, D), jnp.bfloat16)
        assert F._packed_eligible(q, k)

        out_p, lse_p = jax.jit(
            lambda q, k, v: F._pallas_flash_fwd_packed(q, k, v, causal))(
                q, k, v)
        # blocked path, forced via explicit block sizes
        out_b, lse_b = jax.jit(
            lambda q, k, v: F._pallas_flash_attention(
                q, k, v, is_causal=causal, block_q=min(512, S),
                block_k=min(512, S), with_lse=True))(q, k, v)
        assert float(jnp.max(jnp.abs(out_p.astype(jnp.float32)
                                     - out_b.astype(jnp.float32)))) < 0.03

        dq_p, dk_p, dv_p = jax.jit(
            lambda q, k, v, g: F._pallas_flash_bwd_packed(
                q, k, v, g, out_p, lse_p, causal))(q, k, v, g)
        dq_b, dk_b, dv_b = jax.jit(
            lambda q, k, v, g: F._pallas_flash_bwd(
                q, k, v, g, out_b, lse_b, causal))(q, k, v, g)
        for a, b in zip((dq_p, dk_p, dv_p), (dq_b, dk_b, dv_b)):
            a = a.astype(jnp.float32)
            b = b.astype(jnp.float32)
            rel = float(jnp.max(jnp.abs(a - b))) / max(
                1e-6, float(jnp.max(jnp.abs(b))))
            assert rel < 0.02, rel

    def test_gqa_and_cross_len_stay_off_packed(self):
        rng = np.random.RandomState(2)
        q = jnp.array(rng.randn(2, 512, 8, 64), jnp.bfloat16)
        k_gqa = jnp.array(rng.randn(2, 512, 2, 64), jnp.bfloat16)
        assert F._packed_eligible(q, k_gqa) == 0  # unrepeated GQA kv
        k_short = jnp.array(rng.randn(2, 256, 8, 64), jnp.bfloat16)
        assert F._packed_eligible(q, k_short) == 0  # sq != sk (decode)
