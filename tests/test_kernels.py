"""Per-kernel shape/dtype sweeps: Pallas (interpret mode on CPU) vs the
pure-jnp oracles in repro.kernels.ref."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.quant.delta_pot import (
    FORMAT_W8, dpot_quantize, dpot_pack_int8)
from repro.kernels import (
    dpot_matmul, fused_layernorm, wkv4_pallas, wkv6_pallas,
    exp_kernel, sigmoid_kernel)
from repro.kernels import ref as R


class TestDpotMatmul:
    @pytest.mark.parametrize("M,K,N,bm,bn,bk", [
        (8, 128, 128, 8, 128, 128),
        (16, 256, 256, 8, 128, 128),
        (4, 512, 128, 4, 64, 256),
        (128, 128, 384, 64, 128, 128),
    ])
    def test_shapes(self, rng, M, K, N, bm, bn, bk):
        x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
        q = dpot_quantize(w, FORMAT_W8, axis=1)
        packed, scale = dpot_pack_int8(q), q.scale[0]
        got = dpot_matmul(x, packed, scale, bm=bm, bn=bn, bk=bk)
        want = R.dpot_matmul_ref(x, packed, scale)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, rng, dtype):
        x = jnp.asarray(rng.normal(size=(8, 128)), dtype)
        w = jnp.asarray(rng.normal(size=(128, 128)), jnp.float32)
        q = dpot_quantize(w, FORMAT_W8, axis=1)
        got = dpot_matmul(x, dpot_pack_int8(q), q.scale[0])
        assert got.dtype == dtype
        want = R.dpot_matmul_ref(x, dpot_pack_int8(q), q.scale[0])
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=2e-2, atol=2e-2)

    def test_quantized_matmul_close_to_fp(self, rng):
        """End-to-end: Δ-PoT W8 matmul ~ the fp matmul (the paper's
        accuracy-preservation claim at the kernel level)."""
        x = jnp.asarray(rng.normal(size=(16, 256)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(256, 128)) * 0.05, jnp.float32)
        q = dpot_quantize(w, FORMAT_W8, axis=1)
        got = dpot_matmul(x, dpot_pack_int8(q), q.scale[0])
        fp = x @ w
        rel = np.linalg.norm(np.asarray(got - fp)) / \
            np.linalg.norm(np.asarray(fp))
        # ~5.9% relative weight error is intrinsic to a 2-term PoT grid on
        # Gaussian weights (cf. Table 1: proposed ~ FP16 on accuracy, not
        # bit-exact); the matmul must not amplify it
        assert rel < 0.09


class TestFusedLayernorm:
    @pytest.mark.parametrize("shape", [(4, 128), (2, 16, 256), (1, 512)])
    def test_shapes(self, rng, shape):
        x = jnp.asarray(rng.normal(size=shape) * 3 + 1, jnp.float32)
        g = jnp.asarray(rng.normal(size=shape[-1:]), jnp.float32)
        b = jnp.asarray(rng.normal(size=shape[-1:]), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(fused_layernorm(x, g, b)),
            np.asarray(R.fused_layernorm_ref(x, g, b)),
            rtol=1e-5, atol=1e-5)

    def test_bf16(self, rng):
        x = jnp.asarray(rng.normal(size=(8, 128)), jnp.bfloat16)
        g = jnp.ones((128,), jnp.float32)
        b = jnp.zeros((128,), jnp.float32)
        got = fused_layernorm(x, g, b)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32),
            np.asarray(R.fused_layernorm_ref(x, g, b), np.float32),
            rtol=2e-2, atol=2e-2)


class TestWkv4Kernel:
    @pytest.mark.parametrize("B,T,C,bc", [
        (1, 16, 64, 64), (2, 32, 128, 64), (2, 64, 64, 32),
    ])
    def test_vs_ref(self, rng, B, T, C, bc):
        k = jnp.asarray(rng.normal(size=(B, T, C)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, T, C)), jnp.float32)
        w = jnp.asarray(np.abs(rng.normal(size=(C,))) + 0.05, jnp.float32)
        u = jnp.asarray(rng.normal(size=(C,)), jnp.float32)
        y, (a, b, o) = wkv4_pallas(k, v, w, u, bc=bc)
        yr, (ar, br, orr) = R.wkv4_ref(k, v, w, u)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(a), np.asarray(ar),
                                   rtol=1e-5, atol=1e-5)

    def test_state_chaining(self, rng):
        """Kernel(half2, state=Kernel(half1)) == Kernel(full)."""
        B, T, C = 1, 32, 64
        k = jnp.asarray(rng.normal(size=(B, T, C)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, T, C)), jnp.float32)
        w = jnp.asarray(np.abs(rng.normal(size=(C,))) + 0.05, jnp.float32)
        u = jnp.asarray(rng.normal(size=(C,)), jnp.float32)
        y_full, _ = wkv4_pallas(k, v, w, u)
        y1, (a, b, o) = wkv4_pallas(k[:, :16], v[:, :16], w, u)
        y2, _ = wkv4_pallas(k[:, 16:], v[:, 16:], w, u, a, b, o)
        np.testing.assert_allclose(
            np.asarray(y_full),
            np.asarray(jnp.concatenate([y1, y2], 1)), rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("mode", ["plain", "masked", "masked-bf16",
                                      "masked-bf16-lut"])
    @pytest.mark.parametrize("C", [64, 256, 768])
    @pytest.mark.parametrize("B", [1, 3, 8, 12, 32])
    def test_bitwise_vs_step_scan(self, B, C, mode):
        """Every batch tiling (fewer lanes than the f32 sublanes, whole
        sublane blocks, a batch padded to them) is bit-identical to a
        `lax.scan` of `wkv4_step` with the serving path's masked commits
        and dtype snaps, for random prefix masks."""
        from repro.core.approx.units import DIV_LUT_TABLE, EXP_LUT_TABLE
        from repro.core.approx import div_lut, exp_lut
        from repro.core.wkv import WKV4State, wkv4_step
        from repro.kernels.common import exact_jit
        T = 12
        rng = np.random.default_rng(B * 1000 + C)
        f32 = lambda x: jnp.asarray(x, jnp.float32)
        k, v = f32(rng.normal(size=(2, B, T, C)) * 2)
        w, u = f32(np.exp(rng.normal(size=C))), f32(rng.normal(size=C))
        a0, o0 = f32(rng.normal(size=(2, B, C)))
        b0 = f32(np.abs(rng.normal(size=(B, C))))
        n = rng.integers(0, T + 1, B)
        valid = jnp.asarray(np.arange(T)[None] < n[:, None])
        carry = "bfloat16" if "bf16" in mode else None
        snap = ((lambda x: x) if carry is None else
                (lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)))
        kw, exp, div = {}, jnp.exp, None
        if "lut" in mode:
            et = f32(np.reshape(EXP_LUT_TABLE, -1))
            dt = f32(np.reshape(DIV_LUT_TABLE, -1))
            kw = {"exp_table": et, "div_table": dt}
            exp = lambda x: exp_lut(x, table=et)
            div = lambda x, y: div_lut(x, y, table=dt)

        def oracle(k, v, a0, b0, o0, valid):
            def body(st, xs):
                kt, vt, ok = xs
                new, y = wkv4_step(st, kt, vt, w, u, exp=exp, div=div)
                if mode != "plain":
                    new = [jnp.where(ok[:, None], nw, old)
                           for nw, old in zip(new, st)]
                return WKV4State(*map(snap, new)), y
            fin, ys = jax.lax.scan(
                body, WKV4State(a0, b0, o0),
                (jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1), valid.T))
            return jnp.swapaxes(ys, 0, 1), tuple(fin)

        def kernel(k, v, a0, b0, o0, valid):
            return wkv4_pallas(k, v, w, u, a0, b0, o0, carry_dtype=carry,
                               valid=None if mode == "plain" else valid,
                               **kw)

        args = (k, v, a0, b0, o0, valid)
        got = jax.tree_util.tree_leaves(exact_jit(kernel)(*args))
        want = jax.tree_util.tree_leaves(exact_jit(oracle)(*args))
        for g, x in zip(got, want):
            assert g.shape == x.shape
            np.testing.assert_array_equal(np.asarray(g), np.asarray(x))


class TestWkv6Kernel:
    @pytest.mark.parametrize("B,T,H,N,chunk", [
        (1, 32, 2, 16, 16), (2, 64, 2, 32, 32), (1, 128, 1, 64, 64),
    ])
    def test_vs_ref(self, rng, B, T, H, N, chunk):
        r = jnp.asarray(rng.normal(size=(B, T, H, N)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, T, H, N)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, T, H, N)), jnp.float32)
        w = jnp.asarray(rng.uniform(0.2, 0.99, (B, T, H, N)), jnp.float32)
        u = jnp.asarray(rng.normal(size=(H, N)), jnp.float32)
        y, s = wkv6_pallas(r, k, v, w, u, chunk=chunk)
        yr, sr = R.wkv6_ref(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("carry", [None, "bfloat16"])
    def test_sequence_kernel_skips_lanes_with_no_valid_step(self, rng,
                                                            carry):
        """Lanes with no valid step are not walked: their state leaves as
        it came (snapped to the carry dtype) and their y is 0; every other
        lane is bit for bit what the kernel gives it alone."""
        from repro.kernels.wkv6 import wkv6_seq_pallas
        B, T, H, N = 4, 8, 2, 16
        f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
        r, k, v = f32(B, T, H, N), f32(B, T, H, N), f32(B, T, H, N)
        w = jnp.asarray(rng.uniform(0.2, 0.99, (B, T, H, N)), jnp.float32)
        u, s0 = f32(H, N), f32(B, H, N, N)
        valid = jnp.asarray(np.arange(T)[None] < np.array([[T], [0], [3],
                                                           [0]]))
        y, s = wkv6_seq_pallas(r, k, v, w, u, s0, valid=valid,
                               carry_dtype=carry)
        snap = s0 if carry is None else s0.astype(carry).astype(jnp.float32)
        for b in (1, 3):
            np.testing.assert_array_equal(np.asarray(y[b]), 0.0)
            np.testing.assert_array_equal(np.asarray(s[b]),
                                          np.asarray(snap[b]))
        for b in (0, 2):
            one = lambda a: a[b:b + 1]
            yb, sb = wkv6_seq_pallas(one(r), one(k), one(v), one(w), u,
                                     one(s0), valid=one(valid),
                                     carry_dtype=carry)
            np.testing.assert_array_equal(np.asarray(y[b]),
                                          np.asarray(yb[0]))
            np.testing.assert_array_equal(np.asarray(s[b]),
                                          np.asarray(sb[0]))


class TestExpSigKernels:
    @pytest.mark.parametrize("n", [100, 4096, 5000])
    def test_exp(self, rng, n):
        x = jnp.asarray(rng.normal(size=(n,)) * 4, jnp.float32)
        np.testing.assert_allclose(np.asarray(exp_kernel(x)),
                                   np.asarray(R.exp_ref(x)),
                                   rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("n", [100, 4096])
    def test_sigmoid(self, rng, n):
        x = jnp.asarray(rng.normal(size=(n,)) * 4, jnp.float32)
        np.testing.assert_allclose(np.asarray(sigmoid_kernel(x)),
                                   np.asarray(R.sigmoid_ref(x)),
                                   rtol=1e-6, atol=1e-6)


class TestFlashAttention:
    @pytest.mark.parametrize("B,Sq,H,KVH,d,causal,bq,bkv", [
        (2, 64, 4, 4, 32, True, 32, 32),
        (1, 128, 4, 2, 64, True, 64, 32),
        (2, 32, 2, 2, 16, False, 32, 32),
        (1, 256, 8, 1, 64, True, 128, 64),
    ])
    def test_vs_ref(self, rng, B, Sq, H, KVH, d, causal, bq, bkv):
        from repro.kernels import flash_attention
        from repro.kernels.ref import flash_attention_ref
        q = jnp.asarray(rng.normal(size=(B, Sq, H, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, Sq, KVH, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, Sq, KVH, d)), jnp.float32)
        got = flash_attention(q, k, v, causal=causal, bq=bq, bkv=bkv)
        want = flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_bf16(self, rng):
        from repro.kernels import flash_attention
        from repro.kernels.ref import flash_attention_ref
        q = jnp.asarray(rng.normal(size=(1, 64, 2, 32)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(1, 64, 2, 32)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(1, 64, 2, 32)), jnp.bfloat16)
        got = flash_attention(q, k, v, bq=32, bkv=32)
        want = flash_attention_ref(q, k, v)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=3e-2, atol=3e-2)

    def test_gradients_vs_ref(self, rng):
        """Custom-VJP backward kernels (dq / dkv) match autodiff of the
        oracle — through the GQA repeat."""
        from repro.kernels import flash_attention
        from repro.kernels.ref import flash_attention_ref
        q = jnp.asarray(rng.normal(size=(1, 128, 4, 32)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 128, 2, 32)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 128, 2, 32)), jnp.float32)

        def l_kernel(q, k, v):
            return jnp.sum(jnp.sin(flash_attention(q, k, v, bq=64, bkv=32)))

        def l_ref(q, k, v):
            return jnp.sum(jnp.sin(flash_attention_ref(q, k, v)))

        g1 = jax.grad(l_kernel, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(l_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)


class TestFusedCrossEntropy:
    @pytest.mark.parametrize("N,V,bn,bv", [
        (64, 1000, 32, 250), (32, 4096, 32, 1024), (16, 512, 16, 512),
    ])
    def test_vs_ref(self, rng, N, V, bn, bv):
        from repro.kernels import fused_cross_entropy
        from repro.kernels.ref import fused_cross_entropy_ref
        x = jnp.asarray(rng.normal(size=(N, V)) * 3, jnp.float32)
        lbl = jnp.asarray(rng.integers(0, V, N), jnp.int32)
        got = fused_cross_entropy(x, lbl, bn=bn, bv=bv)
        want = fused_cross_entropy_ref(x, lbl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_gradient_vs_ref(self, rng):
        from repro.kernels import fused_cross_entropy
        from repro.kernels.ref import fused_cross_entropy_ref
        x = jnp.asarray(rng.normal(size=(32, 512)) * 2, jnp.float32)
        lbl = jnp.asarray(rng.integers(0, 512, 32), jnp.int32)
        g1 = jax.grad(lambda a: jnp.sum(
            jnp.sin(fused_cross_entropy(a, lbl, bn=16, bv=128))))(x)
        g2 = jax.grad(lambda a: jnp.sum(
            jnp.sin(fused_cross_entropy_ref(a, lbl))))(x)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-4, atol=1e-5)

    def test_batched_bf16(self, rng):
        from repro.kernels import fused_cross_entropy
        from repro.kernels.ref import fused_cross_entropy_ref
        x = jnp.asarray(rng.normal(size=(2, 16, 512)), jnp.bfloat16)
        lbl = jnp.asarray(rng.integers(0, 512, (2, 16)), jnp.int32)
        got = fused_cross_entropy(x, lbl)
        want = fused_cross_entropy_ref(x, lbl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-2, atol=1e-2)
