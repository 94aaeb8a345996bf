"""Quantization library tests: Δ-PoT (paper C1), uniform (C2), baselines,
and the mixed-precision policy — including hypothesis property tests."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:   # optional dep: property tests importorskip at run time
    from conftest import hypothesis_stubs
    given, settings, st = hypothesis_stubs()

from repro.core.quant.delta_pot import (
    DPotFormat, FORMAT_W9, FORMAT_W8, FORMAT_W4, FORMAT_POT4, dpot_levels,
    dpot_max_level, dpot_quantize, dpot_dequantize, dpot_fake_quant,
    dpot_pack_int8, dpot_unpack_int8, dpot_decode_codes)
from repro.core.quant.uniform import (
    uniform_quantize, uniform_dequantize, uniform_fake_quant)
from repro.core.quant.schemes import (
    rtn_fake_quant, pot_fake_quant, logq_fake_quant, proposed_fake_quant)
from repro.core.quant.policy import (
    QuantPolicy, classify_param, fake_quantize_tree, quantize_tree,
    dequantize_tree)
from repro.models.registry import get_model


# ---------------------------------------------------------------------------
# Δ-PoT format / levels
# ---------------------------------------------------------------------------


class TestDPotLevels:
    def test_paper_example(self):
        """§3.1: Δ-PoT with ks=(2,2) can represent 2^-1 + 2^-3 exactly."""
        lv = np.asarray(dpot_levels(DPotFormat((2, 2))))
        assert np.any(np.isclose(lv, 0.5 + 0.125))

    def test_zero_code_is_zero(self):
        for fmt in (FORMAT_W9, FORMAT_W8, FORMAT_POT4):
            assert float(dpot_levels(fmt)[0]) == 0.0

    def test_terms_decreasing(self):
        """Every level is a sum of strictly decreasing PoT terms => every
        level is < 2 * first term <= 1."""
        for fmt in (FORMAT_W9, FORMAT_W8):
            lv = np.asarray(dpot_levels(fmt))
            assert lv.max() <= 1.0
            assert lv.min() >= 0.0

    def test_pot_degenerate(self):
        """Single-term Δ-PoT == classic PoT grid {0} ∪ {2^-q}."""
        lv = sorted(set(np.asarray(dpot_levels(FORMAT_POT4)).tolist()))
        expect = [0.0] + [2.0 ** (-q) for q in range(15, 0, -1)]
        assert np.allclose(lv, expect)

    def test_wider_range_than_apot_equal_bits(self):
        """Differential encoding covers exponents down to 2^-(2^k0-1 + 2^k1-1),
        deeper than APoT's fixed stride at the same bit budget."""
        lv = np.asarray(dpot_levels(DPotFormat((4, 4))))
        nz = lv[lv > 0]
        assert nz.min() <= 2.0 ** -15


class TestDPotQuantize:
    def test_roundtrip_exact_levels(self, rng):
        """Values that ARE representable levels must roundtrip exactly."""
        fmt = FORMAT_W9
        lv = np.asarray(dpot_levels(fmt))
        scale = 1.7
        # well-separated levels (the deepest ones differ at f32 epsilon and
        # legitimately round to neighbours)
        vals = np.unique(lv[lv >= 2.0 ** -8]) * scale
        w = jnp.asarray(np.concatenate([vals, -vals]))
        q = dpot_quantize(w, fmt, axis=None)
        # scale covers max|w|; nearest-level must land on exact values
        got = np.asarray(dpot_dequantize(q))
        np.testing.assert_allclose(got, np.asarray(w), rtol=1e-6)

    def test_error_bounded_by_half_gap(self, rng):
        fmt = FORMAT_W9
        w = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
        q = dpot_quantize(w, fmt, axis=1)
        err = np.abs(np.asarray(dpot_dequantize(q)) - np.asarray(w))
        # per-channel worst error <= half the largest level gap * scale
        lv = np.sort(np.unique(np.asarray(dpot_levels(fmt))))
        max_gap = np.max(np.diff(lv))
        scale = np.asarray(q.scale)
        assert np.all(err <= 0.5 * max_gap * scale + 1e-6)

    def test_per_channel_scales(self, rng):
        w = jnp.asarray(rng.normal(size=(8, 32)), jnp.float32)
        q = dpot_quantize(w, FORMAT_W9, axis=1)
        assert q.scale.shape == (1, 32)

    def test_mse_search_not_worse(self, rng):
        w = jnp.asarray(rng.standard_t(3, size=(256,)), jnp.float32)
        base = dpot_fake_quant(w, (4, 4), None, False)
        ref = dpot_fake_quant(w, (4, 4), None, True)
        e0 = float(jnp.mean((base - w) ** 2))
        e1 = float(jnp.mean((ref - w) ** 2))
        assert e1 <= e0 * 1.0001

    def test_straight_through_gradient(self):
        g = jax.grad(lambda w: jnp.sum(dpot_fake_quant(w, (4, 4), None,
                                                       False)))(
            jnp.ones((4, 4)))
        np.testing.assert_allclose(np.asarray(g), 1.0)

    @given(st.integers(1, 4), st.integers(1, 4), st.integers(0, 10_000))
    @settings(max_examples=30, deadline=None)
    def test_decode_matches_table(self, k0, k1, seed):
        """Property: vectorized decoder == enumerated level table."""
        fmt = DPotFormat((k0, k1))
        rng = np.random.default_rng(seed)
        codes = rng.integers(0, fmt.n_codes, size=(32,)).astype(np.uint8)
        got = np.asarray(dpot_decode_codes(jnp.asarray(codes), fmt.ks))
        want = np.asarray(dpot_levels(fmt))[codes]
        np.testing.assert_allclose(got, want, rtol=1e-6)

    @given(st.integers(0, 10_000))
    @settings(max_examples=25, deadline=None)
    def test_quantize_idempotent(self, seed):
        """Property: fake-quant is idempotent (q(q(x)) == q(x))."""
        rng = np.random.default_rng(seed)
        w = jnp.asarray(rng.normal(size=(64,)), jnp.float32)
        q1 = dpot_fake_quant(w, (4, 4), None, False)
        q2 = dpot_fake_quant(q1, (4, 4), None, False)
        np.testing.assert_allclose(np.asarray(q1), np.asarray(q2),
                                   rtol=1e-5, atol=1e-7)

    def test_pack_unpack_int8(self, rng):
        w = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
        q = dpot_quantize(w, FORMAT_W8, axis=1)
        packed = dpot_pack_int8(q)
        q2 = dpot_unpack_int8(packed, q.scale, FORMAT_W8.ks)
        np.testing.assert_array_equal(np.asarray(q.codes),
                                      np.asarray(q2.codes))
        np.testing.assert_array_equal(np.asarray(q.signs),
                                      np.asarray(q2.signs))

    def test_pack_rejects_w9(self, rng):
        q = dpot_quantize(jnp.ones((4, 4)), FORMAT_W9)
        with pytest.raises(ValueError):
            dpot_pack_int8(q)

    @pytest.mark.parametrize("fmt", [FORMAT_W8, FORMAT_W9, FORMAT_W4,
                                     FORMAT_POT4, DPotFormat((2, 2))],
                             ids=lambda f: str(f.ks))
    def test_closed_form_code_equals_search(self, fmt):
        """The closed-form nearest code is the midpoint search's code at
        every level and every midpoint, one ulp either side of each, and
        off the grid in and out of range (ties go to the lower level)."""
        from repro.core.quant.delta_pot import (
            _closed_form_code, _searched_code, _sorted_levels_np)
        levels, _, mids = _sorted_levels_np(fmt.ks)
        pts = np.concatenate([mids, levels]).astype(np.float32)
        r = np.random.default_rng(0)
        y = np.concatenate([
            pts, np.nextafter(pts, np.float32(0)),
            np.nextafter(pts, np.float32(2)),
            r.uniform(0, 1, 20_000).astype(np.float32),
            np.exp(r.uniform(np.log(1e-12), 0.5, 20_000)).astype(np.float32),
            np.array([0.0, 1e-45, 1.0, 2.0, 1e30, np.inf], np.float32)])
        got = np.asarray(_closed_form_code(jnp.asarray(y), fmt.ks))
        want = np.asarray(_searched_code(jnp.asarray(y), fmt))
        np.testing.assert_array_equal(got, want)

    def test_bytes_accounting(self):
        q = dpot_quantize(jnp.ones((128, 128)), FORMAT_W8, axis=1)
        nb = q.nbytes_hardware()
        assert nb == 128 * 128 * 8 // 8 + 128 * 4


# ---------------------------------------------------------------------------
# Uniform + baseline schemes
# ---------------------------------------------------------------------------


class TestUniform:
    def test_symmetric_grid(self):
        codes, scale = uniform_quantize(jnp.asarray([-1.0, 0.0, 1.0]), 9)
        assert int(codes[0]) == -255 and int(codes[2]) == 255

    @given(st.integers(2, 12), st.integers(0, 10_000))
    @settings(max_examples=30, deadline=None)
    def test_error_bound(self, bits, seed):
        """Property: uniform quant error <= scale/2 everywhere."""
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
        codes, scale = uniform_quantize(x, bits)
        err = np.abs(np.asarray(uniform_dequantize(codes, scale) - x))
        assert np.all(err <= float(scale) / 2 + 1e-7)

    def test_fake_quant_gradient(self):
        g = jax.grad(lambda x: jnp.sum(uniform_fake_quant(x, 9, None)))(
            jnp.linspace(-1, 1, 16))
        np.testing.assert_allclose(np.asarray(g), 1.0)


class TestSchemeOrdering:
    def test_proposed_beats_pot_and_logq(self, rng):
        """The paper's Table-1 ordering on weight-space MSE: Δ-PoT < LogQ,
        PoT (heavier-tailed weights accentuate the gap)."""
        w = jnp.asarray(rng.standard_t(4, size=(512, 64)), jnp.float32)
        def mse(f):
            return float(jnp.mean((f(w, 9, 1) - w) ** 2))
        e_prop = mse(proposed_fake_quant)
        e_pot = mse(pot_fake_quant)
        e_logq = mse(logq_fake_quant)
        assert e_prop < e_pot
        assert e_prop < e_logq


# ---------------------------------------------------------------------------
# Mixed-precision policy (paper §3.2)
# ---------------------------------------------------------------------------


class TestPolicy:
    def test_classification(self):
        assert classify_param("['blocks']['att']['wk']",
                              jnp.ones((8, 8))) == "matmul"
        assert classify_param("['blocks']['att']['time_decay']",
                              jnp.ones((8,))) == "additive"
        assert classify_param("['embed']", jnp.ones((100, 8))) == "additive"
        assert classify_param("['ln0']['scale']",
                              jnp.ones((8,))) == "additive"

    def test_tree_roundtrip_and_compression(self, rng):
        params = {
            "embed": jnp.asarray(rng.normal(size=(64, 16)), jnp.float32),
            "blocks": {"wk": jnp.asarray(rng.normal(size=(16, 16)),
                                         jnp.float32),
                       "time_decay": jnp.asarray(rng.normal(size=(16,)),
                                                 jnp.float32)},
        }
        qt, stats = quantize_tree(params, QuantPolicy())
        assert stats["compression"] > 1.5
        deq = dequantize_tree(qt)
        for k in ("embed",):
            err = np.abs(np.asarray(deq[k]) - np.asarray(params[k]))
            assert err.max() < 0.1 * np.abs(np.asarray(params[k])).max()

    def test_fake_quant_preserves_structure(self, rng):
        params = {"a": jnp.ones((4, 4)), "b": {"scale": jnp.ones((4,))}}
        out = fake_quantize_tree(params)
        assert jax.tree_util.tree_structure(out) == \
            jax.tree_util.tree_structure(params)

    @pytest.mark.parametrize("name,plane", [
        ("time_maa_x", "additive"), ("time_maa", "additive"),
        ("time_faaaa", "additive"), ("maa_w1", "matmul"),
        ("maa_w2", "matmul"), ("td_w1", "matmul"), ("td_w2", "matmul")])
    def test_rwkv6_mu_and_bonus_are_additive(self, name, plane):
        """The paper's rule on RWKV-6's stacked leaves: token-shift mu and
        the bonus u are used additively whatever their rank; the LoRA
        matrices multiply activations."""
        model = get_model("rwkv6-7b", smoke=True)
        att = model.abstract_params()["blocks"]["att"]
        assert att[name].ndim >= 2
        assert classify_param(f"['blocks']['att']['{name}']",
                              att[name]) == plane


# ---------------------------------------------------------------------------
# W4 nibble packing (FORMAT_W4: sign + 3-bit single-term codes, 2/byte)
# ---------------------------------------------------------------------------


class TestNibblePacking:
    def _quantized(self, rng, shape=(48, 32)):
        from repro.core.quant.delta_pot import FORMAT_W4
        w = jnp.asarray(rng.normal(size=shape), jnp.float32)
        return dpot_quantize(w, FORMAT_W4, axis=-1)

    def test_roundtrip_bitwise(self, rng):
        """pack -> unpack reproduces codes, signs AND dequantized values
        bit for bit — the property the in-kernel decode relies on."""
        from repro.core.quant.delta_pot import (FORMAT_W4,
                                                dpot_pack_nibbles,
                                                dpot_unpack_nibbles)
        q = self._quantized(rng)
        packed = dpot_pack_nibbles(q)
        assert packed.dtype == jnp.uint8
        assert packed.shape == (24, 32)          # HALF the rows
        q2 = dpot_unpack_nibbles(packed, q.scale, FORMAT_W4.ks)
        np.testing.assert_array_equal(np.asarray(q.codes),
                                      np.asarray(q2.codes))
        np.testing.assert_array_equal(np.asarray(q.signs),
                                      np.asarray(q2.signs))
        np.testing.assert_array_equal(
            np.asarray(dpot_dequantize(q), np.float32),
            np.asarray(dpot_dequantize(q2), np.float32))

    def test_stacked_leading_axes(self, rng):
        """(L, K, N) stacked leaves pack along axis -2 per layer — the
        megakernel slab form."""
        from repro.core.quant.delta_pot import (FORMAT_W4,
                                                dpot_pack_nibbles,
                                                dpot_unpack_nibbles)
        q = self._quantized(rng, shape=(3, 8, 16))
        packed = dpot_pack_nibbles(q)
        assert packed.shape == (3, 4, 16)
        q2 = dpot_unpack_nibbles(packed, q.scale, FORMAT_W4.ks)
        np.testing.assert_array_equal(np.asarray(q.codes),
                                      np.asarray(q2.codes))

    def test_rejects_wide_formats(self, rng):
        """Only formats with <= 3 code bits fit a nibble beside the sign."""
        from repro.core.quant.delta_pot import dpot_pack_nibbles
        w = jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
        q = dpot_quantize(w, FORMAT_W8, axis=-1)
        with pytest.raises(ValueError):
            dpot_pack_nibbles(q)

    def test_rejects_odd_contraction_axis(self, rng):
        from repro.core.quant.delta_pot import FORMAT_W4, dpot_pack_nibbles
        w = jnp.asarray(rng.normal(size=(7, 8)), jnp.float32)
        with pytest.raises(ValueError):
            dpot_pack_nibbles(dpot_quantize(w, FORMAT_W4, axis=-1))

    def test_w4_levels_single_term_pot(self):
        """FORMAT_W4's level grid is {0} ∪ {2^-1..2^-7}: the degenerate
        single-term Δ-PoT the 3-bit code can address."""
        from repro.core.quant.delta_pot import FORMAT_W4
        lv = sorted(set(np.asarray(dpot_levels(FORMAT_W4)).tolist()))
        np.testing.assert_allclose(
            lv, [0.0] + [2.0 ** (-q) for q in range(7, 0, -1)])


# ---------------------------------------------------------------------------
# VQ codebook plane (per-tensor 1-D k-means, uint8 indices)
# ---------------------------------------------------------------------------


class TestVQ:
    def test_exact_codebook_roundtrips(self, rng):
        """Weights drawn from <= n_codes distinct values reconstruct to
        those values exactly (mod bf16 rounding of the centroids)."""
        from repro.core.quant.vq import vq_dequantize, vq_quantize
        lv = np.asarray([-1.0, -0.25, 0.0, 0.5, 1.5], np.float32)
        w = jnp.asarray(lv[rng.integers(0, len(lv), size=(32, 16))])
        idx, cb = vq_quantize(w, 16)
        got = np.asarray(vq_dequantize(idx, cb), np.float32)
        np.testing.assert_array_equal(
            got, np.asarray(jnp.asarray(w).astype(jnp.bfloat16), np.float32))

    def test_forms(self, rng):
        from repro.core.quant.vq import vq_quantize
        w = jnp.asarray(rng.normal(size=(64, 48)), jnp.float32)
        idx, cb = vq_quantize(w, 256)
        assert idx.dtype == jnp.uint8 and idx.shape == w.shape
        assert cb.dtype == jnp.bfloat16 and cb.shape == (1, 256)

    def test_deterministic(self, rng):
        from repro.core.quant.vq import vq_quantize
        w = jnp.asarray(rng.normal(size=(64, 48)), jnp.float32)
        i1, c1 = vq_quantize(w, 32)
        i2, c2 = vq_quantize(w, 32)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_array_equal(np.asarray(c1, np.float32),
                                      np.asarray(c2, np.float32))

    def test_assignment_is_nearest(self, rng):
        """Every weight maps to its NEAREST stored (bf16) centroid — the
        assignment optimizes the codebook that actually ships."""
        from repro.core.quant.vq import vq_dequantize, vq_quantize
        w = jnp.asarray(rng.normal(size=(512,)), jnp.float32)
        idx, cb = vq_quantize(w, 16)
        got = np.asarray(vq_dequantize(idx, cb), np.float32)
        centers = np.asarray(cb, np.float32).reshape(-1)
        best = np.abs(w[:, None] - centers[None, :]).min(1)
        np.testing.assert_allclose(np.abs(np.asarray(w) - got), best,
                                   atol=1e-6)

    def test_kmeans_reduces_error_vs_quantiles(self, rng):
        from repro.core.quant.vq import kmeans_1d
        v = np.asarray(rng.standard_t(3, size=4096), np.float32)
        c16 = np.asarray(kmeans_1d(jnp.asarray(v), 16), np.float32)
        c4 = np.asarray(kmeans_1d(jnp.asarray(v), 4), np.float32)
        e16 = np.abs(v[:, None] - c16[None]).min(1).mean()
        e4 = np.abs(v[:, None] - c4[None]).min(1).mean()
        assert e16 < e4


# ---------------------------------------------------------------------------
# PlanePolicy: per-tensor plane selection + fingerprints
# ---------------------------------------------------------------------------


class TestPlanePolicy:
    def test_proxy_separates_tails(self, rng):
        from repro.core.quant.policy import weight_outlier_proxy
        gauss = rng.normal(size=(256, 256)).astype(np.float32)
        heavy = rng.standard_t(3, size=(256, 256)).astype(np.float32)
        assert weight_outlier_proxy(gauss) < 1.0
        assert weight_outlier_proxy(heavy) > 8.0

    def test_proxy_thresholds_route_planes(self, rng):
        from repro.core.quant.policy import PLANE_PROXY
        gauss = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
        heavy = jnp.asarray(rng.standard_t(3, size=(64, 64)), jnp.float32)
        assert PLANE_PROXY.plane_for("['x']", gauss) == "w4"
        assert PLANE_PROXY.plane_for("['x']", heavy) == "vq"

    def test_overrides_win(self, rng):
        from repro.core.quant.policy import PlanePolicy
        pol = PlanePolicy(default="w8", overrides=((r"wk", "vq"),))
        w = jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
        assert pol.plane_for("['att']['wk']", w) == "vq"
        assert pol.plane_for("['att']['wv']", w) == "w8"

    def test_invalid_rejected(self):
        from repro.core.quant.policy import PlanePolicy
        with pytest.raises(ValueError):
            PlanePolicy(default="w3")
        with pytest.raises(ValueError):
            PlanePolicy(overrides=(("wk", "int4"),))

    def test_config_roundtrip(self):
        from repro.core.quant.policy import PlanePolicy
        pol = PlanePolicy(default="proxy", w4_max_proxy=2.0,
                          overrides=((r"head", "w4"),))
        assert PlanePolicy.from_config(pol.to_config()) == pol
        assert PlanePolicy.from_config(None) is None

    def test_pack_params_w4_odd_axis_falls_back_to_w8(self, rng):
        from repro.core.quant.policy import PLANE_W4
        from repro.core.quant.serving import leaf_plane, pack_params
        tree = {"att": {"wk": jnp.asarray(rng.normal(size=(47, 8)),
                                          jnp.float32)}}
        packed = pack_params(tree, PLANE_W4)
        assert leaf_plane(packed["att"]["wk"]) == "w8"

    def test_unpack_leaf_matches_reference_per_plane(self, rng):
        """`unpack_leaf` (the single decode source of truth) reproduces
        each plane's reference dequantization bitwise."""
        from repro.core.quant.delta_pot import FORMAT_W4, dpot_pack_nibbles
        from repro.core.quant.serving import unpack_leaf
        from repro.core.quant.vq import vq_dequantize, vq_quantize
        w = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
        q = dpot_quantize(w, FORMAT_W4, axis=-1)
        leaf = {"packed4": dpot_pack_nibbles(q),
                "scale": q.scale.astype(jnp.float32)}
        np.testing.assert_array_equal(
            np.asarray(unpack_leaf(leaf), np.float32),
            np.asarray(dpot_dequantize(q).astype(jnp.bfloat16), np.float32))
        idx, cb = vq_quantize(w, 32)
        np.testing.assert_array_equal(
            np.asarray(unpack_leaf({"vq_idx": idx, "codebook": cb}),
                       np.float32),
            np.asarray(vq_dequantize(idx, cb).astype(jnp.bfloat16),
                       np.float32))

    def test_quantize_tree_plane_stats(self, rng):
        from repro.core.quant.policy import PlanePolicy
        tree = {"att": {"wk": jnp.asarray(rng.normal(size=(16, 16)),
                                          jnp.float32),
                        "wv": jnp.asarray(rng.normal(size=(16, 16)),
                                          jnp.float32)}}
        pol = PlanePolicy(default="w4", overrides=((r"wv", "vq"),))
        _, stats = quantize_tree(tree, planes=pol)
        assert stats["planes"]["['att']['wk']"] == "w4"
        assert stats["planes"]["['att']['wv']"] == "vq"
        assert set(stats["bytes_by_plane"]) == {"w4", "vq"}
        # W4 stores half the code bytes of W8 for the same tensor
        assert stats["bytes_by_plane"]["w4"] < 16 * 16


class TestPlaneFingerprint:
    def test_historical_strings(self, rng):
        """fp trees and all-W8 packs keep the exact historical CacheVariant
        strings, so pre-plane cache entries and snapshots stay valid."""
        from repro.core.quant.serving import pack_params, plane_fingerprint
        tree = {"head": jnp.asarray(rng.normal(size=(16, 16)), jnp.float32)}
        assert plane_fingerprint(tree) == "fp"
        assert plane_fingerprint(pack_params(tree)) == "dpot_w8"

    def test_mixes_hash_and_never_alias(self, rng):
        from repro.core.quant.policy import PLANE_VQ, PLANE_W4
        from repro.core.quant.serving import pack_params, plane_fingerprint
        tree = {"a": jnp.asarray(rng.normal(size=(16, 16)), jnp.float32),
                "b": jnp.asarray(rng.normal(size=(16, 16)), jnp.float32)}
        f_w4 = plane_fingerprint(pack_params(tree, PLANE_W4))
        f_vq = plane_fingerprint(pack_params(tree, PLANE_VQ))
        assert f_w4.startswith("dpot_mix_")
        assert f_vq.startswith("dpot_mix_")
        assert f_w4 != f_vq
        # deterministic: same policy, same fingerprint
        assert f_w4 == plane_fingerprint(pack_params(tree, PLANE_W4))
