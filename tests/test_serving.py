"""Packed Δ-PoT serving path: correctness of pack/unpack, serve-step
variants, and agreement with the fp decode."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ShapeConfig
from repro.core.quant.serving import (
    pack_layerwise, pack_params, packed_abstract, replicate_fsdp,
    serving_axes, unpack_params)
from repro.core.quant.delta_pot import (
    FORMAT_W8, dpot_quantize, dpot_dequantize)
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import build_serve_step
from repro.models.registry import get_model


def _assert_same(a, b):
    """Two trees with the same structure, shapes, dtypes and bits."""
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        assert x.shape == y.shape and x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x).view(np.uint8),
                                      np.asarray(y).view(np.uint8))


class TestPackUnpack:
    def test_roundtrip_matches_fake_quant(self, rng):
        """unpack(pack(w)) == dequantize(quantize(w)) for matmul leaves."""
        w = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
        params = {"blocks": {"wk": w}}
        packed = pack_params(params)
        assert packed["blocks"]["wk"]["packed"].dtype == jnp.uint8
        out = unpack_params(packed)["blocks"]["wk"]
        q = dpot_quantize(w, FORMAT_W8, axis=-1)
        want = dpot_dequantize(q)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want),
            rtol=2e-2, atol=2e-2)  # bf16 storage of the dequant

    def test_additive_leaves_passthrough(self, rng):
        params = {"ln0": {"scale": jnp.ones((8,))},
                  "time_decay": jnp.zeros((8,))}
        packed = pack_params(params)
        assert packed["ln0"]["scale"].dtype == jnp.bfloat16

    def test_abstract_matches_real(self, rng):
        model = get_model("rwkv6-7b", smoke=True)
        params = model.init_params(jax.random.PRNGKey(0))
        packed = pack_params(params)
        ab = packed_abstract(model.spec(), model.abstract_params())
        real_shapes = jax.tree_util.tree_map(
            lambda x: (x.shape, str(x.dtype)), packed)
        ab_shapes = jax.tree_util.tree_map(
            lambda x: (x.shape, str(x.dtype)), ab)
        assert jax.tree_util.tree_structure(real_shapes) == \
            jax.tree_util.tree_structure(ab_shapes)
        flat_r = jax.tree_util.tree_leaves(real_shapes)
        flat_a = jax.tree_util.tree_leaves(ab_shapes)
        # scale shapes differ in broadcast form only; compare packed dtypes
        assert flat_r == flat_a

    def test_packed_leaves_pass_through(self, rng):
        w = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
        packed = pack_params({"blocks": {"wk": w},
                              "ln0": {"scale": jnp.ones((8,))}})
        again = pack_params(packed)
        assert again["blocks"]["wk"]["packed"] is \
            packed["blocks"]["wk"]["packed"]
        assert again["blocks"]["wk"]["scale"] is \
            packed["blocks"]["wk"]["scale"]
        _assert_same(again, packed)

    @pytest.mark.parametrize("arch", ["rwkv4-169m", "rwkv6-7b"])
    def test_layerwise_pack_equals_whole_tree(self, arch):
        """Made one layer at a time (the layer a traced index into the
        stacked tree), the pack equals `pack_params` of the whole tree
        bit for bit: codes, (1, ..., N) scales and bf16 leaves."""
        model = get_model(arch, smoke=True)
        params = model.init_params(jax.random.PRNGKey(3))
        top = {k: v for k, v in params.items() if k != "blocks"}
        make_layer = lambda l, blocks: jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_index_in_dim(x, l, 0, False), blocks)
        seconds = []
        got = pack_layerwise(top, make_layer, model.cfg.n_layers,
                             (params["blocks"],), log=seconds.append)
        _assert_same(got, pack_params(params))
        assert "amax pass" in seconds[0] and "pack pass" in seconds[0]

    def test_replicate_fsdp(self):
        axes = {"w": ("fsdp", "tp"), "b": (None,)}
        out = replicate_fsdp(axes)
        assert out["w"] == (None, "tp")


class TestQuantizedServeStep:
    @pytest.mark.parametrize("variant", ["base", "replicated", "quantized"])
    def test_variants_run(self, variant):
        model = get_model("rwkv6-7b", smoke=True)
        mesh = make_host_mesh()
        shape = ShapeConfig("d", 32, 2, "decode")
        jitted, args, _ = build_serve_step(model, mesh, shape,
                                           variant=variant)
        params = model.init_params(jax.random.PRNGKey(0))
        if variant == "quantized":
            params = pack_params(params)
        state = model.init_decode_state(2, 32)
        tok = jnp.zeros((2, 1), jnp.int32)
        logits, _ = jitted(params, state, tok, jnp.int32(0))
        assert logits.shape == (2, 1, model.cfg.vocab)
        assert not bool(jnp.any(jnp.isnan(logits)))

    def test_quantized_close_to_fp(self):
        """Packed serving ~ fp serving (the paper's accuracy contract)."""
        model = get_model("rwkv4-169m", smoke=True)
        mesh = make_host_mesh()
        shape = ShapeConfig("d", 16, 2, "decode")
        j_fp, _, _ = build_serve_step(model, mesh, shape, variant="base")
        j_q, _, _ = build_serve_step(model, mesh, shape,
                                     variant="quantized")
        params = model.init_params(jax.random.PRNGKey(0))
        state = model.init_decode_state(2, 16)
        tok = jnp.ones((2, 1), jnp.int32)
        l_fp, _ = j_fp(params, state, tok, jnp.int32(0))
        l_q, _ = j_q(pack_params(params),
                     model.init_decode_state(2, 16), tok, jnp.int32(0))
        p = jax.nn.softmax(l_fp.astype(jnp.float32), -1)
        lq = jax.nn.log_softmax(l_q.astype(jnp.float32), -1)
        kl = float(jnp.mean(jnp.sum(
            p * (jnp.log(p + 1e-9) - lq), -1)))
        assert np.isfinite(kl) and kl < 0.1
