"""ExecutionPlan tests: path descriptors, one-pass param preparation,
the (path, batch bucket, dtype) program cache, shared masking semantics,
and — under XLA_FLAGS=--xla_force_host_platform_device_count=8 (the CI
multi-device leg) — the data-parallel serving proof: an 8-device engine
streams BIT-IDENTICAL tokens to the 1-device engine for rwkv4 + rwkv6,
fp + Δ-PoT packed, fused and per-op paths, with the slot pool actually
sharded across all devices."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.quant.serving import (
    PreparedParams, is_packed_leaf, pack_params, predecode_packed_leaves)
from repro.models.registry import get_model
from repro.serving import ServingEngine, build_plan
from repro.serving.plan import masked_state_commit, maybe_unpack

MULTI = len(jax.devices()) >= 8


@pytest.fixture(scope="module")
def rwkv4():
    model = get_model("rwkv4-169m", smoke=True)
    return model, model.init_params(jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# Descriptors + one-pass preparation
# ---------------------------------------------------------------------------


class TestDescriptors:
    def test_decode_paths_match_module_entries(self):
        model = get_model("rwkv4-169m", smoke=True)
        paths = model.decode_paths()
        assert set(paths) == {"per_op", "block", "model"}
        assert paths["per_op"].fused is False
        assert paths["model"].prepare == "prepare_fused_model_params"
        assert set(model.prefill_paths()) == {"per_op", "chunked"}

    def test_has_flags_are_descriptor_views(self, monkeypatch):
        from repro.models import rwkv4 as R4
        monkeypatch.delattr(R4, "decode_step_fused_model")
        model = get_model("rwkv4-169m", smoke=True)
        assert "model" not in model.decode_paths()
        assert not model.has_fused_model_decode
        assert model.has_fused_decode and model.has_decode

    def test_plain_transformer_has_only_per_op(self):
        model = get_model("smollm-135m", smoke=True)
        assert set(model.decode_paths()) == {"per_op"}
        assert set(model.prefill_paths()) == {"per_op"}

    def test_build_plan_rejects_unknown_decode_path(self, rwkv4):
        model, params = rwkv4
        with pytest.raises(ValueError, match="fused_decode"):
            build_plan(model, params, fused_decode="layerwise")

    def test_build_plan_rejects_missing_entry(self, monkeypatch, rwkv4):
        from repro.models import rwkv4 as R4
        monkeypatch.delattr(R4, "prefill_chunk")
        model = get_model("rwkv4-169m", smoke=True)
        with pytest.raises(ValueError, match="prefill_chunk"):
            build_plan(model, fused_prefill=True)


class TestPreparedParams:
    def test_per_op_paths_alias_raw(self, rwkv4):
        model, params = rwkv4
        plan = build_plan(model, params)
        assert isinstance(plan.prepared, PreparedParams)
        assert plan.prepared.decode is plan.prepared.raw
        assert plan.prepared.prefill is plan.prepared.raw
        assert plan.prepared.decode_path == "per_op"

    def test_quantized_packs_once(self, rwkv4):
        model, params = rwkv4
        plan = build_plan(model, params, quantized=True)
        # raw is the packed tree; per-op decode consumes it via in-trace
        # unpack (maybe_unpack), not a second prepared copy
        assert plan.prepared.quantized
        assert is_packed_leaf(
            plan.prepared.raw["blocks"]["att"]["wk"])
        assert plan.prepared.decode is plan.prepared.raw

    def test_rwkv6_prefill_prep_decodes_elementwise_leaves(self):
        model = get_model("rwkv6-7b", smoke=True)
        params = model.init_params(jax.random.PRNGKey(0))
        plan = build_plan(model, params, quantized=True,
                          fused_prefill=True)
        raw_att = plan.prepared.raw["blocks"]["att"]
        pre_att = plan.prepared.prefill["blocks"]["att"]
        # the paper's additive plane: token-shift mu and bonus u are
        # stored plain in bf16, never W8-coded
        for name in ("time_maa_x", "time_maa", "time_faaaa"):
            assert not is_packed_leaf(raw_att[name])
            assert raw_att[name].dtype == jnp.bfloat16
        assert is_packed_leaf(raw_att["maa_w2"])
        assert not is_packed_leaf(pre_att["maa_w2"])     # pre-decoded
        assert is_packed_leaf(pre_att["wk"])             # still packed

    @pytest.mark.parametrize("arch", ["rwkv4-169m", "rwkv6-7b"])
    def test_per_op_decode_unpacks_layer_by_layer(self, arch, rng):
        """The per-op decode program decodes each layer's packed leaves
        inside the layer scan: its top level never holds a stacked bf16
        weight, and its logits and state are bit-identical to decoding
        the whole tree before the step."""
        from repro.core.quant.serving import unpack_params
        from repro.kernels.common import exact_jit
        model = get_model(arch, smoke=True)
        cfg = model.cfg
        plan = build_plan(model, model.init_params(jax.random.PRNGKey(1)),
                          quantized=True)
        packed = plan.prepared.decode
        B = 3
        state = jax.tree_util.tree_map(
            lambda x: jnp.asarray(rng.normal(size=x.shape), x.dtype),
            model.init_slot_state(B, 0, jnp.bfloat16))
        toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, 1)), jnp.int32)
        want = exact_jit(lambda p, s, t: model.decode_step(
            unpack_params(p), s, t, jnp.int32(0)))(packed, state, toks)
        top = jax.make_jaxpr(plan._decode_step())(packed, state, toks).jaxpr
        stacked = [v.aval.shape for e in top.eqns for v in e.outvars
                   if v.aval.dtype == jnp.bfloat16 and v.aval.ndim == 3
                   and v.aval.shape[0] == cfg.n_layers
                   and v.aval.shape[1] >= cfg.d_model]
        assert stacked == []
        logits, new = plan.decode_fn(B)(state, toks, np.ones((B,), bool))
        np.testing.assert_array_equal(np.asarray(logits, np.float32),
                                      np.asarray(want[0], np.float32))
        for a, b in zip(jax.tree_util.tree_leaves(new),
                        jax.tree_util.tree_leaves(want[1])):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))

    def test_megakernel_prep_builds_layer_stack(self, rwkv4):
        from repro.core.quant.serving import FusedLayerStack
        model, params = rwkv4
        plan = build_plan(model, params, fused_decode="model")
        assert isinstance(plan.prepared.decode["blocks"], FusedLayerStack)
        assert plan.prepared.prefill is plan.prepared.raw

    def test_predecode_packed_leaves_targets_only_named_paths(self, rng):
        w = jnp.asarray(rng.normal(size=(16, 16)), jnp.float32)
        tree = pack_params({"a": {"x": w, "y": w}, "b": w})
        out = predecode_packed_leaves(tree, [("a", "x"), ("b",)])
        assert not is_packed_leaf(out["a"]["x"])
        assert not is_packed_leaf(out["b"])
        assert is_packed_leaf(out["a"]["y"])
        # plain leaves at a named path pass through untouched
        plain = {"a": {"x": w}}
        assert predecode_packed_leaves(plain, [("a", "x")])["a"]["x"] is w


# ---------------------------------------------------------------------------
# Program cache + shared masking semantics
# ---------------------------------------------------------------------------


class TestProgramCache:
    def test_cache_hit_same_bucket(self, rwkv4):
        model, params = rwkv4
        plan = build_plan(model, params)
        fn1 = plan.decode_fn(4)
        fn2 = plan.decode_fn(4)
        assert fn1 is fn2                     # cache hit, not a rebuild
        assert plan.prefill_fn(4) is plan.prefill_fn(4)

    def test_keys_include_path_bucket_dtype(self, rwkv4):
        model, params = rwkv4
        plan = build_plan(model, params)
        plan.decode_fn(4)
        plan.decode_fn(8)                     # new bucket -> new entry
        keys = set(plan._programs)
        assert ("decode", "per_op", 4, "bfloat16") in keys
        assert ("decode", "per_op", 8, "bfloat16") in keys

    def test_one_trace_across_ticks(self, rwkv4):
        """The no-recompile guarantee through the plan: churny serving
        still traces each program exactly once (as test_scheduler asserts
        through the engine)."""
        model, params = rwkv4
        engine = ServingEngine(model, params=params, max_batch=3,
                               prefill_chunk=4)
        rng = np.random.default_rng(0)
        for _ in range(2):
            hs = [engine.submit(
                rng.integers(0, model.cfg.vocab,
                             size=int(rng.integers(1, 9))).tolist(),
                max_new_tokens=3) for _ in range(4)]
            engine.run()
            assert all(h.done for h in hs)
        assert engine.trace_counts == {"decode": 1, "prefill": 1}
        assert engine.plan.trace_counts is engine.trace_counts


class TestMaskedCommit:
    def test_masked_state_commit_semantics(self):
        old = {"a": jnp.zeros((2, 3, 4)), "b": jnp.zeros((3, 5))}
        new = {"a": jnp.ones((2, 3, 4)), "b": jnp.ones((3, 5))}
        mask = jnp.asarray([True, False, True])
        out = masked_state_commit(new, old, mask, axes=[1, 0])
        np.testing.assert_array_equal(
            np.asarray(out["a"][:, :, 0]), [[1, 0, 1]] * 2)
        np.testing.assert_array_equal(
            np.asarray(out["b"][:, 0]), [1, 0, 1])

    def test_broadcasts_batch1_template(self):
        """The prefill fresh-lane reset relies on a batch-1 `new` tree
        broadcasting into the masked lanes."""
        old = {"a": jnp.arange(6, dtype=jnp.float32).reshape(3, 2)}
        fresh = {"a": jnp.full((1, 2), 9.0)}
        out = masked_state_commit(old, fresh, ~jnp.asarray([True, False,
                                                            True]),
                                  axes=[0])
        np.testing.assert_array_equal(np.asarray(out["a"])[:, 0],
                                      [9.0, 2.0, 9.0])

    def test_maybe_unpack(self, rng):
        w = jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
        packed = pack_params({"w": w})
        assert maybe_unpack(packed, False) is packed
        assert maybe_unpack(packed, True)["w"].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# Mesh placement (1-device mesh runs everywhere; 8-device under the CI leg)
# ---------------------------------------------------------------------------


def _serving_mesh(n):
    from repro.launch.mesh import make_serving_mesh
    return make_serving_mesh(n)


def _tokens(model, params, prompts, *, mesh, quantized, fd, fp,
            max_batch=8):
    eng = ServingEngine(model, params=params, max_batch=max_batch,
                        prefill_chunk=4, quantized=quantized,
                        fused_decode=fd, fused_prefill=fp, mesh=mesh)
    hs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.run()
    assert eng.trace_counts == {"decode": 1, "prefill": 1}
    return [h.tokens for h in hs], eng


class TestMeshServing:
    def test_one_device_mesh_matches_plain(self, rwkv4):
        """A 1-device mesh is placement-only: same tokens as no mesh."""
        model, params = rwkv4
        rng = np.random.default_rng(2)
        prompts = [rng.integers(0, model.cfg.vocab, size=n).tolist()
                   for n in (3, 9, 1)]
        t_plain, _ = _tokens(model, params, prompts, mesh=None,
                             quantized=False, fd=False, fp=False,
                             max_batch=2)
        t_mesh, eng = _tokens(model, params, prompts,
                              mesh=_serving_mesh(1), quantized=False,
                              fd=False, fp=False, max_batch=2)
        assert t_plain == t_mesh
        assert eng.plan.mesh is not None

    @pytest.mark.skipif(not MULTI, reason="needs >= 8 devices "
                        "(XLA_FLAGS=--xla_force_host_platform_"
                        "device_count=8)")
    @pytest.mark.parametrize("arch,quantized,fd,fp", [
        ("rwkv4-169m", False, False, False),    # per-op, fp
        ("rwkv4-169m", True, False, False),     # per-op, packed
        ("rwkv4-169m", True, "model", True),    # megakernel + chunked
        ("rwkv6-7b", False, "block", True),     # block kernel + chunked
        ("rwkv6-7b", True, False, False),       # per-op, packed
    ])
    def test_8dev_bit_identical_tokens(self, arch, quantized, fd, fp):
        """THE acceptance claim: the 8-device data-parallel engine
        streams bit-identical tokens to the 1-device engine — both archs,
        fp + Δ-PoT packed, fused and per-op paths — and the pool is
        genuinely sharded over all 8 devices."""
        model = get_model(arch, smoke=True)
        params = model.init_params(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, model.cfg.vocab, size=n).tolist()
                   for n in (3, 9, 17, 5, 1)]
        # two lanes per device: XLA:CPU lowers a one-row matmul as a
        # vector-matrix loop whose f32 accumulation order differs from
        # the GEMM of a wider batch, so one lane per device would compare
        # two different roundings, not two placements
        kw = dict(quantized=quantized, fd=fd, fp=fp, max_batch=16)
        t1, _ = _tokens(model, params, prompts, mesh=None, **kw)
        t8, eng = _tokens(model, params, prompts, mesh=_serving_mesh(8),
                          **kw)
        assert t1 == t8
        for leaf in jax.tree_util.tree_leaves(eng.pool.state):
            assert len(leaf.sharding.device_set) == 8, leaf.sharding

    @pytest.mark.skipif(not MULTI, reason="needs >= 8 devices")
    def test_non_divisible_pool_replicates_and_runs(self, rwkv4):
        """max_batch % devices != 0 falls back to replication (the
        divisibility rule) instead of erroring — and still serves."""
        model, params = rwkv4
        prompts = [[1, 2, 3], [4, 5]]
        t1, _ = _tokens(model, params, prompts, mesh=None,
                        quantized=False, fd=False, fp=False, max_batch=3)
        t8, eng = _tokens(model, params, prompts, mesh=_serving_mesh(8),
                          quantized=False, fd=False, fp=False,
                          max_batch=3)
        assert t1 == t8
        leaf = jax.tree_util.tree_leaves(eng.pool.state)[0]
        assert leaf.sharding.is_fully_replicated

    @pytest.mark.skipif(not MULTI, reason="needs >= 8 devices")
    def test_8dev_weights_replicated_pool_sharded(self, rwkv4):
        """Placement split: every prepared weight leaf is fully
        replicated (placed once at startup), while the per-tick batch and
        pool shard over "data"."""
        model, params = rwkv4
        plan = build_plan(model, params, mesh=_serving_mesh(8),
                          fused_decode="model")
        for leaf in jax.tree_util.tree_leaves(plan.prepared.decode):
            assert leaf.sharding.is_fully_replicated
        shards = jax.tree_util.tree_leaves(plan.state_shardings(8))
        assert shards and all("data" in tuple(s.spec) for s in shards)
