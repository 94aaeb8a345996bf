"""Sharding-rule unit tests + an end-to-end sharded train step on the
host mesh (the same code path the production dry-run lowers)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec

from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import (
    build_prefill_step, build_serve_step, build_train_step)
from repro.models.registry import get_model
from repro.parallel.sharding import spec_for_axes


@pytest.fixture(scope="module")
def mesh22():
    # a fake 2x2 mesh over... 1 device won't work; use abstract mesh math
    # only through spec_for_axes (which never touches devices).
    import jax.sharding as shd
    return jax.make_mesh((1, 1), ("data", "model"))


class TestSpecRules:
    def _mesh(self):
        # AbstractMesh lets us test the rules for production shapes without
        # 256 devices.
        return self._abstract_mesh((16, 16), ("data", "model"))

    def _mesh3(self):
        return self._abstract_mesh((2, 16, 16), ("pod", "data", "model"))

    @staticmethod
    def _abstract_mesh(sizes, names):
        from jax.sharding import AbstractMesh
        return AbstractMesh(sizes, names)

    def test_fsdp_tp(self):
        spec = spec_for_axes(("fsdp", "tp"), (4096, 4096), self._mesh())
        assert spec == PartitionSpec("data", "model")

    def test_batch_spans_pod_and_data(self):
        spec = spec_for_axes(("batch", None), (256, 4096), self._mesh3())
        assert spec == PartitionSpec(("pod", "data"), None)

    def test_non_divisible_replicates(self):
        # 9 heads % 16 != 0 -> replicated, not an error
        spec = spec_for_axes((None, "tp", None), (1, 9, 64), self._mesh())
        assert spec == PartitionSpec(None, None, None)

    def test_batch1_falls_back(self):
        spec = spec_for_axes(("batch", None), (1, 524288), self._mesh3())
        assert spec == PartitionSpec(None, None)

    def test_seq_prefers_model_axis(self):
        spec = spec_for_axes(
            ("layers", "batch", "seq", "tp", None),
            (32, 128, 32768, 8, 128), self._mesh())
        # batch -> data, seq -> model (tp then has nothing left and 8 % 16
        # != 0 anyway)
        assert spec == PartitionSpec(None, "data", "model", None, None)

    def test_no_axis_reuse(self):
        spec = spec_for_axes(("fsdp", "fsdp"), (256, 256), self._mesh())
        assert spec == PartitionSpec("data", None)


class TestShardedSteps:
    """Build + run each step kind on the 1x1 host mesh: proves the
    sharding trees match the pytrees (structure errors fail here fast)."""

    def test_train_step_runs(self):
        model = get_model("smollm-135m", smoke=True)
        mesh = make_host_mesh()
        shape = ShapeConfig("t", 32, 4, "train")
        jitted, args, (p_sh, o_sh, b_sh), (init_opt, _) = \
            build_train_step(model, mesh, shape)
        params = jax.device_put(model.init_params(jax.random.PRNGKey(0)),
                                p_sh)
        opt = jax.device_put(init_opt(jax.device_get(params)), o_sh)
        tok = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                 model.cfg.vocab)
        batch = {"tokens": tok, "labels": tok,
                 "mask": jnp.ones((4, 32), jnp.float32)}
        p2, o2, metrics = jitted(params, opt, batch)
        assert np.isfinite(float(metrics["loss"]))

    def test_serve_step_runs(self):
        model = get_model("rwkv6-7b", smoke=True)
        mesh = make_host_mesh()
        shape = ShapeConfig("d", 64, 2, "decode")
        jitted, args, _ = build_serve_step(model, mesh, shape)
        params = model.init_params(jax.random.PRNGKey(0))
        state = model.init_decode_state(2, 64)
        tok = jnp.zeros((2, 1), jnp.int32)
        logits, new_state = jitted(params, state, tok, jnp.int32(0))
        assert logits.shape == (2, 1, model.cfg.vocab)

    def test_prefill_step_runs(self):
        model = get_model("phi3-mini-3.8b", smoke=True)
        mesh = make_host_mesh()
        shape = ShapeConfig("p", 32, 2, "prefill")
        jitted, args, _ = build_prefill_step(model, mesh, shape)
        params = model.init_params(jax.random.PRNGKey(0))
        tok = jnp.zeros((2, 32), jnp.int32)
        batch = {"tokens": tok, "labels": tok,
                 "mask": jnp.ones((2, 32), jnp.float32)}
        logits = jitted(params, batch)
        assert logits.shape == (2, 32, model.cfg.vocab)
