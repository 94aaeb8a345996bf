"""Fused decode kernels vs the per-op oracle.

The contract under test (docs/kernels.md §fully-on-chip datapath): BOTH
fused granularities — the per-block Pallas kernel (`decode_step_fused`,
one launch per layer) and the whole-model megakernel
(`decode_step_fused_model`, ONE launch per decode step with the grid
iterating over layers) — are BIT-IDENTICAL to the per-op decode path
(`decode_step`) — for fp and Δ-PoT-packed weights, for rwkv4 and rwkv6,
from random recurrent states — and the serving engine produces identical
greedy tokens with `fused_decode="block"` / `"model"`.  The megakernel's
launch count is pinned by jaxpr traversal: exactly ONE `pallas_call` per
model decode step (vs L for the per-block path).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.extend import core as jcore

from repro.core.quant.serving import pack_params, unpack_params
from repro.models.registry import get_model

ARCHS = ["rwkv4-169m", "rwkv6-7b"]
BATCH = 4


# ---------------------------------------------------------------------------
# Launch counting: how many pallas_call EXECUTIONS does one step issue?
# ---------------------------------------------------------------------------


def _sub_jaxprs(v):
    if isinstance(v, jcore.ClosedJaxpr):
        return [v.jaxpr]
    if isinstance(v, jcore.Jaxpr):
        return [v]
    if isinstance(v, (list, tuple)):
        return [j for e in v for j in _sub_jaxprs(e)]
    return []


def count_pallas_launches(jaxpr, mult: int = 1) -> int:
    """Number of pallas_call executions one evaluation of `jaxpr` issues:
    a pallas_call inside a scan body counts once per scan iteration (the
    per-block fused path is a scan of L launches), so this measures
    LAUNCHES, not trace sites."""
    n = 0
    for eqn in jaxpr.eqns:
        m = mult
        if eqn.primitive.name == "scan":
            m = mult * eqn.params["length"]
        if eqn.primitive.name == "pallas_call":
            n += mult
        for v in eqn.params.values():
            for j in _sub_jaxprs(v):
                n += count_pallas_launches(j, m)
    return n


def _random_state(model, rng, batch=BATCH, dtype=jnp.bfloat16):
    """A decode state with random (but per-leaf plausible) contents: the
    fresh state is all-zeros/-inf, which would mask bugs that only show
    once the recurrence has history."""
    state = model.init_decode_state(batch, 0, dtype)

    def fill(leaf):
        vals = rng.normal(size=leaf.shape).astype(np.float32)
        if np.all(np.asarray(leaf, np.float32) < -1e30):   # wkv_o running max
            vals = vals - 1.0   # plausible max-exponent values
        return jnp.asarray(vals, leaf.dtype)

    return jax.tree_util.tree_map(fill, state)


def _assert_bitwise(tree_a, tree_b):
    la = jax.tree_util.tree_leaves(tree_a)
    lb = jax.tree_util.tree_leaves(tree_b)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def _fused_step(model, mode: str):
    """The fused decode entry under test: per-block or whole-model."""
    return (model.decode_step_fused_model if mode == "model"
            else model.decode_step_fused)


@pytest.mark.parametrize("mode", ["block", "model"])
@pytest.mark.parametrize("arch", ARCHS)
class TestBitParity:
    def test_fp(self, arch, mode, rng):
        model = get_model(arch, smoke=True)
        params = model.init_params(jax.random.PRNGKey(0))
        state = _random_state(model, rng)
        toks = jnp.asarray(rng.integers(0, model.cfg.vocab, (BATCH, 1)),
                           jnp.int32)
        l1, s1 = jax.jit(model.decode_step)(params, state, toks,
                                            jnp.int32(0))
        l2, s2 = jax.jit(_fused_step(model, mode))(params, state, toks,
                                                   jnp.int32(0))
        _assert_bitwise(l1, l2)
        _assert_bitwise(s1, s2)

    def test_dpot_packed(self, arch, mode, rng):
        """Packed Δ-PoT weights: per-op path unpacks the whole tree inside
        the jit (the engine's quantized oracle); the fused paths hand uint8
        codes to the kernel and decode in-launch — the megakernel
        additionally streams the code planes per layer while the shared
        scales stay resident.  Same bits out."""
        model = get_model(arch, smoke=True)
        packed = pack_params(model.init_params(jax.random.PRNGKey(0)))
        state = _random_state(model, rng)
        toks = jnp.asarray(rng.integers(0, model.cfg.vocab, (BATCH, 1)),
                           jnp.int32)
        oracle = jax.jit(lambda p, s, t: model.decode_step(
            unpack_params(p), s, t, jnp.int32(0)))
        fused = jax.jit(lambda p, s, t: _fused_step(model, mode)(
            p, s, t, jnp.int32(0)))
        l1, s1 = oracle(packed, state, toks)
        l2, s2 = fused(packed, state, toks)
        _assert_bitwise(l1, l2)
        _assert_bitwise(s1, s2)

    def test_multi_step_trajectory(self, arch, mode, rng):
        """Parity holds when the fused path consumes its OWN state: run
        several steps per path independently and compare at the end."""
        model = get_model(arch, smoke=True)
        params = model.init_params(jax.random.PRNGKey(1))
        s1 = model.init_decode_state(BATCH, 0, jnp.bfloat16)
        s2 = jax.tree_util.tree_map(lambda x: x, s1)
        step = jax.jit(model.decode_step)
        fstep = jax.jit(_fused_step(model, mode))
        for i in range(4):
            toks = jnp.asarray(
                rng.integers(0, model.cfg.vocab, (BATCH, 1)), jnp.int32)
            l1, s1 = step(params, s1, toks, jnp.int32(0))
            l2, s2 = fstep(params, s2, toks, jnp.int32(0))
        _assert_bitwise(l1, l2)
        _assert_bitwise(s1, s2)


@pytest.mark.parametrize("mode", ["block", "model"])
def test_rwkv4_hw_numerics_parity(mode, rng):
    """Both fused kernels compose with the paper's LUT/PWL numerics mode
    (the tables travel as explicit VMEM operands)."""
    from repro.models import rwkv4
    fused_fn = (rwkv4.decode_step_fused_model if mode == "model"
                else rwkv4.decode_step_fused)
    model = get_model("rwkv4-169m", smoke=True)
    params = model.cast_params(model.init_params(jax.random.PRNGKey(0)))
    state = _random_state(model, rng)
    toks = jnp.asarray(rng.integers(0, model.cfg.vocab, (BATCH, 1)),
                       jnp.int32)
    l1, s1 = jax.jit(lambda p, s, t: rwkv4.decode_step(
        p, s, t, jnp.int32(0), model.cfg, hw=True))(params, state, toks)
    l2, s2 = jax.jit(lambda p, s, t: fused_fn(
        p, s, t, jnp.int32(0), model.cfg, hw=True))(params, state, toks)
    _assert_bitwise(l1, l2)
    _assert_bitwise(s1, s2)


@pytest.mark.parametrize("bb", [1, 2])   # bb=1 and bb=B//2, both < B
def test_batch_tiling_matches_full_batch(bb, rng):
    """Grid over batch tiles (bb < B, B % bb == 0) produces the same bits
    as one program covering the whole batch — the grid path the default
    whole-batch launch skips entirely."""
    from repro.kernels.fused_decode import fused_block_decode
    from repro.models import rwkv4
    model = get_model("rwkv4-169m", smoke=True)
    cfg = model.cfg
    params = model.cast_params(model.init_params(jax.random.PRNGKey(0)))
    lp = jax.tree_util.tree_map(lambda p: p[0], params["blocks"])
    st = jax.tree_util.tree_map(
        lambda p: p[0], _random_state(model, rng))
    x = jnp.asarray(rng.normal(size=(BATCH, cfg.d_model)), jnp.bfloat16)
    block = lambda l, s, xx: rwkv4.block_decode(l, s, xx, cfg)
    x_full, st_full = jax.jit(
        lambda xx, l, s: fused_block_decode(block, xx, l, s))(x, lp, st)
    x_tile, st_tile = jax.jit(
        lambda xx, l, s: fused_block_decode(block, xx, l, s, bb=bb))(
            x, lp, st)
    _assert_bitwise(x_full, x_tile)
    _assert_bitwise(st_full, st_tile)


def test_batch_tiling_rejects_ragged():
    """B % bb != 0 is a caller error, not a silent truncation."""
    from repro.kernels.fused_decode import fused_block_decode
    from repro.models import rwkv4
    model = get_model("rwkv4-169m", smoke=True)
    params = model.cast_params(model.init_params(jax.random.PRNGKey(0)))
    lp = jax.tree_util.tree_map(lambda p: p[0], params["blocks"])
    st = jax.tree_util.tree_map(
        lambda p: p[0], model.init_decode_state(BATCH, 0, jnp.bfloat16))
    x = jnp.zeros((BATCH, model.cfg.d_model), jnp.bfloat16)
    block = lambda l, s, xx: rwkv4.block_decode(l, s, xx, model.cfg)
    with pytest.raises(ValueError, match="not divisible"):
        fused_block_decode(block, x, lp, st, bb=3)


@pytest.mark.parametrize("bb", [1, 2])
def test_model_kernel_batch_tiling(bb, rng):
    """Megakernel batch tiling: the (B // bb, L) grid re-initializes the
    residual scratch at l == 0 of every batch tile, so tiled and
    whole-batch launches agree bit-for-bit."""
    from repro.models import rwkv4
    model = get_model("rwkv4-169m", smoke=True)
    cfg = model.cfg
    params = model.init_params(jax.random.PRNGKey(0))
    state = _random_state(model, rng)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (BATCH, 1)), jnp.int32)
    l1, s1 = jax.jit(lambda p, s, t: rwkv4.decode_step_fused_model(
        p, s, t, jnp.int32(0), cfg))(params, state, toks)
    l2, s2 = jax.jit(lambda p, s, t: rwkv4.decode_step_fused_model(
        p, s, t, jnp.int32(0), cfg, bb=bb))(params, state, toks)
    _assert_bitwise(l1, l2)
    _assert_bitwise(s1, s2)


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("arch", ARCHS)
def test_model_kernel_prepared_params(arch, quantized, rng):
    """The serving form — `prepare_fused_model_params` chunks the stacked
    weights into per-dtype contiguous slabs ONCE outside the step — is
    bit-identical to feeding the raw tree (fused per call), for fp and
    packed Δ-PoT weights."""
    model = get_model(arch, smoke=True)
    params = model.init_params(jax.random.PRNGKey(0))
    if quantized:
        params = pack_params(params)
    prep = model.prepare_fused_model_params(params)
    state = _random_state(model, rng)
    toks = jnp.asarray(rng.integers(0, model.cfg.vocab, (BATCH, 1)),
                       jnp.int32)
    step = jax.jit(model.decode_step_fused_model)
    l1, s1 = step(params, state, toks, jnp.int32(0))
    l2, s2 = step(prep, state, toks, jnp.int32(0))
    _assert_bitwise(l1, l2)
    _assert_bitwise(s1, s2)


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("arch", ARCHS)
def test_model_kernel_stream_binding(arch, quantized, rng):
    """The "stream" execution structure — the TPU default: grid over
    (batch tile, layer), layer-indexed BlockSpecs, VMEM-scratch residual
    carry — produces the same bits as the oracle and the off-TPU-default
    "resident" structure, exercised here through interpret mode."""
    model = get_model(arch, smoke=True)
    params = model.init_params(jax.random.PRNGKey(0))
    if quantized:
        params = pack_params(params)
    state = _random_state(model, rng)
    toks = jnp.asarray(rng.integers(0, model.cfg.vocab, (BATCH, 1)),
                       jnp.int32)
    oracle = jax.jit(lambda p, s, t: model.decode_step(
        unpack_params(p) if quantized else p, s, t, jnp.int32(0)))
    stream = jax.jit(lambda p, s, t: model.module.decode_step_fused_model(
        p, s, t, jnp.int32(0), model.cfg, weights="stream"))
    l1, s1 = oracle(params, state, toks)
    l2, s2 = stream(params, state, toks)
    _assert_bitwise(l1, l2)
    _assert_bitwise(s1, s2)


def test_model_kernel_stream_binding_hw_and_tiling(rng):
    """Stream binding composed with (a) the hw LUT operands at full batch
    and (b) fp bb < B batch tiling (scratch re-initializes per tile).
    hw + bb < B is deliberately NOT pinned: the A9 activation fake-quant
    scales over the whole batch, so tiling changes the quantization grain
    — an intrinsic property of the hw numerics, not a kernel bug (the
    per-block kernel has the same behavior)."""
    from repro.models import rwkv4
    model = get_model("rwkv4-169m", smoke=True)
    cfg = model.cfg
    params = model.cast_params(model.init_params(jax.random.PRNGKey(0)))
    state = _random_state(model, rng)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (BATCH, 1)), jnp.int32)
    l1, s1 = jax.jit(lambda p, s, t: rwkv4.decode_step(
        p, s, t, jnp.int32(0), cfg, hw=True))(params, state, toks)
    l2, s2 = jax.jit(lambda p, s, t: rwkv4.decode_step_fused_model(
        p, s, t, jnp.int32(0), cfg, hw=True, weights="stream"))(
            params, state, toks)
    _assert_bitwise(l1, l2)
    _assert_bitwise(s1, s2)
    l3, s3 = jax.jit(lambda p, s, t: rwkv4.decode_step_fused_model(
        p, s, t, jnp.int32(0), cfg, weights="stream", bb=2))(
            params, state, toks)
    l4, s4 = jax.jit(lambda p, s, t: rwkv4.decode_step(
        p, s, t, jnp.int32(0), cfg))(params, state, toks)
    _assert_bitwise(l4, l3)
    _assert_bitwise(s4, s3)


def test_prepared_hw_mismatch_rejected():
    """rwkv4: decoding with hw= opposite to how the params were prepared
    is an error, not silently-wrong numerics (the LUT operands travel
    with the prepared stack)."""
    from repro.models import rwkv4
    model = get_model("rwkv4-169m", smoke=True)
    cfg = model.cfg
    params = model.init_params(jax.random.PRNGKey(0))
    state = model.init_decode_state(BATCH, 0, jnp.bfloat16)
    toks = jnp.zeros((BATCH, 1), jnp.int32)
    prep_fp = model.prepare_fused_model_params(params)
    prep_hw = model.prepare_fused_model_params(params, hw=True)
    with pytest.raises(ValueError, match="hw="):
        rwkv4.decode_step_fused_model(prep_fp, state, toks, jnp.int32(0),
                                      cfg, hw=True)
    with pytest.raises(ValueError, match="hw="):
        rwkv4.decode_step_fused_model(prep_hw, state, toks, jnp.int32(0),
                                      cfg, hw=False)
    # matched prepare/decode works and equals the oracle
    l1, _ = jax.jit(lambda p, s, t: rwkv4.decode_step(
        model.cast_params(p), s, t, jnp.int32(0), cfg, hw=True))(
            params, state, toks)
    l2, _ = jax.jit(lambda p, s, t: rwkv4.decode_step_fused_model(
        p, s, t, jnp.int32(0), cfg, hw=True))(prep_hw, state, toks)
    _assert_bitwise(l1, l2)


def test_fuse_layer_stack_roundtrip(rng):
    """fuse_layer_stack -> unfuse_layer is bit-exact per layer and routes
    broadcast leading-1 leaves (shared Δ-PoT scales) to the resident aux
    operands instead of the slabs."""
    from repro.core.quant.serving import (
        fuse_layer_stack, pack_params, unfuse_layer)
    model = get_model("rwkv4-169m", smoke=True)
    blocks = pack_params(model.init_params(jax.random.PRNGKey(0)))["blocks"]
    Lc = model.cfg.n_layers
    stack = fuse_layer_stack(blocks, Lc)
    # Δ-PoT code planes are chunked, one slab per last-axis width
    assert any(k.startswith("uint8:") for k in stack.slabs)
    assert len(stack.aux) > 0              # shared scales stay resident
    flat, _ = jax.tree_util.tree_flatten(blocks)
    for l in range(Lc):
        rows = {k: v[l] for k, v in stack.slabs.items()}
        aux = [a[0] for a in stack.aux]
        layer = unfuse_layer(rows, aux, stack.manifest, stack.tdef)
        expect = jax.tree_util.tree_map(
            lambda a: a[l] if a.shape[0] == Lc else a[0], blocks)
        _assert_bitwise(expect, layer)


@pytest.mark.parametrize("arch,quantized,fits", [
    ("rwkv4-169m", True, True), ("rwkv4-169m", False, True),
    ("rwkv4-7b", True, False), ("rwkv6-7b", True, False)])
def test_megakernel_vmem_budget(arch, quantized, fits):
    """The streamed megakernel's VMEM request holds two layer blocks; a
    published width whose blocks cannot fit is refused with a clear error
    (build_plan calls this on a compiling backend) instead of failing
    inside the compiler.  Shapes only: nothing is allocated."""
    from repro.core.quant.serving import pack_params
    from repro.kernels.fused_decode import megakernel_vmem_bytes
    model = get_model(arch, smoke=False)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    if quantized:
        params = jax.eval_shape(pack_params, params)
    stack = jax.eval_shape(model.prepare_fused_model_params, params)["blocks"]
    if fits:
        assert megakernel_vmem_bytes(stack) <= 128 * 2**20
    else:
        with pytest.raises(ValueError, match="K-tiled weight stream"):
            megakernel_vmem_bytes(stack)


@pytest.mark.parametrize("arch", ARCHS)
def test_model_kernel_single_launch(arch):
    """THE megakernel claim: one model decode step issues exactly ONE
    pallas_call — vs L for the per-block fused path (a scan of L
    launches), counted by jaxpr traversal with scan trip counts."""
    model = get_model(arch, smoke=True)
    params = model.init_params(jax.random.PRNGKey(0))
    state = model.init_decode_state(BATCH, 0, jnp.bfloat16)
    toks = jnp.zeros((BATCH, 1), jnp.int32)
    jx_model = jax.make_jaxpr(lambda p, s, t: model.decode_step_fused_model(
        p, s, t, jnp.int32(0)))(params, state, toks)
    jx_block = jax.make_jaxpr(lambda p, s, t: model.decode_step_fused(
        p, s, t, jnp.int32(0)))(params, state, toks)
    assert count_pallas_launches(jx_model.jaxpr) == 1
    assert count_pallas_launches(jx_block.jaxpr) == model.cfg.n_layers
    # and the per-op oracle issues none at all
    jx_oracle = jax.make_jaxpr(lambda p, s, t: model.decode_step(
        p, s, t, jnp.int32(0)))(params, state, toks)
    assert count_pallas_launches(jx_oracle.jaxpr) == 0


@pytest.mark.parametrize("fused", ["block", "model"])
@pytest.mark.parametrize("quantized", [False, True])
def test_engine_greedy_equivalence(quantized, fused):
    """ServingEngine(fused_decode="block"/"model") streams the exact token
    sequences of the per-op engine — greedy decode is
    bitwise-deterministic, so this is an end-to-end bit-parity check
    through admission, chunked prefill, masked decode, and retirement."""
    from repro.serving import ServingEngine
    model = get_model("rwkv4-169m", smoke=True)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, model.cfg.vocab, size=n).tolist()
               for n in (3, 9, 17, 5)]

    def run(mode):
        eng = ServingEngine(model, params=params, max_batch=3,
                            prefill_chunk=4, quantized=quantized,
                            fused_decode=mode)
        handles = [eng.submit(p, max_new_tokens=8) for p in prompts]
        eng.run()
        return [h.tokens for h in handles]

    assert run(False) == run(fused)


def test_engine_fused_decode_true_is_block():
    """PR 2 compatibility: fused_decode=True still means the per-block
    kernel, and bogus modes are rejected up front."""
    from repro.serving import ServingEngine
    model = get_model("rwkv4-169m", smoke=True)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params=params, fused_decode=True)
    assert eng.fused_decode == "block"
    with pytest.raises(ValueError, match="fused_decode"):
        ServingEngine(model, params=params, fused_decode="layerwise")


def test_fused_capability_flag():
    """has_fused_decode / has_fused_model_decode mark exactly the models
    shipping the kernels; the engine refuses fused_decode for anything
    else."""
    for arch in ARCHS:
        m = get_model(arch, smoke=True)
        assert m.has_fused_decode
        assert m.has_fused_model_decode
    z = get_model("zamba2-7b", smoke=True)
    assert not z.has_fused_decode
    assert not z.has_fused_model_decode


# ---------------------------------------------------------------------------
# Mixed weight planes (W8 / W4-nibble / VQ-codebook per tensor)
# ---------------------------------------------------------------------------

# One tensor family per plane so every decode branch runs: wk streams W4
# nibble pairs, the FFN down-projection gathers a VQ codebook, the head is
# W4, everything else stays scalar W8.
MIXED_PLANES_POLICY = None


def _mixed_policy():
    global MIXED_PLANES_POLICY
    if MIXED_PLANES_POLICY is None:
        from repro.core.quant.policy import PlanePolicy
        MIXED_PLANES_POLICY = PlanePolicy(default="w8", overrides=(
            (r"\['att'\]\['wk'\]", "w4"),
            (r"\['ffn'\]\['wv'\]", "vq"),
            (r"\['head'\]", "w4"),
        ))
    return MIXED_PLANES_POLICY


@pytest.mark.parametrize("mode", ["block", "model"])
@pytest.mark.parametrize("arch", ARCHS)
def test_mixed_plane_bit_parity(arch, mode, rng):
    """A tree packed under a MIXED plane policy runs every fused decode
    granularity bit-identically to the per-op unpack oracle: the uint8
    slab carries W8 codes, W4 nibble pairs (half bytes) and VQ indices
    side by side; scales AND codebooks ride the resident const maps."""
    model = get_model(arch, smoke=True)
    packed = pack_params(model.init_params(jax.random.PRNGKey(0)),
                         _mixed_policy())
    state = _random_state(model, rng)
    toks = jnp.asarray(rng.integers(0, model.cfg.vocab, (BATCH, 1)),
                       jnp.int32)
    oracle = jax.jit(lambda p, s, t: model.decode_step(
        unpack_params(p), s, t, jnp.int32(0)))
    fused = jax.jit(lambda p, s, t: _fused_step(model, mode)(
        p, s, t, jnp.int32(0)))
    l1, s1 = oracle(packed, state, toks)
    l2, s2 = fused(packed, state, toks)
    _assert_bitwise(l1, l2)
    _assert_bitwise(s1, s2)


@pytest.mark.parametrize("arch", ARCHS)
def test_mixed_plane_prepared_megakernel(arch, rng):
    """The serving form — `prepare_fused_model_params` over a mixed-plane
    tree (per-dtype slabs + resident codebooks) — matches the per-op
    oracle bitwise, and still launches exactly ONE pallas_call."""
    model = get_model(arch, smoke=True)
    packed = pack_params(model.init_params(jax.random.PRNGKey(0)),
                         _mixed_policy())
    prep = model.prepare_fused_model_params(packed)
    state = _random_state(model, rng)
    toks = jnp.asarray(rng.integers(0, model.cfg.vocab, (BATCH, 1)),
                       jnp.int32)
    oracle = jax.jit(lambda p, s, t: model.decode_step(
        unpack_params(p), s, t, jnp.int32(0)))
    mega = jax.jit(lambda p, s, t: model.decode_step_fused_model(
        p, s, t, jnp.int32(0)))
    l1, s1 = oracle(packed, state, toks)
    l2, s2 = mega(prep, state, toks)
    _assert_bitwise(l1, l2)
    _assert_bitwise(s1, s2)
    jx = jax.make_jaxpr(lambda s, t: mega(prep, s, t))(state, toks)
    assert count_pallas_launches(jx.jaxpr) == 1


def test_mixed_plane_engine_greedy_equivalence():
    """The engine serves a mixed-plane plan end to end: fused decode
    produces the same greedy tokens as the per-op path under the SAME
    plane policy."""
    from repro.serving import ServingEngine
    model = get_model("rwkv4-169m", smoke=True)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, model.cfg.vocab, size=n).tolist()
               for n in (3, 5)]

    def run(fused):
        eng = ServingEngine(model, params=params, quantized=True,
                            plane_policy=_mixed_policy(),
                            fused_decode=fused, max_batch=2)
        handles = [eng.submit(p, max_new_tokens=8) for p in prompts]
        eng.run()
        return [h.tokens for h in handles]

    assert run(False) == run("model")
