"""Fused chunked prefill vs the per-op scan-of-decode_step oracle.

The contract under test (docs/kernels.md §fused chunked prefill): the
fused path — chunk-shaped matmuls + the masked on-chip WKV sequence
kernel, packed Δ-PoT weights decoded in-kernel — is BIT-IDENTICAL to a
`lax.scan` of `decode_step` with the engine's per-step masked state
commits, for fp and packed weights, rwkv4 and rwkv6, hw LUT numerics,
and any per-slot PREFIX validity mask (partial chunks, empty lanes).

Both sides compile with defined rounding semantics
(`kernels.common.exact_jit` — `xla_allow_excess_precision=False`), the
property that makes differently-structured programs with the same
per-op math bitwise comparable; the serving engine compiles its two
prefill programs the same way.

Engine-level: `ServingEngine(fused_prefill=True)` streams the exact
greedy tokens of the per-op engine through admission, ragged prompts,
chunk-boundary splits, mid-prefill cancellation, and slot reuse — plus
the packed path never unpacks weights in its trace (jaxpr inspection).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.extend import core as jcore

from repro.core.quant.serving import pack_params
from repro.kernels.common import exact_jit
from repro.models.registry import get_model
from repro.serving import ServingEngine

ARCHS = ["rwkv4-169m", "rwkv6-7b"]
B, C = 4, 6
# per-slot prefix masks: full, partial, empty (a decode/free lane), single
PREFIX_LENS = (C, 3, 0, 1)


def _random_state(model, rng, batch=B, dtype=jnp.bfloat16):
    state = model.init_decode_state(batch, 0, dtype)

    def fill(leaf):
        vals = rng.normal(size=leaf.shape).astype(np.float32)
        if np.all(np.asarray(leaf, np.float32) < -1e30):  # wkv_o running max
            vals = vals - 1.0
        return jnp.asarray(vals, leaf.dtype)

    return jax.tree_util.tree_map(fill, state)


def _prefix_valid(lens, cols=C):
    valid = np.zeros((len(lens), cols), bool)
    for i, n in enumerate(lens):
        valid[i, :n] = True
    return jnp.asarray(valid)


def _assert_bitwise(tree_a, tree_b):
    la = jax.tree_util.tree_leaves(tree_a)
    lb = jax.tree_util.tree_leaves(tree_b)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def oracle_prefill(model, params, state, tokens, valid, *,
                   quantized=False, hw=False):
    """The engine's per-op prefill semantics: scan `decode_step` over the
    chunk, committing state only where `valid` — through the SAME
    `masked_state_commit` / `maybe_unpack` the plan's programs use
    (repro.serving.plan), so the masking semantics exist in exactly one
    place and the oracle can never drift from the engine."""
    from repro.serving.plan import masked_state_commit, maybe_unpack
    axes = model.decode_state_batch_axes()
    masked = lambda new, old, mask: masked_state_commit(new, old, mask,
                                                        axes)
    p = maybe_unpack(params, quantized)
    if hw:
        step = lambda pp, s, t: model.module.decode_step(
            pp, s, t, jnp.int32(0), model.cfg, hw=True)
    else:
        step = lambda pp, s, t: model.decode_step(pp, s, t, jnp.int32(0))

    def body(carry, xs):
        st, last = carry
        tok, ok = xs
        logits, stepped = step(p, st, tok[:, None])
        return (masked(stepped, st, ok),
                jnp.where(ok[:, None, None], logits, last)), None

    last0 = jnp.zeros((tokens.shape[0], 1, model.cfg.vocab),
                      jnp.dtype(model.cfg.dtype))
    (st, last), _ = jax.lax.scan(body, (state, last0), (tokens.T, valid.T))
    return st, last


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_chunk_bit_parity(arch, quantized, rng):
    """THE tentpole claim: fused chunked prefill == masked scan of
    decode_step, bit for bit — states AND last-valid logits — over full,
    partial, empty and single-token prefix masks, from random (non-fresh)
    recurrent states."""
    model = get_model(arch, smoke=True)
    params = model.init_params(jax.random.PRNGKey(0))
    if quantized:
        params = pack_params(params)
    state = _random_state(model, rng)
    tokens = jnp.asarray(rng.integers(0, model.cfg.vocab, (B, C)),
                         jnp.int32)
    valid = _prefix_valid(PREFIX_LENS)
    s1, l1 = exact_jit(lambda p, s: oracle_prefill(
        model, p, s, tokens, valid, quantized=quantized))(params, state)
    prep = model.prepare_prefill_params(params) if quantized else params
    s2, l2 = exact_jit(lambda p, s: model.prefill_chunk(
        p, s, tokens, valid))(prep, state)
    _assert_bitwise(s1, s2)
    _assert_bitwise(l1, l2)


def test_prefill_chunk_hw_numerics_parity(rng):
    """The paper's LUT/PWL numerics compose with the fused prefill: the
    EXP/DIV tables ride into the WKV kernel as operands, and the A9
    activation fake-quant is scoped per token position — same bits as
    scanning decode_step(hw=True)."""
    from repro.models import rwkv4
    model = get_model("rwkv4-169m", smoke=True)
    params = model.cast_params(model.init_params(jax.random.PRNGKey(0)))
    state = _random_state(model, rng)
    tokens = jnp.asarray(rng.integers(0, model.cfg.vocab, (B, C)),
                         jnp.int32)
    valid = _prefix_valid(PREFIX_LENS)
    s1, l1 = exact_jit(lambda p, s: oracle_prefill(
        model, p, s, tokens, valid, hw=True))(params, state)
    s2, l2 = exact_jit(lambda p, s: rwkv4.prefill_chunk(
        p, s, tokens, valid, jnp.int32(0), model.cfg, hw=True))(
            params, state)
    _assert_bitwise(s1, s2)
    _assert_bitwise(l1, l2)


def test_chunk_matmul_packed_equals_unpack(rng):
    """`chunk_matmul` on a packed leaf == `x @ unpack_leaf(leaf).astype`
    exactly: the kernel body calls the SAME unpack_leaf, tiles never split
    the contraction."""
    from repro.core.quant.serving import unpack_leaf
    from repro.kernels.fused_prefill import chunk_matmul
    from repro.core.quant.delta_pot import FORMAT_W8, dpot_pack_int8, \
        dpot_quantize
    w = jnp.asarray(rng.normal(size=(48, 80)), jnp.float32)
    q = dpot_quantize(w, FORMAT_W8, axis=-1)
    leaf = {"packed": dpot_pack_int8(q), "scale": q.scale.astype(jnp.float32)}
    x = jnp.asarray(rng.normal(size=(3, 5, 48)), jnp.bfloat16)
    got = exact_jit(lambda x, l: chunk_matmul(x, l, jnp.bfloat16))(x, leaf)
    want = exact_jit(
        lambda x, l: x @ unpack_leaf(l).astype(jnp.bfloat16))(x, leaf)
    _assert_bitwise(want, got)


def test_shifted_prev_prefix_semantics():
    """Position t sees seq_{t-1} inside the prefix, the LAST valid entry
    after it (the oracle's frozen carry), and `first` at t=0 / empty."""
    from repro.kernels.fused_prefill import shifted_prev
    seq = jnp.arange(1, 5, dtype=jnp.float32).reshape(1, 4, 1)
    seq = jnp.concatenate([seq, seq * 10], 0)          # (2, 4, 1)
    first = jnp.asarray([[100.0], [200.0]])
    valid = _prefix_valid((2, 0), cols=4)
    out = np.asarray(shifted_prev(seq, first, valid))[..., 0]
    np.testing.assert_array_equal(out[0], [100.0, 1.0, 2.0, 2.0])
    np.testing.assert_array_equal(out[1], [200.0] * 4)


# ---------------------------------------------------------------------------
# No-unpack-in-trace: jaxpr inspection of the packed prefill program
# ---------------------------------------------------------------------------


def _outside_kernel_primitives(jaxpr, acc):
    """Primitive names appearing OUTSIDE pallas_call kernels (recursing
    into scan/cond bodies but NOT into kernel jaxprs)."""
    for eqn in jaxpr.eqns:
        acc.add(eqn.primitive.name)
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            vals = v if isinstance(v, (list, tuple)) else [v]
            for e in vals:
                if isinstance(e, jcore.ClosedJaxpr):
                    _outside_kernel_primitives(e.jaxpr, acc)
                elif isinstance(e, jcore.Jaxpr):
                    _outside_kernel_primitives(e, acc)
    return acc


def _pallas_consumes_uint8(jaxpr):
    found = [False]

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call" and any(
                    getattr(v.aval, "dtype", None) == jnp.uint8
                    for v in eqn.invars):
                found[0] = True
            for v in eqn.params.values():
                vals = v if isinstance(v, (list, tuple)) else [v]
                for e in vals:
                    if isinstance(e, jcore.ClosedJaxpr):
                        walk(e.jaxpr)
                    elif isinstance(e, jcore.Jaxpr):
                        walk(e)
    walk(jaxpr)
    return found[0]


@pytest.mark.parametrize("arch", ARCHS)
def test_packed_prefill_never_unpacks_in_trace(arch):
    """THE bandwidth claim: with packed Δ-PoT weights the fused prefill
    trace contains NO weight decode outside a Pallas kernel — the decode's
    signature `exp2` appears only inside kernels, and the uint8 code
    planes are consumed by pallas_call directly.  The per-op oracle, by
    contrast, unpacks in-trace (detector sanity check)."""
    model = get_model(arch, smoke=True)
    packed = pack_params(model.init_params(jax.random.PRNGKey(0)))
    prep = model.prepare_prefill_params(packed)
    state = model.init_decode_state(B, 0, jnp.bfloat16)
    tokens = jnp.zeros((B, C), jnp.int32)
    valid = jnp.ones((B, C), bool)
    jx = jax.make_jaxpr(lambda p, s: model.prefill_chunk(
        p, s, tokens, valid))(prep, state)
    outside = _outside_kernel_primitives(jx.jaxpr, set())
    assert "exp2" not in outside, (
        "packed Δ-PoT decode leaked out of the kernels into the prefill "
        "trace")
    assert _pallas_consumes_uint8(jx.jaxpr)
    # detector sanity: the per-op oracle DOES decode in-trace
    jx_oracle = jax.make_jaxpr(lambda p, s: oracle_prefill(
        model, p, s, tokens, valid, quantized=True))(packed, state)
    assert "exp2" in _outside_kernel_primitives(jx_oracle.jaxpr, set())


# ---------------------------------------------------------------------------
# Engine-level equivalence + prefill edge cases
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def rwkv4():
    model = get_model("rwkv4-169m", smoke=True)
    return model, model.init_params(jax.random.PRNGKey(0))


def _engine(model, params, *, fused_prefill, max_batch=3, chunk=4, **kw):
    return ServingEngine(model, params=params, max_batch=max_batch,
                         prefill_chunk=chunk, fused_prefill=fused_prefill,
                         **kw)


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("arch", ARCHS)
def test_engine_greedy_equivalence(arch, quantized):
    """End-to-end: the fused-prefill engine streams the exact token
    sequences of the per-op engine — prompts shorter than one chunk (1),
    exactly one chunk (4), a non-multiple of the chunk (9, 17), through
    admission, chunked prefill, masked decode and retirement."""
    model = get_model(arch, smoke=True)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, model.cfg.vocab, size=n).tolist()
               for n in (1, 4, 9, 17)]

    def run(fused):
        eng = _engine(model, params, fused_prefill=fused,
                      quantized=quantized)
        handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.run()
        assert eng.trace_counts == {"decode": 1, "prefill": 1}
        return [h.tokens for h in handles]

    assert run(False) == run(True)


def test_engine_cancel_mid_prefill(rwkv4):
    """A request cancelled MID-PREFILL frees its slot; the next admission
    resets the lane via the fresh mask.  Fused and per-op engines agree on
    every surviving request's tokens."""
    model, params = rwkv4
    rng = np.random.default_rng(3)
    long_prompt = rng.integers(0, model.cfg.vocab, size=30).tolist()
    others = [rng.integers(0, model.cfg.vocab, size=n).tolist()
              for n in (5, 11)]

    def run(fused):
        eng = _engine(model, params, fused_prefill=fused, max_batch=1)
        h_long = eng.submit(long_prompt, max_new_tokens=8)
        hs = [eng.submit(p, max_new_tokens=4) for p in others]
        eng.step()                    # absorbs one 4-token chunk of 30
        assert not h_long.done
        assert eng.cancel(h_long)     # slot freed with partial state
        eng.run()
        assert all(h.done for h in hs)
        return [h.tokens for h in hs]

    assert run(False) == run(True)


def test_engine_slot_reuse_after_retire(rwkv4):
    """A slot freed by retirement and re-admitted (max_batch=1 forces
    immediate reuse) must not leak the previous request's state into the
    next — the fresh-lane reset inside the prefill call covers fused and
    per-op identically."""
    model, params = rwkv4
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, model.cfg.vocab, size=n).tolist()
               for n in (7, 7, 3)]

    def run(fused):
        eng = _engine(model, params, fused_prefill=fused, max_batch=1)
        hs = [eng.submit(p, max_new_tokens=3) for p in prompts]
        eng.run()
        return [h.tokens for h in hs]

    from repro.launch.serve import sequential_decode
    toks = run(True)
    assert toks == run(False)
    # and both equal decoding each request alone (no cross-request leak)
    for p, t in zip(prompts, toks):
        assert t == sequential_decode(model, params, p, 3)


def test_engine_temperature_sampling_equivalence(rwkv4):
    """Seeded Gumbel sampling is bit-stable across prefill modes (the
    batched sampler draws from each slot's own RNG stream)."""
    model, params = rwkv4

    def run(fused):
        eng = _engine(model, params, fused_prefill=fused, max_batch=2)
        h1 = eng.submit([1, 2, 3, 4, 5], max_new_tokens=6,
                        temperature=0.9, seed=13)
        h2 = eng.submit([7, 8], max_new_tokens=6, temperature=0.7, seed=5)
        eng.run()
        return h1.tokens, h2.tokens

    assert run(False) == run(True)


def test_engine_rejects_fused_prefill_without_entry(monkeypatch):
    assert not get_model("zamba2-7b", smoke=True).has_fused_prefill
    # an otherwise engine-capable model without the fused-prefill entry
    from repro.models import rwkv4
    monkeypatch.delattr(rwkv4, "prefill_chunk")
    model = get_model("rwkv4-169m", smoke=True)
    assert not model.has_fused_prefill
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServingEngine(model, fused_prefill=True)


def test_fused_prefill_capability_flag():
    for arch in ARCHS:
        assert get_model(arch, smoke=True).has_fused_prefill


# ---------------------------------------------------------------------------
# Batched host-side sampling + TTFT telemetry
# ---------------------------------------------------------------------------


def test_sample_tokens_matches_per_row_reference(rng):
    """The batched sampler consumes each slot's RNG stream exactly like
    the per-row reference, and the batched argmax resolves greedy ties
    identically."""
    from repro.serving.scheduler import Request, _Slot, sample_token, \
        sample_tokens
    V, n = 32, 5
    rows = rng.normal(size=(n, V)).astype(np.float32)
    rows[0, 3] = rows[0, 7] = rows[0].max() + 1.0       # greedy tie
    temps = [0.0, 0.9, 0.0, 0.3, 1.7]
    metas = [_Slot(req=Request(rid=i, prompt=[1], temperature=t, seed=i),
                   rng=np.random.default_rng(i)) for i, t in enumerate(temps)]
    got = sample_tokens(rows.copy(), metas)
    ref_rngs = [np.random.default_rng(i) for i in range(n)]
    want = [sample_token(rows[i], temps[i], ref_rngs[i]) for i in range(n)]
    assert list(got) == want
    # streams advanced identically: the NEXT draw matches too
    for m, r in zip(metas, ref_rngs):
        if m.req.temperature > 0:
            assert m.rng.standard_normal() == r.standard_normal()


def test_counters_prefill_ttft_tracking(rwkv4):
    """ServingCounters decomposes TTFT: per-request prefill ticks and
    admit->first-token wall time, with cancelled requests dropped."""
    from repro.runtime.monitor import ServingCounters
    model, params = rwkv4
    t = [0.0]
    clock = lambda: t.__setitem__(0, t[0] + 1.0) or t[0]
    counters = ServingCounters(clock=clock)
    eng = ServingEngine(model, params=params, max_batch=2, prefill_chunk=4,
                        fused_prefill=True, counters=counters)
    eng.submit(list(range(1, 10)), max_new_tokens=2)   # 9 tokens -> 3 ticks
    eng.submit([1, 2], max_new_tokens=2)               # 2 tokens -> 1 tick
    snap = eng.run()
    assert sorted(counters.prefill_ticks) == [1, 3]
    assert len(counters.prefill_s) == 2
    assert all(s > 0 for s in counters.prefill_s)
    assert snap["mean_prefill_ticks"] == 2.0
    assert snap["mean_prefill_s"] > 0
    assert snap["prefill_tokens"] == 11


# ---------------------------------------------------------------------------
# Mixed weight planes through the fused prefill
# ---------------------------------------------------------------------------


def _mixed_policy():
    from repro.core.quant.policy import PlanePolicy
    return PlanePolicy(default="w8", overrides=(
        (r"\['att'\]\['wk'\]", "w4"),
        (r"\['ffn'\]\['wv'\]", "vq"),
        (r"\['head'\]", "w4"),
    ))


def test_chunk_matmul_w4_equals_unpack(rng):
    """`chunk_matmul` on a W4 nibble-packed leaf == the unpack oracle
    exactly: the kernel re-interleaves the nibble pairs with the SAME
    decode as `unpack_leaf`, and the streamed tile is HALF the bytes."""
    from repro.core.quant.serving import unpack_leaf
    from repro.kernels.fused_prefill import chunk_matmul
    from repro.core.quant.delta_pot import FORMAT_W4, dpot_pack_nibbles, \
        dpot_quantize
    w = jnp.asarray(rng.normal(size=(48, 80)), jnp.float32)
    q = dpot_quantize(w, FORMAT_W4, axis=-1)
    leaf = {"packed4": dpot_pack_nibbles(q),
            "scale": q.scale.astype(jnp.float32)}
    assert leaf["packed4"].shape == (24, 80)
    x = jnp.asarray(rng.normal(size=(3, 5, 48)), jnp.bfloat16)
    got = exact_jit(lambda x, l: chunk_matmul(x, l, jnp.bfloat16))(x, leaf)
    want = exact_jit(
        lambda x, l: x @ unpack_leaf(l).astype(jnp.bfloat16))(x, leaf)
    _assert_bitwise(want, got)


def test_chunk_matmul_vq_equals_unpack(rng):
    """`chunk_matmul` on a VQ leaf == the unpack oracle exactly: the
    codebook enters the kernel flattened with a constant index map (one
    resident copy, uint8 indices streamed)."""
    from repro.core.quant.serving import unpack_leaf
    from repro.core.quant.vq import vq_quantize
    from repro.kernels.fused_prefill import chunk_matmul
    w = jnp.asarray(rng.normal(size=(48, 80)), jnp.float32)
    idx, codebook = vq_quantize(w, 64)
    leaf = {"vq_idx": idx, "codebook": codebook}
    x = jnp.asarray(rng.normal(size=(3, 5, 48)), jnp.bfloat16)
    got = exact_jit(lambda x, l: chunk_matmul(x, l, jnp.bfloat16))(x, leaf)
    want = exact_jit(
        lambda x, l: x @ unpack_leaf(l).astype(jnp.bfloat16))(x, leaf)
    _assert_bitwise(want, got)


@pytest.mark.parametrize("arch", ARCHS)
def test_mixed_plane_prefill_bit_parity(arch, rng):
    """Fused chunked prefill over a MIXED-plane tree (W4 wk, VQ ffn.wv,
    W4 head, W8 rest) == the masked per-op scan oracle, bit for bit,
    under prefix masks including an all-invalid lane."""
    model = get_model(arch, smoke=True)
    params = pack_params(model.init_params(jax.random.PRNGKey(0)),
                         _mixed_policy())
    state = _random_state(model, rng)
    tokens = jnp.asarray(rng.integers(0, model.cfg.vocab, (B, C)),
                         jnp.int32)
    valid = _prefix_valid(PREFIX_LENS)
    s1, l1 = exact_jit(lambda p, s: oracle_prefill(
        model, p, s, tokens, valid, quantized=True))(params, state)
    prep = model.prepare_prefill_params(params)
    s2, l2 = exact_jit(lambda p, s: model.prefill_chunk(
        p, s, tokens, valid))(prep, state)
    _assert_bitwise(s1, s2)
    _assert_bitwise(l1, l2)


def _outside_kernel_flat_gather(jaxpr):
    """True if a gather with a 1-D operand (the flattened VQ codebook)
    appears OUTSIDE pallas_call kernels.  The embedding gather is exempt:
    its operand is the 2-D (V, D) table."""
    found = [False]

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                continue
            if eqn.primitive.name == "gather" and \
                    getattr(eqn.invars[0].aval, "ndim", 0) == 1:
                found[0] = True
            for v in eqn.params.values():
                vals = v if isinstance(v, (list, tuple)) else [v]
                for e in vals:
                    if isinstance(e, jcore.ClosedJaxpr):
                        walk(e.jaxpr)
                    elif isinstance(e, jcore.Jaxpr):
                        walk(e)
    walk(jaxpr)
    return found[0]


@pytest.mark.parametrize("arch", ARCHS)
def test_mixed_plane_prefill_never_decodes_in_trace(arch):
    """The bandwidth claim for ALL planes: the mixed-plane fused prefill
    trace contains no weight decode outside a Pallas kernel — no exp2
    (W8/W4 Δ-PoT decode) and no 1-D-operand gather (VQ codebook lookup)
    outside pallas_call; the uint8 planes are consumed by kernels
    directly.  The per-op oracle trips both detectors."""
    model = get_model(arch, smoke=True)
    packed = pack_params(model.init_params(jax.random.PRNGKey(0)),
                         _mixed_policy())
    prep = model.prepare_prefill_params(packed)
    state = model.init_decode_state(B, 0, jnp.bfloat16)
    tokens = jnp.zeros((B, C), jnp.int32)
    valid = jnp.ones((B, C), bool)
    jx = jax.make_jaxpr(lambda p, s: model.prefill_chunk(
        p, s, tokens, valid))(prep, state)
    outside = _outside_kernel_primitives(jx.jaxpr, set())
    assert "exp2" not in outside, (
        "Δ-PoT decode leaked out of the kernels into the prefill trace")
    assert not _outside_kernel_flat_gather(jx.jaxpr), (
        "VQ codebook gather leaked out of the kernels")
    assert _pallas_consumes_uint8(jx.jaxpr)
    # detector sanity: the per-op oracle decodes in-trace
    jx_oracle = jax.make_jaxpr(lambda p, s: oracle_prefill(
        model, p, s, tokens, valid, quantized=True))(packed, state)
    assert "exp2" in _outside_kernel_primitives(jx_oracle.jaxpr, set())
    assert _outside_kernel_flat_gather(jx_oracle.jaxpr)
