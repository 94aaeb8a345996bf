"""RWKV-4 — the paper's model (BlinkDL RWKV-4, faithful block structure).

Block = TimeMix (token-shift → r/k/v projections → WKV recurrence →
sigmoid(r)-gated output) + ChannelMix (token-shift → squared-ReLU FFN with
sigmoid(r) gate), each preceded by LayerNorm, plus the pre-block ln0.

Two numerics modes:
  * standard  — f32/bf16 math (training + FP baseline)
  * hw        — the accelerator's numerics (paper §3–4): Δ-PoT-dequantized
    weights are supplied by the caller; activations fake-quantized to 9-bit;
    exp/sigmoid/division via the LUT/PWL units (repro.core.approx).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.approx import exp_lut, sigmoid_pwl, div_lut
from repro.core.quant.uniform import uniform_fake_quant
from repro.core.wkv.wkv4 import wkv4_scan, wkv4_step, WKV4State
from repro.models import layers as L
from repro.models.param import P
from repro.parallel.sharding import constrain


def _stack(spec, n: int):
    return jax.tree_util.tree_map(
        lambda p: P((n, *p.shape), ("layers", *p.axes), init=p.init,
                    scale=p.scale, const=p.const),
        spec, is_leaf=lambda x: isinstance(x, P))


def _block_spec(cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "ln1": L.spec_norm(d, "layernorm"),
        "ln2": L.spec_norm(d, "layernorm"),
        "att": {
            "time_mix_r": P((d,), (None,), init="uniform", scale=0.5),
            "time_mix_k": P((d,), (None,), init="uniform", scale=0.5),
            "time_mix_v": P((d,), (None,), init="uniform", scale=0.5),
            "time_decay": P((d,), (None,), init="zeros"),   # w = exp(·)
            "time_first": P((d,), (None,), init="zeros"),   # bonus u
            "wr": P((d, d), ("fsdp", "tp")),
            "wk": P((d, d), ("fsdp", "tp")),
            "wv": P((d, d), ("fsdp", "tp")),
            "wo": P((d, d), ("tp", "fsdp")),
        },
        "ffn": {
            "time_mix_r": P((d,), (None,), init="uniform", scale=0.5),
            "time_mix_k": P((d,), (None,), init="uniform", scale=0.5),
            "wr": P((d, d), ("fsdp", "tp")),
            "wk": P((d, f), ("fsdp", "tp")),
            "wv": P((f, d), ("tp", "fsdp")),
        },
    }


def spec(cfg: ModelConfig) -> dict:
    return {
        "embed": P((cfg.vocab, cfg.d_model), ("tp", "fsdp"), scale=0.02),
        "ln0": L.spec_norm(cfg.d_model, "layernorm"),
        "blocks": _stack(_block_spec(cfg), cfg.n_layers),
        "ln_f": L.spec_norm(cfg.d_model, "layernorm"),
        "head": P((cfg.d_model, cfg.vocab), ("fsdp", "tp")),
    }


# ---------------------------------------------------------------------------
# Numerics contexts
# ---------------------------------------------------------------------------


class _Std:
    exp = staticmethod(jnp.exp)
    sigmoid = staticmethod(jax.nn.sigmoid)
    mm = staticmethod(jnp.matmul)
    div = staticmethod(lambda a, b: a / b)
    act_q = staticmethod(lambda x: x)


class _Hw:
    """Paper numerics: LUT exp, PWL sigmoid, LUT division, A9 activations."""
    exp = staticmethod(exp_lut)
    sigmoid = staticmethod(sigmoid_pwl)
    div = staticmethod(div_lut)
    act_q = staticmethod(lambda x: uniform_fake_quant(x, 9, None))
    mm = staticmethod(jnp.matmul)


def _numerics(hw: bool):
    return _Hw if hw else _Std


def _hw_numerics_with_tables(exp_table, div_table):
    """_Hw with the LUTs bound as explicit arrays: the fused Pallas kernel
    cannot capture array constants, so the tables travel as kernel operands
    (VMEM-resident, like the paper's on-chip LUTs)."""
    class _HwTabled:
        exp = staticmethod(lambda x: exp_lut(x, table=exp_table))
        sigmoid = staticmethod(sigmoid_pwl)
        div = staticmethod(
            lambda a, b: div_lut(a, b, table=div_table))
        act_q = _Hw.act_q
        mm = _Hw.mm
    return _HwTabled


def _f32_sigmoid(x):
    return jax.nn.sigmoid(x.astype(jnp.float32)).astype(x.dtype)


def _mosaic_numerics(nm):
    """`nm` in the forms a compiled Pallas TPU kernel lowers: matmuls
    accumulate in f32 and the logistic runs in f32, each rounded once to
    the compute dtype — how XLA evaluates the bf16 forms, but Mosaic takes
    only 32-bit matmul accumulators and cannot lower a bf16 logistic.
    Interpreted kernels keep `nm` itself, so the CPU runs stay bitwise
    equal to the per-op program."""
    from repro.kernels.common import f32_dot

    class _Mosaic(nm):
        mm = staticmethod(f32_dot)
        if nm.sigmoid is jax.nn.sigmoid:
            sigmoid = staticmethod(_f32_sigmoid)
    return _Mosaic


# ---------------------------------------------------------------------------
# Block application — sequence mode
# ---------------------------------------------------------------------------


def _token_shift_seq(x, prev):
    """(B,S,D) -> previous-token tensor; prev (B,D) is x_{-1}."""
    return jnp.concatenate([prev[:, None], x[:, :-1]], axis=1)


def _time_mix_seq(p, x, prev, cfg, nm):
    xx = _token_shift_seq(x, prev)
    mix = lambda m: nm.act_q(x * p[m] + xx * (1.0 - p[m]))
    r = mix("time_mix_r") @ p["wr"]
    k = mix("time_mix_k") @ p["wk"]
    v = mix("time_mix_v") @ p["wv"]
    r = constrain(r, ("batch", None, "tp"))
    w = jnp.exp(p["time_decay"].astype(jnp.float32))
    if getattr(cfg, "wkv_stub", False):
        out = v          # dry-run instrumentation: zero-cost recurrence
    else:
        out, _ = wkv4_scan(k, v, w, p["time_first"].astype(jnp.float32),
                           exp=nm.exp, div=nm.div)
    out = nm.act_q(nm.sigmoid(r) * out.astype(r.dtype))
    return constrain(out @ p["wo"], ("batch", None, None)), x[:, -1]


def _channel_mix_seq(p, x, prev, cfg, nm):
    xx = _token_shift_seq(x, prev)
    mix = lambda m: nm.act_q(x * p[m] + xx * (1.0 - p[m]))
    r = nm.sigmoid(mix("time_mix_r") @ p["wr"])
    k = mix("time_mix_k") @ p["wk"]
    k = constrain(k, ("batch", None, "tp"))
    k = jnp.square(jax.nn.relu(k))
    out = nm.act_q(r * (nm.act_q(k) @ p["wv"]))
    return constrain(out, ("batch", None, None)), x[:, -1]


def forward(params, batch: dict, cfg: ModelConfig, *, hw: bool = False):
    nm = _numerics(hw)
    tokens = batch["tokens"]
    B = tokens.shape[0]
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
    x = constrain(x, ("batch", None, None))
    x = L.apply_norm(params["ln0"], x, "layernorm")
    zeros_prev = jnp.zeros((B, cfg.d_model), x.dtype)

    def body(x, lp):
        h = L.apply_norm(lp["ln1"], x, "layernorm")
        att, _ = _time_mix_seq(lp["att"], h, zeros_prev, cfg, nm)
        x = x + att.astype(x.dtype)   # hw-numerics units emit f32
        h = L.apply_norm(lp["ln2"], x, "layernorm")
        ffn, _ = _channel_mix_seq(lp["ffn"], h, zeros_prev, cfg, nm)
        return x + ffn.astype(x.dtype), jnp.zeros((), jnp.float32)

    blk = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(blk, x, params["blocks"])
    x = L.apply_norm(params["ln_f"], x, "layernorm")
    logits = x @ params["head"].astype(x.dtype)
    return constrain(logits, ("batch", None, "tp")), jnp.zeros(
        (), jnp.float32)


# ---------------------------------------------------------------------------
# Decode — the paper's serving mode (token-by-token, state carried)
# ---------------------------------------------------------------------------

# decode_step ignores `pos` entirely, so slots in a serving pool may sit at
# unrelated sequence offsets within one fused step (repro.serving).
DECODE_POS_FREE = True


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int = 0,
                      dtype=jnp.float32):
    """State per layer: att token-shift x, ffn token-shift x, wkv (a,b,o).
    max_len is ignored (O(1) state — the paper's linear-memory claim)."""
    Lc, D = cfg.n_layers, cfg.d_model
    z = lambda: jnp.zeros((Lc, batch, D), dtype)
    return {
        "att_x": z(), "ffn_x": z(),
        "wkv_a": z(), "wkv_b": z(),
        "wkv_o": jnp.full((Lc, batch, D), -1e38, dtype),
    }


def decode_state_axes(cfg: ModelConfig):
    ax = ("layers", "batch", None)
    return {k: ax for k in ("att_x", "ffn_x", "wkv_a", "wkv_b", "wkv_o")}


def block_decode(lp, st, x, cfg: ModelConfig, nm=_Std):
    """One layer's FULL decode-step datapath: ln1 -> token-shift mix ->
    r/k/v matvecs -> WKV update -> gated output, then ln2 -> channel mix.

    x: (B, D) residual entering the block; st: this layer's state slice.
    Shared verbatim by the per-op scan (`decode_step`, the oracle) and the
    fused Pallas kernel (`decode_step_fused`), which is what makes the two
    paths bit-identical."""
    att_x, ffn_x = st["att_x"], st["ffn_x"]
    wkv = WKV4State(st["wkv_a"].astype(jnp.float32),
                    st["wkv_b"].astype(jnp.float32),
                    st["wkv_o"].astype(jnp.float32))
    h = L.apply_norm(lp["ln1"], x[:, None], "layernorm")[:, 0]
    p = lp["att"]
    mix = lambda m: nm.act_q(h * p[m] + att_x * (1.0 - p[m]))
    r = nm.mm(mix("time_mix_r"), p["wr"])
    k = nm.mm(mix("time_mix_k"), p["wk"])
    v = nm.mm(mix("time_mix_v"), p["wv"])
    w = jnp.exp(p["time_decay"].astype(jnp.float32))
    new_wkv, out = wkv4_step(wkv, k.astype(jnp.float32),
                             v.astype(jnp.float32), w,
                             p["time_first"].astype(jnp.float32),
                             exp=nm.exp, div=nm.div)
    att = nm.mm(nm.act_q(nm.sigmoid(r) * out.astype(r.dtype)), p["wo"])
    x2 = x + att.astype(x.dtype)
    h2 = L.apply_norm(lp["ln2"], x2[:, None], "layernorm")[:, 0]
    p = lp["ffn"]
    mix2 = lambda m: nm.act_q(h2 * p[m] + ffn_x * (1.0 - p[m]))
    rr = nm.sigmoid(nm.mm(mix2("time_mix_r"), p["wr"]))
    kk = jnp.square(jax.nn.relu(nm.mm(mix2("time_mix_k"), p["wk"])))
    ffn = nm.act_q(rr * nm.mm(nm.act_q(kk), p["wv"]))
    new_st = {"att_x": h.astype(att_x.dtype),
              "ffn_x": h2.astype(ffn_x.dtype),
              "wkv_a": new_wkv.a.astype(st["wkv_a"].dtype),
              "wkv_b": new_wkv.b.astype(st["wkv_b"].dtype),
              "wkv_o": new_wkv.o.astype(st["wkv_o"].dtype)}
    return x2 + ffn.astype(x2.dtype), new_st


def _chunk_numerics(hw: bool):
    """Chunk-shaped variant of the decode numerics: identical elementwise
    units, with the A9 activation fake-quant scoped PER TOKEN POSITION
    (axis=1 of a (B, C, ...) chunk tensor) — each position then sees
    exactly the (B, features) scaling grain the per-step oracle applies,
    which is what keeps hw-numerics prefill bit-identical."""
    if not hw:
        return _Std

    class _HwChunk(_Hw):
        act_q = staticmethod(lambda x: uniform_fake_quant(x, 9, 1))
    return _HwChunk


def block_prefill(lp, st, x, valid, cfg: ModelConfig, nm=_Std, *,
                  hw: bool = False, interpret: bool | None = None):
    """One layer's chunked-prefill datapath over a (B, C, D) token window:
    ln1 -> shifted-sequence token mixes -> CHUNK-shaPED r/k/v matmuls
    (packed Δ-PoT leaves decode inside `kernels.fused_prefill.chunk_matmul`)
    -> the masked sequential WKV Pallas kernel (per-channel state in VMEM
    across the window, seeded from the pool state and snapped to its dtype
    every step) -> gated output -> ln2 -> chunk-shaped channel mix.

    Bit-identical to scanning `block_decode` over the window with the
    engine's per-step state masking, for any per-slot PREFIX validity mask
    (the scheduler only emits prefix masks: a prompt's chunk occupies
    positions [0, n)).  Factored the same way `block_decode` was: the
    models' `prefill_chunk` entry points and the tests share it verbatim."""
    from repro.kernels.fused_prefill import (
        chunk_matmul, last_valid_select, shifted_prev)
    from repro.kernels.wkv4 import wkv4_pallas
    dt = x.dtype
    att_x, ffn_x = st["att_x"], st["ffn_x"]
    h = L.apply_norm(lp["ln1"], x, "layernorm")
    p = lp["att"]
    # shifted sequence: position 0 mixes with the carried state, position t
    # with h_{t-1} ROUNDED THROUGH THE STATE DTYPE (the oracle stores the
    # carry as `h.astype(att_x.dtype)` between steps); past the valid
    # prefix the carry freezes, exactly like the oracle's masked commits
    hx = shifted_prev(h.astype(att_x.dtype), att_x, valid)
    mm = lambda a, w_: chunk_matmul(a, w_, dt, interpret=interpret)
    mix = lambda m: nm.act_q(h * p[m] + hx * (1.0 - p[m]))
    r = mm(mix("time_mix_r"), p["wr"])
    k = mm(mix("time_mix_k"), p["wk"])
    v = mm(mix("time_mix_v"), p["wv"])
    w = jnp.exp(p["time_decay"].astype(jnp.float32))
    tables = {}
    if hw:
        from repro.core.approx.units import DIV_LUT_TABLE, EXP_LUT_TABLE
        tables = {
            "exp_table": jnp.asarray(
                np.reshape(EXP_LUT_TABLE, -1), jnp.float32),
            "div_table": jnp.asarray(
                np.reshape(DIV_LUT_TABLE, -1), jnp.float32)}
    out, (af, bf, of) = wkv4_pallas(
        k.astype(jnp.float32), v.astype(jnp.float32), w,
        p["time_first"].astype(jnp.float32),
        st["wkv_a"].astype(jnp.float32), st["wkv_b"].astype(jnp.float32),
        st["wkv_o"].astype(jnp.float32),
        valid=valid, carry_dtype=jnp.dtype(st["wkv_a"].dtype).name,
        interpret=interpret, **tables)
    att = mm(nm.act_q(nm.sigmoid(r) * out.astype(r.dtype)), p["wo"])
    x2 = x + att.astype(x.dtype)
    h2 = L.apply_norm(lp["ln2"], x2, "layernorm")
    p = lp["ffn"]
    h2x = shifted_prev(h2.astype(ffn_x.dtype), ffn_x, valid)
    mix2 = lambda m: nm.act_q(h2 * p[m] + h2x * (1.0 - p[m]))
    rr = nm.sigmoid(mm(mix2("time_mix_r"), p["wr"]))
    kk = jnp.square(jax.nn.relu(mm(mix2("time_mix_k"), p["wk"])))
    ffn = nm.act_q(rr * mm(nm.act_q(kk), p["wv"]))
    n_valid = jnp.sum(valid.astype(jnp.int32), axis=1)
    new_st = {"att_x": last_valid_select(h, att_x, n_valid),
              "ffn_x": last_valid_select(h2, ffn_x, n_valid),
              # WKV finals are masked + dtype-snapped inside the kernel
              "wkv_a": af.astype(st["wkv_a"].dtype),
              "wkv_b": bf.astype(st["wkv_b"].dtype),
              "wkv_o": of.astype(st["wkv_o"].dtype)}
    return x2 + ffn.astype(x2.dtype), new_st


def prefill_chunk(params, state, tokens, valid, pos, cfg: ModelConfig, *,
                  hw: bool = False, interpret: bool | None = None,
                  all_logits: bool = False):
    """Fused chunked prefill: tokens (B, C) with a per-slot PREFIX validity
    mask (B, C) -> (new_state, last-valid logits (B, 1, V)).

    `all_logits=True` is the speculative VERIFIER variant: the head scores
    EVERY position -> (new_state, (B, C, V)), row k holding the logits the
    plain decode tick would produce after consuming token k.  Row-wise
    bit-identical to the last-valid gather (the (B·C, D) head matmul
    computes each row independently); invalid positions return zeros.

    Bit-identical to the engine's per-op prefill oracle — a `lax.scan` of
    `decode_step` with per-step masked state commits — while restructuring
    the chunk per the paper's §4 reordering: position-parallel work becomes
    (B·C, D) matmuls, the WKV recurrence runs on-chip through the Pallas
    sequence kernel, and packed Δ-PoT weights are decoded INSIDE the
    matmul kernels (no `unpack_params` anywhere in this trace — uint8
    codes are what crosses HBM for the whole prompt phase).  Lanes with no
    valid tokens keep their state and return zero logits, exactly like the
    oracle's untouched carry."""
    del pos
    from repro.core.quant.serving import broadcast_packed_scales, \
        cast_compute
    from repro.kernels.fused_prefill import chunk_matmul, gather_last_valid
    nm = _chunk_numerics(hw)
    dt = jnp.dtype(cfg.dtype)
    params = cast_compute(params, dt)
    x = jnp.take(params["embed"], tokens, axis=0).astype(dt)     # (B,C,D)
    x = L.apply_norm(params["ln0"], x, "layernorm")
    blocks = broadcast_packed_scales(params["blocks"], cfg.n_layers)

    def body(x, xs):
        lp, st = xs
        return block_prefill(lp, st, x, valid, cfg, nm, hw=hw,
                             interpret=interpret)

    x, new_state = jax.lax.scan(body, x, (blocks, state))
    n_valid = jnp.sum(valid.astype(jnp.int32), axis=1)
    if all_logits:
        xf = L.apply_norm(params["ln_f"], x, "layernorm")
        logits = chunk_matmul(xf, params["head"], xf.dtype,
                              interpret=interpret)
        return new_state, jnp.where(valid[:, :, None], logits,
                                    jnp.zeros_like(logits))
    xl = gather_last_valid(x, jnp.maximum(n_valid - 1, 0))[:, None]
    xl = L.apply_norm(params["ln_f"], xl, "layernorm")
    logits = chunk_matmul(xl, params["head"], xl.dtype, interpret=interpret)
    return new_state, jnp.where((n_valid > 0)[:, None, None], logits,
                                jnp.zeros_like(logits))


# rwkv4 ships no `prepare_prefill_params`: its packed Δ-PoT leaves are ALL
# consumed by chunk matmuls (r/k/v/wo, the FFN pair, the head), so nothing
# needs pre-decoding — the registry's "chunked" prefill descriptor has no
# module prep and passes the tree through (rwkv6 pre-decodes its few
# elementwise-consumed packed leaves; see its PREFILL_PLAIN_LEAVES).


def decode_step(params, state, tokens, pos, cfg: ModelConfig, *,
                hw: bool = False):
    """tokens: (B,1). Returns (logits (B,1,V), new_state).

    Takes a plain or a packed tree: packed Δ-PoT leaves decode one layer at
    a time inside the layer scan and the head once per call
    (`core.quant.serving.unpack_layer`), bit-identical to decoding the
    whole tree first."""
    del pos  # RWKV state is position-free
    from repro.core.quant.serving import (broadcast_packed_scales,
                                          unpack_layer)
    nm = _numerics(hw)
    dt = jnp.dtype(cfg.dtype)
    x = jnp.take(params["embed"], tokens[:, 0], axis=0).astype(dt)  # (B,D)
    x = L.apply_norm(params["ln0"], x[:, None], "layernorm")[:, 0]
    blocks = broadcast_packed_scales(params["blocks"], cfg.n_layers)

    def body(x, xs):
        lp, st = xs
        return block_decode(unpack_layer(lp, dt), st, x, cfg, nm)

    x, new_state = jax.lax.scan(body, x, (blocks, state))
    x = L.apply_norm(params["ln_f"], x[:, None], "layernorm")
    logits = x @ unpack_layer(params["head"], x.dtype).astype(x.dtype)
    return logits, new_state


def _fused_kernel_block(cfg: ModelConfig, nm, dt, interpret):
    """Per-layer body traced INSIDE a fused Pallas launch (shared by the
    per-block kernel and the whole-model megakernel): pops the optional LUT
    operands (hw numerics needs the tables as explicit VMEM inputs),
    decodes packed Δ-PoT leaves in-VMEM, then runs the same `block_decode`
    the per-op oracle uses."""
    from repro.core.quant.serving import is_packed_leaf, unpack_leaf
    from repro.kernels.common import interpret_default
    lower = (lambda n: n) if interpret_default(interpret) \
        else _mosaic_numerics

    def kernel_block(lp, st, xx):
        lp = dict(lp)
        luts = lp.pop("_luts", None)
        nm_k = lower(nm if luts is None else _hw_numerics_with_tables(
            luts["exp"], luts["div"]))
        lp = jax.tree_util.tree_map(
            lambda l: unpack_leaf(l).astype(dt) if is_packed_leaf(l) else l,
            lp, is_leaf=is_packed_leaf)
        return block_decode(lp, st, xx, cfg, nm_k)
    return kernel_block


def _lut_operands(n_layers: int):
    """The EXP/DIV fraction tables as stacked kernel operands: (L, 256)
    broadcast views — a scan (or layer-indexed BlockSpec) slices one (256,)
    copy per layer; a leading-1 form stays resident under the megakernel's
    constant index map."""
    from repro.core.approx.units import DIV_LUT_TABLE, EXP_LUT_TABLE
    tab = lambda t: jnp.broadcast_to(
        jnp.asarray(np.reshape(t, -1), jnp.float32), (n_layers, 256))
    return {"exp": tab(EXP_LUT_TABLE), "div": tab(DIV_LUT_TABLE)}


def decode_step_fused(params, state, tokens, pos, cfg: ModelConfig, *,
                      hw: bool = False, interpret: bool | None = None):
    """Fused-kernel decode: same math as `decode_step`, but each block runs
    as ONE Pallas launch (`kernels.fused_decode`) — layernorms, token-shift
    mixes, matvecs, exp/σ units, and the WKV update never leave the chip,
    and Δ-PoT-packed weights (`{"packed","scale"}` leaves from
    `core.quant.serving.pack_params`) are decoded *inside* the launch so
    uint8 codes are all that crosses HBM.  Accepts packed or plain trees;
    bit-identical to the per-op path either way
    (tests/test_fused_decode.py)."""
    del pos
    from repro.core.quant.serving import cast_compute, unpack_leaf
    from repro.kernels.fused_decode import (
        broadcast_packed_scales, fused_block_decode)
    nm = _numerics(hw)
    dt = jnp.dtype(cfg.dtype)
    params = cast_compute(params, dt)
    x = jnp.take(params["embed"], tokens[:, 0], axis=0).astype(dt)
    x = L.apply_norm(params["ln0"], x[:, None], "layernorm")[:, 0]

    kernel_block = _fused_kernel_block(cfg, nm, dt, interpret)
    blocks = broadcast_packed_scales(params["blocks"], cfg.n_layers)
    if hw:
        # LUTs as scanned operands (per-layer slices are identical views)
        blocks = {**blocks, "_luts": _lut_operands(cfg.n_layers)}

    def body(x, xs):
        lp, st = xs
        return fused_block_decode(kernel_block, x, lp, st,
                                  interpret=interpret)

    x, new_state = jax.lax.scan(body, x, (blocks, state))
    x = L.apply_norm(params["ln_f"], x[:, None], "layernorm")
    logits = x @ unpack_leaf(params["head"]).astype(x.dtype)
    return logits, new_state


def prepare_fused_model_params(params, cfg: ModelConfig, *,
                               hw: bool = False):
    """One-time host-side prep for the megakernel serving path — the
    generic `core.quant.serving.prepare_layer_stack_params` (compute cast
    + per-layer slab chunking), with the hw LUT operands attached as extra
    block operands when requested.  `decode_step_fused_model` accepts the
    result directly; raw trees also work but repack the slab every step."""
    from repro.core.quant.serving import prepare_layer_stack_params
    return prepare_layer_stack_params(
        params, cfg, {"_luts": _lut_operands(1)} if hw else None)


def _stack_has_luts(stack) -> bool:
    """Whether a prepared FusedLayerStack was built with the hw LUT
    operands attached (prepare_fused_model_params(hw=True))."""
    probe = jax.tree_util.tree_unflatten(
        stack.tdef, [None] * stack.tdef.num_leaves)
    return "_luts" in probe


def decode_step_fused_model(params, state, tokens, pos, cfg: ModelConfig, *,
                            hw: bool = False, bb: int | None = None,
                            weights: str | None = None,
                            interpret: bool | None = None):
    """Megakernel decode: the ENTIRE layer stack as ONE Pallas launch
    (`kernels.fused_decode.fused_model_decode`).  Where `decode_step_fused`
    still issues L launches under `lax.scan` — the residual and each
    layer's state round-tripping HBM between them — here the whole stack
    runs in one launch: the residual stays on-chip across layers, and each
    layer's weights arrive as one contiguous chunk per dtype (uint8 Δ-PoT
    code planes when packed), double-buffered behind the previous layer's
    compute in the streaming binding, while shared packed scales / hw LUTs
    stay VMEM-resident under constant index maps.  Same `block_decode`
    body, so bit-identical to the per-op oracle
    (tests/test_fused_decode.py).  `params` may be a plain tree or the
    output of `prepare_fused_model_params` (pre-cast, weights pre-chunked
    — the serving path)."""
    del pos
    from repro.core.quant.serving import (
        FusedLayerStack, cast_compute, unpack_leaf)
    from repro.kernels.fused_decode import fused_model_decode
    nm = _numerics(hw)
    dt = jnp.dtype(cfg.dtype)
    prepared = isinstance(params.get("blocks"), FusedLayerStack)
    if prepared and _stack_has_luts(params["blocks"]) != hw:
        raise ValueError(
            f"prepared params were built with hw={not hw} but decode was "
            f"called with hw={hw}; rebuild them via "
            "prepare_fused_model_params(params, hw=...) — without the LUT "
            "operands the hw numerics would capture the tables as kernel "
            "constants, which Pallas cannot lower")
    if not prepared:
        params = cast_compute(params, dt)
    x = jnp.take(params["embed"], tokens[:, 0], axis=0).astype(dt)
    x = L.apply_norm(params["ln0"], x[:, None], "layernorm")[:, 0]

    blocks = params["blocks"]   # packed scales keep their broadcast form
    if hw and not prepared:
        luts = _lut_operands(1)   # leading-1: resident across the grid
        blocks = {**blocks, "_luts": luts}
    x, new_state = fused_model_decode(
        _fused_kernel_block(cfg, nm, dt, interpret), x, blocks, state, bb=bb,
        weights=weights, interpret=interpret)
    x = L.apply_norm(params["ln_f"], x[:, None], "layernorm")
    logits = x @ unpack_leaf(params["head"]).astype(x.dtype)
    return logits, new_state
