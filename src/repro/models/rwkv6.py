"""RWKV-6 "Finch" (arXiv:2404.05892) — data-dependent decay linear attention.

Block = TimeMix (ddlerp token-shift -> r/k/v/w/g projections -> multi-head
WKV-6 recurrence -> GroupNorm -> SiLU(g) gate) + ChannelMix (same squared-ReLU
gated FFN as RWKV-4), each preceded by LayerNorm, plus the pre-block ln0.

The data-dependent parts follow the published formulation:
  ddlerp: xxx = x + dx * mu_x;  d = tanh(xxx @ maa_w1) @ maa_w2 -> 5 deltas
          x_s  = x + dx * (mu_s + d_s)        for s in {w,k,v,r,g}
  decay:  w_t  = exp(-exp(time_decay + tanh(x_w @ td_w1) @ td_w2))
The recurrence itself lives in repro.core.wkv.wkv6 (scan / chunked / step);
training & prefill use the chunked sub-quadratic form, decode the O(1) step —
this model is the closest assigned architecture to the paper's RWKV-4 and is
the primary target of its technique.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.wkv.wkv6 import (
    wkv6_chunked, wkv6_init_state, wkv6_scan, wkv6_step)
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
    H = cfg.n_heads
    N = cfg.rwkv_head_dim
    rm, rd = cfg.rwkv_mix_rank, cfg.rwkv_decay_rank
    assert H * N == d, f"heads {H} x head_dim {N} != d_model {d}"
    return {
        "ln1": L.spec_norm(d, "layernorm"),
        "ln2": L.spec_norm(d, "layernorm"),
        "att": {
            "time_maa_x": P((d,), (None,), init="uniform", scale=0.5),
            # per-stream mus: w, k, v, r, g
            "time_maa": P((5, d), (None, None), init="uniform", scale=0.5),
            "maa_w1": P((d, 5 * rm), (None, None), scale=0.01),
            "maa_w2": P((5, rm, d), (None, None, None), scale=0.01),
            "time_decay": P((d,), (None,), init="zeros"),
            "td_w1": P((d, rd), (None, None), scale=0.01),
            "td_w2": P((rd, d), (None, None), scale=0.01),
            "time_faaaa": P((H, N), (None, None), init="zeros"),  # bonus u
            "wr": P((d, d), ("fsdp", "tp")),
            "wk": P((d, d), ("fsdp", "tp")),
            "wv": P((d, d), ("fsdp", "tp")),
            "wg": P((d, d), ("fsdp", "tp")),
            "wo": P((d, d), ("tp", "fsdp")),
            "ln_x": {"scale": P((d,), (None,), init="ones"),
                     "bias": P((d,), (None,), init="zeros")},
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
# TimeMix internals (shared between sequence and step forms)
# ---------------------------------------------------------------------------


def _ddlerp(p, x, dx):
    """Data-dependent token-shift mixes.  x, dx: (..., D).
    Returns (xw, xk, xv, xr, xg)."""
    xxx = x + dx * p["time_maa_x"]
    lead = xxx.shape[:-1]
    dmix = jnp.tanh(xxx @ p["maa_w1"])                 # (..., 5R)
    dmix = dmix.reshape(*lead, 5, -1)
    deltas = jnp.einsum("...sr,srd->...sd", dmix, p["maa_w2"])  # (...,5,D)
    mus = p["time_maa"] + deltas                       # (...,5,D)
    return tuple(x + dx * mus[..., i, :] for i in range(5))


def _decay(p, xw):
    """w_t in (0,1): exp(-exp(time_decay + lora(x_w)))."""
    dd = p["time_decay"] + jnp.tanh(xw @ p["td_w1"]) @ p["td_w2"]
    return jnp.exp(-jnp.exp(dd.astype(jnp.float32)))


def _group_norm(p, y, H, eps=64e-5):
    """Per-head LayerNorm (the official ln_x GroupNorm(H))."""
    lead = y.shape[:-1]
    yh = y.reshape(*lead, H, -1).astype(jnp.float32)
    mu = jnp.mean(yh, axis=-1, keepdims=True)
    var = jnp.var(yh, axis=-1, keepdims=True)
    yh = (yh - mu) * jax.lax.rsqrt(var + eps)
    out = yh.reshape(*lead, -1) * p["scale"] + p["bias"]
    return out.astype(y.dtype)


def _time_mix_seq(p, x, prev, cfg, wkv_fn):
    """x: (B,S,D); prev: (B,D) token-shift carry."""
    B, S, D = x.shape
    H, N = cfg.n_heads, cfg.rwkv_head_dim
    dx = jnp.concatenate([prev[:, None], x[:, :-1]], axis=1) - x
    xw, xk, xv, xr, xg = _ddlerp(p, x, dx)
    r = (xr @ p["wr"]).reshape(B, S, H, N)
    k = (xk @ p["wk"]).reshape(B, S, H, N)
    v = (xv @ p["wv"]).reshape(B, S, H, N)
    g = jax.nn.silu(xg @ p["wg"])
    w = _decay(p, xw).reshape(B, S, H, N)
    r = constrain(r, ("batch", None, "tp", None))
    y, _ = wkv_fn(r, k, v, w, p["time_faaaa"].astype(jnp.float32))
    y = _group_norm(p["ln_x"], y.reshape(B, S, D), H)
    out = (y * g) @ p["wo"]
    return constrain(out, ("batch", None, None)), x[:, -1]


def _channel_mix_seq(p, x, prev):
    xx = jnp.concatenate([prev[:, None], x[:, :-1]], axis=1)
    mix = lambda m: x * p[m] + xx * (1.0 - p[m])
    r = jax.nn.sigmoid(mix("time_mix_r") @ p["wr"])
    k = constrain(mix("time_mix_k") @ p["wk"], ("batch", None, "tp"))
    k = jnp.square(jax.nn.relu(k))
    return constrain(r * (k @ p["wv"]), ("batch", None, None)), x[:, -1]


# ---------------------------------------------------------------------------
# Forward (training / prefill): chunked sub-quadratic WKV
# ---------------------------------------------------------------------------


def forward(params, batch: dict, cfg: ModelConfig, *, chunk: int = 64):
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
    x = constrain(x, ("batch", None, None))
    x = L.apply_norm(params["ln0"], x, "layernorm")
    zeros_prev = jnp.zeros((B, cfg.d_model), x.dtype)
    wkv_fn = (lambda r, k, v, w, u: wkv6_chunked(r, k, v, w, u, chunk=chunk)
              ) if S % chunk == 0 and S > chunk else wkv6_scan

    def body(x, lp):
        h = L.apply_norm(lp["ln1"], x, "layernorm")
        att, _ = _time_mix_seq(lp["att"], h, zeros_prev, cfg, wkv_fn)
        x = x + att
        h = L.apply_norm(lp["ln2"], x, "layernorm")
        ffn, _ = _channel_mix_seq(lp["ffn"], h, zeros_prev)
        return x + ffn, jnp.zeros((), jnp.float32)

    blk = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(blk, x, params["blocks"])
    x = L.apply_norm(params["ln_f"], x, "layernorm")
    logits = x @ params["head"].astype(x.dtype)
    return constrain(logits, ("batch", None, "tp")), jnp.zeros(
        (), jnp.float32)


# ---------------------------------------------------------------------------
# Decode — O(1) state per token (the linear-inference story)
# ---------------------------------------------------------------------------

# decode_step ignores `pos` entirely, so slots in a serving pool may sit at
# unrelated sequence offsets within one fused step (repro.serving).
DECODE_POS_FREE = True


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int = 0,
                      dtype=jnp.float32):
    del max_len  # O(1) state
    Lc, D = cfg.n_layers, cfg.d_model
    H, N = cfg.n_heads, cfg.rwkv_head_dim
    return {
        "att_x": jnp.zeros((Lc, batch, D), dtype),
        "ffn_x": jnp.zeros((Lc, batch, D), dtype),
        "wkv_s": jnp.zeros((Lc, batch, H, N, N), dtype),
    }


def decode_state_axes(cfg: ModelConfig):
    return {"att_x": ("layers", "batch", None),
            "ffn_x": ("layers", "batch", None),
            "wkv_s": ("layers", "batch", "tp", None, None)}


def block_decode(lp, st, x, cfg: ModelConfig):
    """One layer's FULL decode-step datapath: ln1 -> ddlerp mixes ->
    r/k/v/w/g projections -> multi-head WKV-6 update -> GroupNorm ->
    SiLU-gated output, then ln2 -> channel mix.

    x: (B, D) residual entering the block; st: this layer's state slice.
    Shared verbatim by the per-op scan (`decode_step`, the oracle) and the
    fused Pallas kernel (`decode_step_fused`), which is what makes the two
    paths bit-identical."""
    B = x.shape[0]
    H, N, D = cfg.n_heads, cfg.rwkv_head_dim, cfg.d_model
    h = L.apply_norm(lp["ln1"], x[:, None], "layernorm")[:, 0]
    p = lp["att"]
    dx = st["att_x"].astype(h.dtype) - h
    xw, xk, xv, xr, xg = _ddlerp(p, h, dx)
    r = (xr @ p["wr"]).reshape(B, H, N)
    k = (xk @ p["wk"]).reshape(B, H, N)
    v = (xv @ p["wv"]).reshape(B, H, N)
    g = jax.nn.silu(xg @ p["wg"])
    w = _decay(p, xw).reshape(B, H, N)
    S_new, y = wkv6_step(st["wkv_s"].astype(jnp.float32),
                         r.astype(jnp.float32), k.astype(jnp.float32),
                         v.astype(jnp.float32), w,
                         p["time_faaaa"].astype(jnp.float32))
    y = _group_norm(p["ln_x"], y.reshape(B, D).astype(h.dtype), H)
    x2 = x + (y * g) @ p["wo"]
    h2 = L.apply_norm(lp["ln2"], x2[:, None], "layernorm")[:, 0]
    p2 = lp["ffn"]
    ffn_x = st["ffn_x"].astype(h2.dtype)
    mix = lambda m: h2 * p2[m] + ffn_x * (1.0 - p2[m])
    rr = jax.nn.sigmoid(mix("time_mix_r") @ p2["wr"])
    kk = jnp.square(jax.nn.relu(mix("time_mix_k") @ p2["wk"]))
    ffn = rr * (kk @ p2["wv"])
    new_st = {"att_x": h.astype(st["att_x"].dtype),
              "ffn_x": h2.astype(st["ffn_x"].dtype),
              "wkv_s": S_new.astype(st["wkv_s"].dtype)}
    return x2 + ffn, new_st


def block_prefill(lp, st, x, valid, cfg: ModelConfig, *,
                  interpret: bool | None = None):
    """One layer's chunked-prefill datapath over a (B, C, D) token window:
    ln1 -> shifted-sequence ddlerp mixes -> CHUNK-shaped r/k/v/w/g
    projections (packed Δ-PoT leaves decode inside
    `kernels.fused_prefill.chunk_matmul`) -> the masked SEQUENTIAL WKV-6
    Pallas kernel (each head's (N, N) state in VMEM across the window,
    advanced with the exact `wkv6_step` math and snapped to the pool dtype
    every step) -> GroupNorm -> SiLU-gated output, then ln2 -> chunk-shaped
    channel mix.

    Bit-identical to scanning `block_decode` over the window with the
    engine's per-step state masking, for any per-slot PREFIX validity mask
    (the scheduler only emits prefix masks).  Factored the same way
    `block_decode` was; `lp` must carry maa_w2 as a PLAIN leaf (it is
    einsum'd, not fed to a chunk matmul — `prepare_prefill_params`
    pre-decodes it once at startup)."""
    from repro.kernels.fused_prefill import (
        chunk_matmul, last_valid_select, shifted_prev)
    from repro.kernels.wkv6 import wkv6_seq_pallas
    B, C, D = x.shape
    H, N = cfg.n_heads, cfg.rwkv_head_dim
    dt = x.dtype
    h = L.apply_norm(lp["ln1"], x, "layernorm")
    p = lp["att"]
    mm = lambda a, w_: chunk_matmul(a, w_, dt, interpret=interpret)
    # shifted sequence: position t mixes with h_{t-1} rounded through the
    # state dtype (the oracle's `h.astype(att_x.dtype)` carry); past the
    # valid prefix the carry freezes, like the oracle's masked commits
    prev = shifted_prev(h.astype(st["att_x"].dtype), st["att_x"], valid)
    dx = prev.astype(h.dtype) - h
    # ddlerp with the low-rank matmuls chunk-shaped
    xxx = h + dx * p["time_maa_x"]
    dmix = jnp.tanh(mm(xxx, p["maa_w1"])).reshape(B, C, 5, -1)
    deltas = jnp.einsum("...sr,srd->...sd", dmix, p["maa_w2"])
    mus = p["time_maa"] + deltas
    xw, xk, xv, xr, xg = (h + dx * mus[..., i, :] for i in range(5))
    r = mm(xr, p["wr"]).reshape(B, C, H, N)
    k = mm(xk, p["wk"]).reshape(B, C, H, N)
    v = mm(xv, p["wv"]).reshape(B, C, H, N)
    g = jax.nn.silu(mm(xg, p["wg"]))
    dd = p["time_decay"] + mm(jnp.tanh(mm(xw, p["td_w1"])), p["td_w2"])
    w = jnp.exp(-jnp.exp(dd.astype(jnp.float32))).reshape(B, C, H, N)
    y, S_new = wkv6_seq_pallas(
        r.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        w, p["time_faaaa"].astype(jnp.float32),
        st["wkv_s"].astype(jnp.float32), valid=valid,
        carry_dtype=jnp.dtype(st["wkv_s"].dtype).name, interpret=interpret)
    y = _group_norm(p["ln_x"], y.reshape(B, C, D).astype(h.dtype), H)
    x2 = x + mm(y * g, p["wo"])
    h2 = L.apply_norm(lp["ln2"], x2, "layernorm")
    p2 = lp["ffn"]
    prev2 = shifted_prev(h2.astype(st["ffn_x"].dtype), st["ffn_x"], valid)
    ffn_x = prev2.astype(h2.dtype)
    mix = lambda m: h2 * p2[m] + ffn_x * (1.0 - p2[m])
    rr = jax.nn.sigmoid(mm(mix("time_mix_r"), p2["wr"]))
    kk = jnp.square(jax.nn.relu(mm(mix("time_mix_k"), p2["wk"])))
    ffn = rr * mm(kk, p2["wv"])
    n_valid = jnp.sum(valid.astype(jnp.int32), axis=1)
    new_st = {"att_x": last_valid_select(h, st["att_x"], n_valid),
              "ffn_x": last_valid_select(h2, st["ffn_x"], n_valid),
              # masked + dtype-snapped inside the kernel
              "wkv_s": S_new.astype(st["wkv_s"].dtype)}
    return x2 + ffn, new_st


def prefill_chunk(params, state, tokens, valid, pos, cfg: ModelConfig, *,
                  interpret: bool | None = None, all_logits: bool = False):
    """Fused chunked prefill: tokens (B, C) with a per-slot PREFIX validity
    mask (B, C) -> (new_state, last-valid logits (B, 1, V)).  Bit-identical
    to the engine's scan-of-`decode_step` prefill oracle; packed Δ-PoT
    projection weights decode inside the chunk-matmul kernels (run
    `prepare_prefill_params` once first so the few element-wise-consumed
    packed leaves arrive plain).  See models/rwkv4.py `prefill_chunk` for
    the shared contract and the `all_logits=True` verifier variant
    (-> (new_state, (B, C, V)), one logits row per valid position)."""
    del pos
    from repro.core.quant.serving import broadcast_packed_scales, \
        cast_compute
    from repro.kernels.fused_prefill import chunk_matmul, gather_last_valid
    dt = jnp.dtype(cfg.dtype)
    params = cast_compute(params, dt)
    x = jnp.take(params["embed"], tokens, axis=0).astype(dt)     # (B,C,D)
    x = L.apply_norm(params["ln0"], x, "layernorm")
    blocks = broadcast_packed_scales(params["blocks"], cfg.n_layers)

    def body(x, xs):
        lp, st = xs
        return block_prefill(lp, st, x, valid, cfg, interpret=interpret)

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


# packed leaves block_prefill consumes OUTSIDE a matmul: the einsum'd
# low-rank delta table (the element-wise mixes and the WKV bonus are
# additive leaves, stored plain in the compute dtype)
PREFILL_PLAIN_LEAVES = (("blocks", "att", "maa_w2"),)


def prepare_prefill_params(params, cfg: ModelConfig):
    """One-time host-side prep for the fused prefill path: pre-decode the
    few packed leaves the chunk datapath consumes element-wise (they're
    additive-sized — decoding them once at startup costs nothing), so the
    prefill TRACE never unpacks anything: every remaining packed leaf
    streams its uint8 codes straight into a chunk-matmul kernel.  The
    generic `core.quant.serving.predecode_packed_leaves` does the work
    (same `unpack_leaf` as the per-op oracle, so bits match)."""
    del cfg
    from repro.core.quant.serving import predecode_packed_leaves
    return predecode_packed_leaves(params, PREFILL_PLAIN_LEAVES)


def decode_step(params, state, tokens, pos, cfg: ModelConfig):
    """tokens: (B,1) -> (logits (B,1,V), new_state).  Takes a plain or a
    packed tree: packed Δ-PoT leaves decode one layer at a time inside the
    layer scan and the head once per call, as in models/rwkv4.py."""
    del pos
    from repro.core.quant.serving import (broadcast_packed_scales,
                                          unpack_layer)
    dt = jnp.dtype(cfg.dtype)
    x = jnp.take(params["embed"], tokens[:, 0], axis=0).astype(dt)
    x = L.apply_norm(params["ln0"], x[:, None], "layernorm")[:, 0]
    blocks = broadcast_packed_scales(params["blocks"], cfg.n_layers)

    def body(x, xs):
        lp, st = xs
        return block_decode(unpack_layer(lp, dt), st, x, cfg)

    x, new_state = jax.lax.scan(body, x, (blocks, state))
    x = L.apply_norm(params["ln_f"], x[:, None], "layernorm")
    logits = x @ unpack_layer(params["head"], x.dtype).astype(x.dtype)
    return logits, new_state


def _fused_kernel_block(cfg: ModelConfig, dt):
    """Per-layer body traced INSIDE a fused Pallas launch (shared by the
    per-block kernel and the whole-model megakernel): decodes packed Δ-PoT
    leaves in-VMEM, then runs the same `block_decode` the per-op oracle
    uses."""
    from repro.core.quant.serving import is_packed_leaf, unpack_leaf

    def kernel_block(lp, st, xx):
        lp = jax.tree_util.tree_map(
            lambda l: unpack_leaf(l).astype(dt) if is_packed_leaf(l) else l,
            lp, is_leaf=is_packed_leaf)
        return block_decode(lp, st, xx, cfg)
    return kernel_block


def decode_step_fused(params, state, tokens, pos, cfg: ModelConfig, *,
                      interpret: bool | None = None):
    """Fused-kernel decode: same math as `decode_step`, but each block runs
    as ONE Pallas launch (`kernels.fused_decode`) with the (H, N, N) WKV
    state resident for the whole block and Δ-PoT-packed weights decoded
    inside the launch.  Accepts packed or plain trees; bit-identical to the
    per-op path either way (tests/test_fused_decode.py)."""
    del pos
    from repro.core.quant.serving import cast_compute, unpack_leaf
    from repro.kernels.fused_decode import (
        broadcast_packed_scales, fused_block_decode)
    dt = jnp.dtype(cfg.dtype)
    params = cast_compute(params, dt)
    x = jnp.take(params["embed"], tokens[:, 0], axis=0).astype(dt)
    x = L.apply_norm(params["ln0"], x[:, None], "layernorm")[:, 0]

    kernel_block = _fused_kernel_block(cfg, dt)
    blocks = broadcast_packed_scales(params["blocks"], cfg.n_layers)

    def body(x, xs):
        lp, st = xs
        return fused_block_decode(kernel_block, x, lp, st,
                                  interpret=interpret)

    x, new_state = jax.lax.scan(body, x, (blocks, state))
    x = L.apply_norm(params["ln_f"], x[:, None], "layernorm")
    logits = x @ unpack_leaf(params["head"]).astype(x.dtype)
    return logits, new_state


def prepare_fused_model_params(params, cfg: ModelConfig):
    """One-time host-side prep for the megakernel serving path — the
    generic `core.quant.serving.prepare_layer_stack_params` (compute cast
    + per-dtype per-layer slab chunking): one weight stream per layer
    instead of one gather per leaf."""
    from repro.core.quant.serving import prepare_layer_stack_params
    return prepare_layer_stack_params(params, cfg)


def decode_step_fused_model(params, state, tokens, pos, cfg: ModelConfig, *,
                            bb: int | None = None,
                            weights: str | None = None,
                            interpret: bool | None = None):
    """Megakernel decode: the ENTIRE layer stack as ONE Pallas launch
    (`kernels.fused_decode.fused_model_decode`) — the residual stays
    on-chip across layers, each layer's weights arrive as one contiguous
    chunk per dtype (uint8 Δ-PoT code planes when packed) double-buffered
    behind the previous layer's compute in the streaming binding, and the
    (H, N, N) WKV state is read and written once per layer.  Same
    `block_decode` body as the per-op oracle, so bit-identical
    (tests/test_fused_decode.py).  `params` may be a plain tree or the
    output of `prepare_fused_model_params` (pre-cast, weights pre-chunked
    — the serving path)."""
    del pos
    from repro.core.quant.serving import (
        FusedLayerStack, cast_compute, unpack_leaf)
    from repro.kernels.fused_decode import fused_model_decode
    dt = jnp.dtype(cfg.dtype)
    if not isinstance(params.get("blocks"), FusedLayerStack):
        params = cast_compute(params, dt)
    x = jnp.take(params["embed"], tokens[:, 0], axis=0).astype(dt)
    x = L.apply_norm(params["ln0"], x[:, None], "layernorm")[:, 0]
    # packed scales keep their broadcast (1, ...) form: the megakernel
    # binds them with a constant index map (no per-layer copies)
    x, new_state = fused_model_decode(
        _fused_kernel_block(cfg, dt), x, params["blocks"], state, bb=bb,
        weights=weights, interpret=interpret)
    x = L.apply_norm(params["ln_f"], x[:, None], "layernorm")
    logits = x @ unpack_leaf(params["head"]).astype(x.dtype)
    return logits, new_state
