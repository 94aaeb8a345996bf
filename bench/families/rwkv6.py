"""The RWKV-6 "Finch" family: its random weights, its reference block and
its work counts, for the benchmark's generic code.

The interface is the one `bench/families/rwkv4.py` sets out, with one more
rule: every entry of `MATMUL_LEAVES` is a (K, N) matrix per layer, so the
generic reference's per-column amax over a layer's first axis is the
program's per-column amax over the stacked leaf.  RWKV-6's `maa_w2` is a
(5, R, D) table in the program: `layer_params` gives it as a (5 R, D)
matrix, `model_params` (the tree the harness checks against the program's)
and the served tree reshape it to (L, 5, R, D), and `block` reshapes it
back.  Two counts are this family's own: `wkv_call_flops` and
`wkv_call_bytes`, the work of one call of the prefill's WKV-6 sequence
kernel (one layer of a prefill call), which `bench/metrics/
wkv6_roofline.rwkv6-gen.py` reads.

Each leaf of layer l draws from its own key, `fold_in(fold_in(root, i),
l)` with i the leaf's place in `_BLOCK`.  Values follow BlinkDL's RWKV-6
initialisation where it is not random: the decay ramp in `time_decay`
(-6 + 5 (n / (D-1))^(0.7 + 1.3 r), r = l / (L-1)) and the bonus ramp in
`time_faaaa` (r (1 - n / (D-1)) + a zigzag of 0.1); the token-shift mixes
mu are uniform on [0, 1).  Matrices are Gaussian at 1/sqrt(fan-in); the
second factor of each LoRA is a tenth of that, so the data-dependent
terms move the mixes and the decay by a few hundredths, not by their
whole range (BlinkDL starts them at or near zero).

The block is written from the paper's equations (Eagle and Finch,
arXiv:2404.05892, section 4): LayerNorm; the time mix with its
data-dependent token shift (ddlerp: mu_x, a rank-R_m LoRA giving five
deltas, x + dx (mu_s + delta_s) for s in w, k, v, r, g), the receptance,
key, value and gate projections, the data-dependent decay
w = exp(-exp(time_decay + tanh(x_w A) B)), the WKV-6 recurrence per head
(y_t = r_t (S + diag(u) k_t^T v_t), S' = diag(w_t) S + k_t^T v_t, from a
zero state), a GroupNorm per head with eps 64e-5, the SiLU gate and the
output projection; then LayerNorm and the channel mix (token shift,
sigmoid receptance, squared-ReLU key, value).  Every matmul runs in f32
at `highest`.  Departures from the published model: BlinkDL's
`rescale_every` halving of the residual, a float16 guard that bfloat16
does not need, is left out; the channel mix's mixes keep RWKV-4's names
(`time_mix_r`, `time_mix_k`), as the program's tree does.
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R
from bench.weights import nest

HIGHEST = jax.lax.Precision.HIGHEST

# leaf ids: the order of this tuple fixes each leaf's key
_BLOCK = (("ln1", "scale"), ("ln1", "bias"), ("ln2", "scale"),
          ("ln2", "bias"),
          ("att", "time_maa_x"), ("att", "time_maa"), ("att", "maa_w1"),
          ("att", "maa_w2"), ("att", "time_decay"), ("att", "td_w1"),
          ("att", "td_w2"), ("att", "time_faaaa"),
          ("att", "wr"), ("att", "wk"), ("att", "wv"), ("att", "wg"),
          ("att", "wo"), ("att", "ln_x", "scale"), ("att", "ln_x", "bias"),
          ("ffn", "time_mix_r"), ("ffn", "time_mix_k"),
          ("ffn", "wr"), ("ffn", "wk"), ("ffn", "wv"))
_TOP = (("embed",), ("ln0", "scale"), ("ln0", "bias"), ("ln_f", "scale"),
        ("ln_f", "bias"), ("head",))

MATMUL_LEAVES = (("att", "maa_w1"), ("att", "maa_w2"), ("att", "td_w1"),
                 ("att", "td_w2"), ("att", "wr"), ("att", "wk"),
                 ("att", "wv"), ("att", "wg"), ("att", "wo"),
                 ("ffn", "wr"), ("ffn", "wk"), ("ffn", "wv"))

GN_EPS = 64e-5      # BlinkDL's 1e-5 x head_size_divisor (8) squared


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _heads(model: dict) -> tuple[int, int]:
    n = model["rwkv_head_dim"]
    return model["d_model"] // n, n


def _matrix_shape(model: dict, name: tuple) -> tuple[int, int]:
    d, f = model["d_model"], model["d_ff"]
    rm, rd = model["rwkv_mix_rank"], model["rwkv_decay_rank"]
    return {("att", "maa_w1"): (d, 5 * rm), ("att", "maa_w2"): (5 * rm, d),
            ("att", "td_w1"): (d, rd), ("att", "td_w2"): (rd, d),
            ("ffn", "wk"): (d, f), ("ffn", "wv"): (f, d)}.get(name, (d, d))


def _ramps(model: dict) -> tuple[np.ndarray, np.ndarray]:
    """BlinkDL's decay and bonus ramps, (layers, channels), made on the
    host so that every program that indexes them sees the same values."""
    d, n_layers = model["d_model"], model["n_layers"]
    frac = np.arange(d) / max(d - 1, 1)
    depth = np.arange(n_layers)[:, None] / max(n_layers - 1, 1)
    decay = -6.0 + 5.0 * frac ** (0.7 + 1.3 * depth)
    zigzag = ((np.arange(d) + 1) % 3 - 1) * 0.1
    bonus = depth * (1.0 - frac) + zigzag
    return decay.astype(np.float32), bonus.astype(np.float32)


def _leaf(model: dict, name: tuple, key, layer):
    d = model["d_model"]
    kind = name[-1]
    if kind == "scale":
        return jnp.ones((d,), jnp.float32)
    if kind == "bias":
        return jnp.zeros((d,), jnp.float32)
    if kind in ("time_maa_x", "time_mix_r", "time_mix_k"):
        return jax.random.uniform(key, (d,), jnp.float32)
    if kind == "time_maa":
        return jax.random.uniform(key, (5, d), jnp.float32)
    if kind == "time_decay":
        return jnp.asarray(_ramps(model)[0])[layer]
    if kind == "time_faaaa":
        return jnp.asarray(_ramps(model)[1])[layer].reshape(_heads(model))
    k_in, n_out = _matrix_shape(model, name)
    std = k_in ** -0.5 * (0.1 if kind in ("maa_w2", "td_w2") else 1.0)
    return jax.random.normal(key, (k_in, n_out), jnp.float32) * std


def layer_params(model: dict, root, layer) -> dict:
    """Layer `layer`'s weights (f32), `maa_w2` as a (5 R, D) matrix.
    `layer` may be traced."""
    return nest(
        (name, _leaf(model, name, jax.random.fold_in(
            jax.random.fold_in(root, i), layer), layer))
        for i, name in enumerate(_BLOCK))


def _program_layer(model: dict, layer, root) -> dict:
    """Layer `layer` in the program's shapes: `maa_w2` (5, R, D)."""
    p = layer_params(model, root, layer)
    w2 = p["att"]["maa_w2"]
    p["att"]["maa_w2"] = w2.reshape(5, -1, w2.shape[-1])
    return p


def top_params(model: dict, root) -> dict:
    """Embedding, the outer LayerNorms and the head (f32)."""
    d, v = model["d_model"], model["vocab"]
    out = []
    for i, name in enumerate(_TOP):
        key = jax.random.fold_in(root, len(_BLOCK) + i)
        if name == ("embed",):
            value = jax.random.normal(key, (v, d), jnp.float32)
        elif name == ("head",):
            value = jax.random.normal(key, (d, v), jnp.float32) * d ** -0.5
        else:
            value = _leaf(model, name, key, 0)
        out.append((name, value))
    return nest(out)


def model_params(model: dict, root) -> dict:
    """The whole tree in the program's shapes, blocks stacked on a leading
    layer axis (f32)."""
    blocks = jax.vmap(lambda l: _program_layer(model, l, root))(
        jnp.arange(model["n_layers"]))
    return {**top_params(model, root), "blocks": blocks}


def program_params(cfg: dict, root, pack):
    """The served tree, made and packed layer by layer on the device (the
    f32 tree of a 7B model does not fit the chip): the program's
    layer-wise W8 pack, then `pack` (which passes packed leaves through
    and prepares each path).  The key is an argument of every program, so
    every seed runs the same ones."""
    from repro.core.quant.serving import pack_layerwise, pack_params
    model = cfg["model"]
    top = jax.jit(lambda r: pack_params(top_params(model, r)))(root)
    packed = pack_layerwise(
        top, lambda l, r: _program_layer(model, l, r), model["n_layers"],
        (root,), log=lambda m: print(f"[bench] set-up: {m}",
                                     file=sys.stderr, flush=True))
    return pack(packed)


# ---------------------------------------------------------------------------
# the reference's forward pass
# ---------------------------------------------------------------------------


def _shift(h):
    """(B, T, D) -> the previous position's value, zero before the first."""
    return jnp.concatenate([jnp.zeros_like(h[:, :1]), h[:, :-1]], axis=1)


def wkv(r, k, v, w, u, rnd):
    """WKV-6 over (B, T, H, N) from a zero state: y_t = r_t (S + diag(u)
    k_t^T v_t), S' = diag(w_t) S + k_t^T v_t, the state passed through
    `rnd` after every step.  Returns (B, T, H, N)."""
    def step(s, xs):
        rt, kt, vt, wt = xs                              # (B, H, N)
        kv = kt[..., :, None] * vt[..., None, :]         # (B, H, N, N)
        y = jnp.einsum("bhn,bhnm->bhm", rt, s + u[:, :, None] * kv,
                       precision=HIGHEST)
        return rnd(wt[..., :, None] * s + kv), y

    b, _, h, n = r.shape
    time_major = lambda a: jnp.swapaxes(a, 0, 1)
    _, y = jax.lax.scan(step, jnp.zeros((b, h, n, n), jnp.float32),
                        tuple(map(time_major, (r, k, v, w))))
    return time_major(y)


def _group_norm(y, p, heads: int):
    """LayerNorm of each head's N channels, then the (D,) scale and bias."""
    yh = y.reshape(*y.shape[:-1], heads, -1)
    mu = jnp.mean(yh, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(yh - mu), axis=-1, keepdims=True)
    yh = (yh - mu) / jnp.sqrt(var + GN_EPS)
    return yh.reshape(y.shape) * p["scale"] + p["bias"]


def block(p, x, rnd):
    """One block over (B, T, D).  `rnd` stands where the served program
    rounds to its compute or state dtype: identity in the reference."""
    mm = lambda a, b: jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)
    bsz, t, d = x.shape
    a = p["att"]
    heads, n = a["time_faaaa"].shape
    h = rnd(R.layer_norm(x, p["ln1"]))
    dx = rnd(_shift(h) - h)
    # ddlerp: five data-dependent token-shift mixes
    xxx = rnd(h + rnd(dx * a["time_maa_x"]))
    dmix = rnd(jnp.tanh(mm(xxx, a["maa_w1"]))).reshape(bsz, t, 5, -1)
    w2 = a["maa_w2"].reshape(5, -1, d)
    deltas = rnd(jnp.einsum("btsr,srd->btsd", dmix, rnd(w2),
                            precision=HIGHEST))
    mus = rnd(a["time_maa"] + deltas)
    xw, xk, xv, xr, xg = (rnd(h + rnd(dx * mus[:, :, i])) for i in range(5))
    r = rnd(mm(xr, a["wr"])).reshape(bsz, t, heads, n)
    k = rnd(mm(xk, a["wk"])).reshape(bsz, t, heads, n)
    v = rnd(mm(xv, a["wv"])).reshape(bsz, t, heads, n)
    g = rnd(jax.nn.silu(rnd(mm(xg, a["wg"]))))
    # data-dependent decay, in f32 from the rounded log-log decay
    lora = rnd(mm(rnd(jnp.tanh(rnd(mm(xw, a["td_w1"])))), a["td_w2"]))
    w = jnp.exp(-jnp.exp(rnd(a["time_decay"] + lora)))
    y = rnd(wkv(r, k, v, w.reshape(bsz, t, heads, n), a["time_faaaa"], rnd))
    y = rnd(_group_norm(y.reshape(bsz, t, d), a["ln_x"], heads))
    x = rnd(x + rnd(mm(rnd(y * g), a["wo"])))
    # channel mix
    h = rnd(R.layer_norm(x, p["ln2"]))
    f = p["ffn"]
    hx = _shift(h)
    mix = lambda m: rnd(h * f[m] + hx * (1.0 - f[m]))
    rr = rnd(jax.nn.sigmoid(rnd(mm(mix("time_mix_r"), f["wr"]))))
    kk = rnd(jnp.square(jax.nn.relu(rnd(mm(mix("time_mix_k"), f["wk"])))))
    return rnd(x + rnd(rr * rnd(mm(kk, f["wv"]))))


def top_stored(cfg: dict, root) -> dict:
    """Embedding, outer LayerNorms and head as the plane stores them: the
    head W8-coded with one scale per column, the rest in `other`."""
    t = top_params(cfg["model"], root)
    out = jax.tree_util.tree_map(
        lambda x: R.stored(x, cfg["plane"]["other"]), t)
    head = t["head"]
    out["head"] = R.w8_value(head, R.w8_scale(jnp.max(jnp.abs(head), 0)))
    return out


# ---------------------------------------------------------------------------
# work counts
# ---------------------------------------------------------------------------


def layer_matmul_weights(model: dict) -> int:
    """Matmul weights of one block: r, k, v, g, o of the time mix and the
    channel mix's r (D x D each), its k (D x F) and v (F x D), the ddlerp
    LoRA (D x 5 R_m and 5 R_m x D) and the decay LoRA (D x R_d, R_d x D)."""
    d, f = model["d_model"], model["d_ff"]
    rm, rd = model["rwkv_mix_rank"], model["rwkv_decay_rank"]
    return 5 * d * d + 2 * d * f + d * d + 5 * rm * d * 2 + 2 * rd * d


def head_weights(model: dict) -> int:
    return model["d_model"] * model["vocab"]


def scale_count(model: dict) -> int:
    """Per-output-channel scales of the W8 plane, one per column of each
    stacked matmul leaf: D for each of r, k, v, g, o, the channel mix's r
    and v, maa_w2 and td_w2 (9 D), F for the channel mix's k, 5 R_m for
    maa_w1, R_d for td_w1; plus the head's V."""
    d, f = model["d_model"], model["d_ff"]
    rm, rd = model["rwkv_mix_rank"], model["rwkv_decay_rank"]
    return 9 * d + f + 5 * rm + rd + model["vocab"]


def small_param_count(model: dict) -> int:
    """Parameters outside the matmuls that a step reads whole: per block two
    LayerNorms (2 x 2D), mu_x (D), the five mus (5D), the decay (D), the
    bonus (H N = D), the GroupNorm (2D) and the channel mix's two mixes
    (2D); ln0 and ln_f (2 x 2D)."""
    d = model["d_model"]
    return model["n_layers"] * 16 * d + 4 * d


def state_elems_per_lane(model: dict) -> int:
    """att_x, ffn_x and the (N, N) WKV state of each head, per layer."""
    heads, n = _heads(model)
    return model["n_layers"] * (2 * model["d_model"] + heads * n * n)


def layer_extra_flops(model: dict) -> int:
    """The WKV-6 step per token and layer, as the reference writes it, per
    head: k^T v, diag(u) k^T v and its sum with S (N^2 each), r times that
    (2 N^2), diag(w) S and its sum with k^T v (N^2 each): 7 H N^2."""
    heads, n = _heads(model)
    return 7 * heads * n * n


def wkv_call_flops(cfg: dict) -> int:
    """One call of the prefill's WKV-6 sequence kernel (one layer of one
    prefill call) over the pool's lanes x a chunk: 7 H N^2 per position."""
    eng = cfg["engine"]
    return eng["max_batch"] * eng["prefill_chunk"] * layer_extra_flops(
        cfg["model"])


def wkv_call_bytes(cfg: dict) -> int:
    """One call of that kernel: r, k, v and w in and y out, (B, T, H, N)
    f32 each, and the (B, H, N, N) f32 state in and out."""
    eng = cfg["engine"]
    heads, n = _heads(cfg["model"])
    b, t = eng["max_batch"], eng["prefill_chunk"]
    return 4 * (5 * b * t * heads * n + 2 * b * heads * n * n)
