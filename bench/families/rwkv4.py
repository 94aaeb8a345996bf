"""The RWKV-4 family: its random weights, its reference block and its work
counts, for the benchmark's generic code.

A model family is one module in `bench/families/`, found from the
configuration's `model` section by `bench.spec.family` (`family` "rwkv"
with `rwkv_version` 4: this file).  The harness, the reference and the
work counts reach the model only through these names:

  MATMUL_LEAVES                  (group, leaf) names of a block's matmul
                                 weights, which the W8 plane codes
  layer_params(model, root, l)   block l's weights, f32; l may be traced
  top_params(model, root)        `embed`, `ln0`, `ln_f`, `head`, f32
  model_params(model, root)      the whole tree, blocks stacked on a
                                 leading layer axis, f32
  program_params(cfg, root, pack)
                                 the tree the engine serves: `pack` maps
                                 the f32 tree to it (the program's
                                 packing and path preparation); a family
                                 whose f32 tree does not fit the chip
                                 makes and packs it layer by layer here
  block(p, x, rnd)               one block's forward pass over (B, T, D)
                                 for the reference, `rnd` standing where
                                 the served program rounds to its compute
                                 or state dtype
  top_stored(cfg, root)          the top leaves as the plane stores them
  layer_matmul_weights(model), head_weights(model), scale_count(model),
  small_param_count(model), state_elems_per_lane(model)
                                 the counts `bench/work.py` builds every
                                 byte and FLOP from
  layer_extra_flops(model)       optional: FLOPs per token and layer
                                 outside the matmuls (0 when absent)

Each leaf of layer l draws from its own key, `fold_in(fold_in(root, i),
l)` with i the leaf's place in `_BLOCK`, so one layer can be made without
the others and the order of `_BLOCK` fixes every leaf's values.  Values
follow BlinkDL's RWKV-4 initialisation where it is not random (decay and
bonus ramps over the channels) and are Gaussian with a 1/sqrt(fan-in)
scale where it is.

The block follows BlinkDL's RWKV-4: a LayerNorm, the time mix (token
shift of the normalised input, receptance/key/value projections, the WKV
recurrence with decay exp(time_decay) and bonus time_first in its
running-max form, the sigmoid(r) gate, the output projection) and the
channel mix (token shift, sigmoid receptance, squared-ReLU key, value).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R
from bench.weights import nest

# leaf ids: the order of this tuple fixes each leaf's key
_BLOCK = (("ln1", "scale"), ("ln1", "bias"), ("ln2", "scale"),
          ("ln2", "bias"),
          ("att", "time_mix_r"), ("att", "time_mix_k"), ("att", "time_mix_v"),
          ("att", "time_decay"), ("att", "time_first"),
          ("att", "wr"), ("att", "wk"), ("att", "wv"), ("att", "wo"),
          ("ffn", "time_mix_r"), ("ffn", "time_mix_k"),
          ("ffn", "wr"), ("ffn", "wk"), ("ffn", "wv"))
_TOP = (("embed",), ("ln0", "scale"), ("ln0", "bias"), ("ln_f", "scale"),
        ("ln_f", "bias"), ("head",))

MATMUL_LEAVES = tuple(k for k in _BLOCK if k[-1] in ("wr", "wk", "wv", "wo"))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _matrix_shape(model: dict, name: tuple) -> tuple[int, int]:
    d, f = model["d_model"], model["d_ff"]
    if name == ("ffn", "wk"):
        return d, f
    if name == ("ffn", "wv"):
        return f, d
    return d, d


def _decay(model: dict) -> np.ndarray:
    """BlinkDL's decay ramp, (layers, channels), made on the host so that
    every program that indexes it sees the same f32 values."""
    d, n_layers = model["d_model"], model["n_layers"]
    ramp = np.arange(d) / max(d - 1, 1)
    depth = np.arange(n_layers)[:, None] / max(n_layers - 1, 1)
    return (-5.0 + 8.0 * ramp ** (0.7 + 1.3 * depth)).astype(np.float32)


def _leaf(model: dict, name: tuple, key, layer):
    d = model["d_model"]
    kind = name[-1]
    if kind == "scale":
        return jnp.ones((d,), jnp.float32)
    if kind == "bias":
        return jnp.zeros((d,), jnp.float32)
    if kind.startswith("time_mix"):
        return jax.random.uniform(key, (d,), jnp.float32)
    if kind == "time_decay":
        return jnp.asarray(_decay(model))[layer]
    if kind == "time_first":
        zigzag = ((jnp.arange(d) + 1) % 3 - 1).astype(jnp.float32) * 0.5
        return jnp.log(0.3) + zigzag
    k_in, n_out = _matrix_shape(model, name)
    return jax.random.normal(key, (k_in, n_out), jnp.float32) * k_in ** -0.5


def layer_params(model: dict, root, layer) -> dict:
    """Layer `layer`'s weights (f32).  `layer` may be traced."""
    return nest(
        (name, _leaf(model, name, jax.random.fold_in(
            jax.random.fold_in(root, i), layer), layer))
        for i, name in enumerate(_BLOCK))


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
    """The whole tree, blocks stacked on a leading layer axis (f32)."""
    blocks = jax.vmap(lambda l: layer_params(model, root, l))(
        jnp.arange(model["n_layers"]))
    return {**top_params(model, root), "blocks": blocks}


def program_params(cfg: dict, root, pack):
    """The served tree in one jitted call: the whole f32 tree, then
    `pack`.  The key is an argument, so every seed runs one program."""
    model = cfg["model"]

    def make(root):
        return pack(model_params(model, root))

    return jax.jit(make)(root)


# ---------------------------------------------------------------------------
# the reference's forward pass
# ---------------------------------------------------------------------------


def _shift(h):
    """(B, T, D) -> the previous position's value, zero before the first."""
    return jnp.concatenate([jnp.zeros_like(h[:, :1]), h[:, :-1]], axis=1)


def wkv(k, v, w, u, rnd):
    """RWKV-4 WKV over (B, T, D), running-max form, from a zero state,
    the state passed through `rnd` after every step."""
    def step(carry, kv):
        a, b, o = carry
        kt, vt = kv
        no = jnp.maximum(o, u + kt)
        e1, e2 = jnp.exp(o - no), jnp.exp(u + kt - no)
        out = (e1 * a + e2 * vt) / (e1 * b + e2)
        no2 = jnp.maximum(o - w, kt)
        e1, e2 = jnp.exp(o - w - no2), jnp.exp(kt - no2)
        return (rnd(e1 * a + e2 * vt), rnd(e1 * b + e2), rnd(no2)), out

    zero = jnp.zeros_like(k[:, 0])
    init = (zero, zero, rnd(jnp.full_like(zero, -1e38)))
    _, out = jax.lax.scan(step, init, (jnp.swapaxes(k, 0, 1),
                                      jnp.swapaxes(v, 0, 1)))
    return jnp.swapaxes(out, 0, 1)


def block(p, x, rnd):
    """One block.  `rnd` stands where the served program rounds to its
    compute or state dtype: identity in the reference."""
    mm = lambda a, b: jnp.matmul(rnd(a), rnd(b),
                                 precision=jax.lax.Precision.HIGHEST)
    h = rnd(R.layer_norm(x, p["ln1"]))
    a = p["att"]
    hx = _shift(h)
    mix = lambda m: rnd(h * a[m] + hx * (1.0 - a[m]))
    r = rnd(mm(mix("time_mix_r"), a["wr"]))
    k = rnd(mm(mix("time_mix_k"), a["wk"]))
    v = rnd(mm(mix("time_mix_v"), a["wv"]))
    out = rnd(wkv(k, v, jnp.exp(a["time_decay"]), a["time_first"], rnd))
    x = rnd(x + rnd(mm(rnd(jax.nn.sigmoid(r) * out), a["wo"])))
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
    """Matmul weights of one block: r, k, v, o of the time mix (D x D
    each) and the channel mix's r (D x D), k (D x F) and v (F x D)."""
    d, f = model["d_model"], model["d_ff"]
    return 5 * d * d + 2 * d * f


def head_weights(model: dict) -> int:
    return model["d_model"] * model["vocab"]


def scale_count(model: dict) -> int:
    """Per-output-channel scales of the W8 plane: one per column of each
    matmul leaf, shared over the stacked layers (5 D + F), plus the head's V."""
    d, f = model["d_model"], model["d_ff"]
    return 5 * d + f + model["vocab"]


def small_param_count(model: dict) -> int:
    """Parameters outside the matmuls that a step reads whole: per block two
    LayerNorms (2 x 2D), the time mix's three mixes, decay and bonus (5D)
    and the channel mix's two mixes (2D); ln0 and ln_f (2 x 2D)."""
    d = model["d_model"]
    return model["n_layers"] * 11 * d + 4 * d


def state_elems_per_lane(model: dict) -> int:
    """att_x, ffn_x and the WKV a, b, o per layer."""
    return model["n_layers"] * 5 * model["d_model"]
