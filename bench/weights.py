"""Random RWKV-4 weights made from a seed, layer by layer.

Both sides of the comparison make their weights here: the harness stacks
every layer in one jitted call and hands the tree to the program, which
packs it to its weight plane; the reference makes one layer at a time
and applies its own reading of the plane.  Each leaf of layer l draws
from its own key, `fold_in(fold_in(root, leaf), l)`, so one layer can be
made without the others.  Values follow BlinkDL's RWKV-4 initialisation
where it is not random (decay and bonus ramps over the channels) and are
Gaussian with a 1/sqrt(fan-in) scale where it is.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

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


def root_key(seed: int):
    """A key for any whole-number seed, including ones past 32 bits."""
    seed = int(seed) % 2 ** 64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


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


def _nest(pairs) -> dict:
    out: dict = {}
    for name, value in pairs:
        node = out
        for part in name[:-1]:
            node = node.setdefault(part, {})
        node[name[-1]] = value
    return out


def layer_params(model: dict, root, layer) -> dict:
    """Layer `layer`'s weights (f32).  `layer` may be traced."""
    return _nest(
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
    return _nest(out)


def model_params(model: dict, root) -> dict:
    """The whole tree, blocks stacked on a leading layer axis (f32)."""
    blocks = jax.vmap(lambda l: layer_params(model, root, l))(
        jnp.arange(model["n_layers"]))
    return {**top_params(model, root), "blocks": blocks}
