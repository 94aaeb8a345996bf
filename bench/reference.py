"""Plain float32 reference: weights from the seed, the stated plane applied
by its definition, one layer at a time.

It imports nothing of the program.  The model comes from the
configuration's family module (`bench/families/`, see
`bench.spec.family`): its weights, made one layer at a time, and its
block's forward pass.  What stays here is what every family shares: the
configuration's weight plane, read from its definition,

  * Δ-PoT W8 (the paper's §3.1 with term widths (3, 4) and a sign bit):
    a level is 0, 2^-a, or 2^-a + 2^-(a+b) for a in 1..7, b in 1..15;
    each output channel of a matmul leaf gets the scale
    max|w| / max level, the maximum taken over the stacked layers too
    (the plane's scale is per column of the stacked leaf), and a weight
    becomes sign(w) * scale * the level nearest |w| / scale (ties to
    the lower level);
  * every other parameter is stored in the plane's `other` dtype;

and the pass around the blocks: ln0 after the embedding, the family's
blocks in order, ln_f and the head.  Every matmul runs in float32 at
`highest` precision.  The control rounds, one precision step below the
configuration's, every value that the served program rounds to its
compute or state dtype.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from bench.weights import root_key

W8_KS = (3, 4)


MAX_LEVEL = 0.75        # 2^-1 + 2^-2


def w8_level(y):
    """The W8 level nearest y >= 0 (ties to the lower level), exactly.

    Between 2^e and 2^(e+1), for a = -e in 1..7, the levels are 2^e and
    2^e (1 + 2^-b), b in 1..15, so with y = 2^e (1 + t) the nearest level
    rounds t to 0 or a power of two: between 2^k and 2^(k+1) the midpoint
    is 1.5 x 2^k.  Below 2^-7 only 0 and 2^-7 remain; above 0.75 only
    0.75.  frexp and ldexp keep every step exact."""
    mant, ex = jnp.frexp(y)
    e = ex - 1                                  # y in [2^e, 2^(e+1))
    t = 2.0 * mant - 1.0
    tm, tk = jnp.frexp(t)                       # t in [2^(tk-1), 2^tk)
    frac = jnp.ldexp(jnp.ones_like(t), jnp.where(tm > 0.75, tk, tk - 1))
    frac = jnp.where(t <= 2.0 ** -16, 0.0,
                     jnp.where(tk - 1 < -15, 2.0 ** -15, frac))
    level = jnp.where(e < -7, 2.0 ** -7, jnp.ldexp(1.0 + frac, e))
    return jnp.where(y > 2.0 ** -8, jnp.minimum(level, MAX_LEVEL), 0.0)


def w8_value(w, scale):
    """A matmul weight as the W8 plane stores it (f32)."""
    return jnp.sign(w) * w8_level(jnp.abs(w) / scale) * scale


def w8_scale(amax):
    """Per-column scale from the column's largest magnitude."""
    s = amax / MAX_LEVEL
    return jnp.where(s <= 0, 1.0, s)


def stored(x, dtype):
    """x as a leaf stored in `dtype` reads back (f32)."""
    return x.astype(dtype).astype(jnp.float32)


def layer_norm(x, p, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _plane_layer(cfg, fam, root, layer, amax):
    """Layer `layer` as the configuration's plane stores it (f32)."""
    model, other = cfg["model"], cfg["plane"]["other"]
    raw = fam.layer_params(model, root, layer)
    p = jax.tree_util.tree_map(lambda x: stored(x, other), raw)
    for a, b in fam.MATMUL_LEAVES:
        p[a][b] = w8_value(raw[a][b], w8_scale(amax[(a, b)]))
    return p


@functools.partial(jax.jit, static_argnums=(0, 1))
def _leaf_amax(fam, model_items, root, layer):
    model = dict(model_items)
    p = fam.layer_params(model, root, layer)
    return {name: jnp.max(jnp.abs(p[name[0]][name[1]]), axis=0)
            for name in fam.MATMUL_LEAVES}


def matmul_amax(fam, model: dict, root) -> dict:
    """Each matmul leaf's per-column max |w| over all layers."""
    items = tuple(sorted(model.items()))
    out = None
    for layer in range(model["n_layers"]):
        cur = _leaf_amax(fam, items, root, layer)
        out = cur if out is None else {k: jnp.maximum(out[k], cur[k])
                                        for k in out}
    return out


def lower_precision(dtype):
    """Rounding through `dtype`, saturating at its largest finite value
    (float8 e4m3 has no infinity)."""
    dtype = jnp.dtype(dtype)
    big = float(jnp.finfo(dtype).max)
    return lambda x: jnp.clip(x, -big, big).astype(dtype).astype(x.dtype)


class Reference:
    """The reference for one configuration, its family module `fam` and a
    seed: float32 at `highest`.  With `control_dtype` every value the
    served program rounds to its compute or state dtype is rounded
    through that dtype instead: the control, the program one precision
    step lower."""

    def __init__(self, cfg: dict, fam, seed: int, control_dtype=None):
        self.cfg, self.fam = cfg, fam
        self.model = cfg["model"]
        self.root = root_key(seed)      # an argument of every program
        self.rnd = (lambda x: x) if control_dtype is None \
            else lower_precision(control_dtype)
        self._embed = jax.jit(self._embed_fn)
        self._layer = jax.jit(self._layer_fn)
        self._head = jax.jit(self._head_fn)

    def _embed_fn(self, tokens, root):
        top = self.fam.top_stored(self.cfg, root)
        return self.rnd(layer_norm(top["embed"][tokens], top["ln0"]))

    def _layer_fn(self, x, layer, amax, root):
        return self.fam.block(
            _plane_layer(self.cfg, self.fam, root, layer, amax), x, self.rnd)

    def _head_fn(self, rows, root):
        top = self.fam.top_stored(self.cfg, root)
        return jnp.matmul(self.rnd(layer_norm(rows, top["ln_f"])),
                          self.rnd(top["head"]),
                          precision=jax.lax.Precision.HIGHEST)

    def logits_at(self, tokens: np.ndarray, positions: list[np.ndarray],
                  block_rows: int = 1024) -> list[np.ndarray]:
        """f32 logits of sequences `tokens` (B, T), padded at the end, at
        the given positions of each row: a list of (n_b, V) arrays."""
        amax = matmul_amax(self.fam, self.model, self.root)
        x = self._embed(jnp.asarray(tokens), self.root)
        for layer in range(self.model["n_layers"]):
            x = self._layer(x, jnp.int32(layer), amax, self.root)
        rows = np.concatenate([np.asarray(x[b])[pos]
                               for b, pos in enumerate(positions)])
        pad = (-rows.shape[0]) % block_rows
        rows = np.pad(rows, ((0, pad), (0, 0)))
        out = np.concatenate([np.asarray(self._head(rows[i:i + block_rows],
                                                    self.root))
                              for i in range(0, rows.shape[0], block_rows)])
        splits = np.cumsum([len(p) for p in positions])[:-1]
        return np.split(out[:out.shape[0] - pad], splits)


def served_positions(prompt_len: int, n_served: int) -> np.ndarray:
    """Positions whose logits chose the served tokens: the prompt's last
    position gives the first token, each served token the next."""
    return np.arange(prompt_len - 1, prompt_len - 1 + n_served)


def pack_sequences(requests, rows: int, length: int):
    """requests: (prompt, served) pairs -> (rows, length) tokens, each row a
    prompt and every served token but the last, zero-padded; and each
    request's served positions.  A fixed shape per cell lets every run
    find the reference's programs compiled."""
    tokens = np.zeros((rows, length), np.int32)
    for i, (p, s) in enumerate(requests):
        seq = list(p) + list(s[:-1])
        tokens[i, :len(seq)] = seq
    return tokens, [served_positions(len(p), len(s)) for p, s in requests]


def reference_length(traffic: dict, multiple: int = 256) -> int:
    """The padded sequence length that holds any request of a mix."""
    n = traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"] - 1
    return -(-n // multiple) * multiple


def token_gaps(logits: np.ndarray, served) -> np.ndarray:
    """How far each served token's logit lies below the best logit."""
    served = np.asarray(served)
    return logits.max(axis=1) - logits[np.arange(len(served)), served]
