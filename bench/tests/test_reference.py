"""The reference's reading of the W8 plane, its weights and its logits."""
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec, weights
from bench.reference import (MAX_LEVEL, Reference, w8_level, w8_scale,
                             w8_value)
from conftest import FIXTURE, ROOT

SEED = 3_000_000_007


def brute_levels():
    """0, 2^-a and 2^-a + 2^-(a+b), a in 1..7, b in 1..15."""
    out = {0.0}
    for a in range(1, 8):
        out.add(2.0 ** -a)
        out.update(2.0 ** -a + 2.0 ** -(a + b) for b in range(1, 16))
    return np.array(sorted(out))


def test_level_set():
    lv = brute_levels()
    assert len(lv) == 113 and lv.max() == MAX_LEVEL
    got = np.unique(np.asarray(w8_level(jnp.asarray(lv, jnp.float32))))
    assert np.array_equal(got, lv.astype(np.float32))


def test_nearest_level_ties_low():
    lv = brute_levels()
    rng = np.random.default_rng(0)
    mids = (lv[1:] + lv[:-1]) / 2
    y = np.concatenate([
        rng.uniform(0, 0.8, 200_000),
        np.exp(rng.uniform(np.log(1e-9), np.log(0.8), 200_000)),
        mids, np.nextafter(mids, 0), np.nextafter(mids, 1), lv,
        [0.0, 0.76, 1.0]]).astype(np.float32)
    y64 = y.astype(np.float64)
    # nearest by exhaustive search, the lower level on a tie
    dist = np.abs(y64[:, None] - lv[None, :])
    want = lv[np.argmin(dist, axis=1)]
    got = np.asarray(w8_level(jnp.asarray(y)), np.float64)
    assert np.array_equal(got, want)


def test_value_keeps_sign_and_scale():
    w = jnp.array([[-0.3, 0.75], [0.0, -1.5]], jnp.float32)
    s = w8_scale(jnp.max(jnp.abs(w), axis=0))     # columns: 0.3, 1.5 -> /0.75
    v = np.asarray(w8_value(w, s))
    assert v[0, 0] == pytest.approx(-0.3) and v[1, 1] == pytest.approx(-1.5)
    assert v[1, 0] == 0.0
    assert v[0, 1] == pytest.approx(0.375 * 2.0)  # 0.75 / 2.0 is a level


def test_weights_layer_by_layer():
    """The stacked tree the harness makes equals the layers the reference
    makes one at a time, for a seed past 32 bits, to f32 rounding: the two
    programs may evaluate the normal draws' inverse error function with
    different vector code, a few ulp apart (far below the bf16 the
    program stores)."""
    model = dict(family="rwkv", rwkv_version=4, n_layers=3, d_model=8,
                 d_ff=32, vocab=16)
    fam = spec.family(model)
    root = weights.root_key(SEED)
    stacked = fam.model_params(model, root)
    one_layer = jax.jit(lambda l: fam.layer_params(model, root, l))
    for layer in range(3):
        one = one_layer(jnp.int32(layer))
        flat_s = jax.tree_util.tree_leaves(stacked["blocks"])
        flat_o = jax.tree_util.tree_leaves(one)
        for s, o in zip(flat_s, flat_o):
            np.testing.assert_allclose(np.asarray(s[layer]), np.asarray(o),
                                       rtol=1e-5, atol=1e-7)
    assert weights.root_key(5).shape == weights.root_key(2 ** 40 + 5).shape
    assert not np.array_equal(weights.root_key(5),
                              weights.root_key(2 ** 40 + 5))


def digest(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(tree):
        a = np.asarray(leaf)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


def _config(path):
    return json.loads(path.read_text())


# Digests of what the benchmark made before its model moved into
# `bench/families/rwkv4.py` (the same programs on the same seed, on the
# CPU): the move changed no weight, no W8 code and no reference logit.
PINNED = {"model_params": "fa4cf39a6bb2f123", "codes": "b3dc8cb77cc6da0d",
          "layer0_169m": "e1087e8dc60bbd74", "top_169m": "a7c9d8a577afdaa6",
          "logits": "dec9d87f7d7da4ea", "control": "ee47de1db0cade58"}


def test_weights_and_codes_are_pinned():
    from repro.core.quant.serving import pack_params
    tiny = _config(FIXTURE / "bench" / "configs" / "tiny-w8.json")
    m169 = _config(ROOT / "bench" / "configs" / "rwkv4-169m-w8.json")["model"]
    fam = spec.family(tiny["model"])
    assert spec.family(m169) is fam
    root = weights.root_key(SEED)
    got = {
        "model_params": digest(jax.jit(
            lambda r: fam.model_params(tiny["model"], r))(root)),
        "codes": digest(fam.program_params(tiny, root, pack_params)),
        "layer0_169m": digest(jax.jit(
            lambda l: fam.layer_params(m169, root, l))(jnp.int32(0))),
        "top_169m": digest(jax.jit(
            lambda r: fam.top_params(m169, r))(root))}
    assert got == {k: PINNED[k] for k in got}


def test_reference_logits_are_pinned():
    tiny = _config(FIXTURE / "bench" / "configs" / "tiny-w8.json")
    fam = spec.family(tiny["model"])
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, tiny["model"]["vocab"], (3, 40)).astype(np.int32)
    positions = [np.arange(5, 12), np.arange(30, 40), np.arange(0, 3)]
    got = {"logits": digest(Reference(tiny, fam, SEED).logits_at(
               tokens, positions)),
           "control": digest(Reference(tiny, fam, SEED, "float8_e4m3fn")
                             .logits_at(tokens, positions))}
    assert got == {k: PINNED[k] for k in got}
