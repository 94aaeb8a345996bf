"""The RWKV-6 family (`bench/families/rwkv6.py`): its tree against the
program's, the program against its f32 reference at a small size on the
CPU, and its work counts by hand at 7B."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, spec, work
from bench.reference import Reference
from bench.weights import root_key
from conftest import ROOT

SEED = 3_000_000_017
CONTROL = "float8_e4m3fn"


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def small():
    """The 7B configuration at a size the CPU runs: every mechanism, two
    layers, four heads of 16."""
    cfg = config("rwkv6-7b-w8")
    cfg["model"] = {**cfg["model"], "name": "rwkv6-small", "n_layers": 2,
                    "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
                    "rwkv_head_dim": 16, "d_ff": 224, "vocab": 256,
                    "rwkv_mix_rank": 8, "rwkv_decay_rank": 16}
    cfg["engine"] = {**cfg["engine"], "max_batch": 4, "prefill_chunk": 8}
    return cfg


def test_tree_matches_the_program_at_7b():
    """The family's stacked tree has the program's structure and shapes at
    the published widths (shapes only: nothing is made)."""
    from repro.configs.base import ModelConfig
    from repro.models.registry import get_model
    model = config("rwkv6-7b-w8")["model"]
    fam = spec.family(model)
    made = jax.eval_shape(lambda: fam.model_params(model, root_key(0)))
    want = get_model(ModelConfig(**model)).abstract_params()
    assert jax.tree_util.tree_structure(made) == \
        jax.tree_util.tree_structure(want)
    assert [a.shape for a in jax.tree_util.tree_leaves(made)] == \
        [a.shape for a in jax.tree_util.tree_leaves(want)]
    one = jax.eval_shape(lambda: fam.layer_params(model, root_key(0), 0))
    for a, b in fam.MATMUL_LEAVES:
        assert len(one[a][b].shape) == 2          # (K, N) per layer
    assert one["att"]["maa_w2"].shape == (5 * 64, 4096)


def served_logits(cfg, fam, tokens, prompt_len):
    """Logits of the served program: the chunked prefill over each lane's
    prompt (one chunk), then per-op decode steps teacher-forced on the
    rest of `tokens`.  Row t of lane b scores position prompt_len - 1 + t."""
    engine = harness.build_engine(cfg, fam, SEED)
    plan = engine.plan
    B, C = cfg["engine"]["max_batch"], cfg["engine"]["prefill_chunk"]
    assert prompt_len <= C
    state = plan.model.init_slot_state(B, 0, jnp.bfloat16)
    chunk = np.zeros((B, C), np.int32)
    chunk[:, :prompt_len] = tokens[:, :prompt_len]
    valid = np.zeros((B, C), bool)
    valid[:, :prompt_len] = True
    state, last = plan.prefill_fn(B)(state, chunk, valid, np.ones(B, bool))
    rows = [np.asarray(last[:, 0], np.float32)]
    for t in range(prompt_len, tokens.shape[1]):
        logits, state = plan.decode_fn(B)(state, tokens[:, t:t + 1],
                                          np.ones(B, bool))
        rows.append(np.asarray(logits[:, 0], np.float32))
    return np.stack(rows, axis=1)


@pytest.fixture(scope="module")
def readings():
    """The program's and the control's largest logit error against the f32
    reference at the small size, over 4 lanes x 13 positions."""
    cfg = small()
    fam = spec.family(cfg["model"])
    rng = np.random.default_rng(0)
    prompt_len, length = 7, 20
    tokens = rng.integers(0, cfg["model"]["vocab"],
                          (cfg["engine"]["max_batch"], length)).astype(np.int32)
    got = served_logits(cfg, fam, tokens, prompt_len)
    positions = [np.arange(prompt_len - 1, length)] * tokens.shape[0]
    ref = np.stack(Reference(cfg, fam, SEED).logits_at(tokens, positions))
    ctl = np.stack(Reference(cfg, fam, SEED, CONTROL).logits_at(tokens,
                                                                positions))
    scale = np.abs(ref).max()
    return {"program": np.abs(got - ref).max() / scale,
            "control": np.abs(ctl - ref).max() / scale}


# The served program rounds every activation and the recurrent state to
# bfloat16 (8 significant bits, a relative step of 2^-8), and its W8
# weights are the reference's exactly up to their bf16 storage.  Over two
# blocks and a 20-step recurrence those roundings leave the logits within
# a few bf16 steps of the f32 reference: 5% of the largest logit bounds
# that with room.  One precision step lower (float8 e4m3: 4 significant
# bits, a relative step of 2^-4, 16 times coarser) must not fit.  Read on
# the CPU at this size over three seeds: the program 0.011-0.027 of the
# largest logit, the control 0.21-0.24.
TOLERANCE = 0.05


def test_program_is_close_to_the_reference(readings):
    assert readings["program"] < TOLERANCE


def test_control_in_float8_is_not(readings):
    assert readings["control"] > TOLERANCE


def test_program_packs_layer_by_layer_as_a_whole_tree():
    """The served tree the family makes layer by layer is the program's
    `pack_params` of the whole f32 tree: the same structure, shapes and
    dtypes, the same scales and codes.  The draws are equal only to an ulp
    or so (a vmapped or a looped normal may run other vector code for its
    inverse error function), so a scale may move by an ulp and a rare
    weight that lies on a level's midpoint may take the next code."""
    from repro.core.quant.serving import pack_params
    cfg = small()
    model = cfg["model"]
    fam = spec.family(model)
    root = root_key(SEED)
    got = fam.program_params(cfg, root, lambda tree: tree)
    want = jax.jit(lambda r: pack_params(fam.model_params(model, r)))(root)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == np.uint8:
            assert np.mean(a != b) < 1e-3
        else:
            np.testing.assert_allclose(a.astype(np.float32),
                                       b.astype(np.float32), rtol=1e-2)


def test_work_counts_by_hand_at_7b():
    cfg = config("rwkv6-7b-w8")
    model = cfg["model"]
    fam = spec.family(model)
    D, F, V, L, H, N, Rm, Rd = 4096, 14336, 65536, 32, 64, 64, 64, 128
    layer = 6 * D * D + 2 * D * F + 2 * 5 * Rm * D + 2 * Rd * D
    assert fam.layer_matmul_weights(model) == layer == 221_773_824
    assert fam.head_weights(model) == D * V == 268_435_456
    assert fam.scale_count(model) == 9 * D + F + 5 * Rm + Rd + V == 117_184
    assert fam.small_param_count(model) == L * 16 * D + 4 * D == 2_113_536
    assert fam.state_elems_per_lane(model) == L * (2 * D + H * N * N) \
        == 8_650_752
    assert fam.layer_extra_flops(model) == 7 * H * N * N == 1_835_008
    assert fam.wkv_call_flops(cfg) == 32 * 64 * 7 * H * N * N
    assert fam.wkv_call_bytes(cfg) == 4 * (5 * 32 * 64 * H * N
                                           + 2 * 32 * H * N * N) \
        == 234_881_024
    weights = L * layer + D * V + 4 * (9 * D + F + 5 * Rm + Rd + V) \
        + 2 * (L * 16 * D + 4 * D)
    assert work.weight_bytes(fam, cfg) == weights
    lane = 2 * D + 2 * 2 * L * (2 * D + H * N * N) + 2 * V
    assert work.decode_step_bytes(fam, cfg, 32) == weights + 32 * lane
    # a decode token: every matmul weight twice, head included, and the
    # WKV-6 step in every layer
    assert work.token_flops(fam, model, 1, 1) == \
        2 * (L * layer + D * V) + L * 7 * H * N * N
