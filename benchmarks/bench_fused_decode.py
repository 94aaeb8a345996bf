"""Fused decode kernels vs the per-op decode path, across depths/batches.

Measures single-token decode throughput (tokens/s, greedy, state carried
across steps) for four executions of the SAME math — all producing
identical argmax tokens (asserted before timing):

  * PER-OP      — one device launch per datapath op (layernorm, each
    token-shift mix, each matvec, the WKV update, each gate), i.e. every
    intermediate makes an HBM round-trip between launches.  This is the
    baseline the paper's fully-on-chip pipeline is built against (and what
    RWKVQuant's bandwidth analysis says dominates single-token inference).
  * MONOLITHIC  — the engine's per-op path: `decode_step` under one jit.
    XLA fuses elementwise chains but still materializes matmul and scan
    intermediates between its kernels.
  * FUSED-BLOCK — `decode_step_fused`: ONE Pallas launch per block
    (kernels/fused_decode.py), L launches per step under `lax.scan`.
  * FUSED-MODEL — `decode_step_fused_model`: the whole-model megakernel —
    ONE Pallas launch per step, residual on-chip across the entire stack,
    each layer's weights fetched as one contiguous chunk per dtype
    (pre-chunked once outside the step via `prepare_fused_model_params`,
    exactly as the serving engine runs it) and double-buffered behind the
    previous layer's compute in the streaming binding.  Off-TPU all
    Pallas paths run in interpret mode, so the megakernel's advantage
    here is launch amortization plus the chunked weight stream (one
    fetch per layer instead of one gather per leaf); on TPU the same
    launch additionally keeps residual + state VMEM-resident for the
    entire stack.

The sweep covers batch 1 and 8 at several model depths (launch overhead
scales with L, which is exactly what the megakernel amortizes) and reports
an analytic HBM bytes/token estimate per path, fp(bf16) vs Δ-PoT-packed —
the paper's bandwidth story.

Gates (enforced via exit status on full runs, recorded always):
  * fused-block >= 1.5x PER-OP at batch 8 (PR 2's gate, kept honest);
  * fused-model >= 1.0x fused-block at batch 8 (the megakernel must not
    lose to the per-block path it replaces).

`--json` writes the machine-readable `BENCH_decode.json` (median tok/s and
bytes/token per variant) so the repo's perf trajectory is tracked across
PRs; `--smoke` shrinks the sweep for CI, where gates are reported but not
enforced (shared-runner timing is too noisy to fail a build on).

Run: PYTHONPATH=src python -m benchmarks.bench_fused_decode [--smoke] [--json]
"""
from __future__ import annotations

import argparse
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import emit, tokens_per_s, write_bench_json
from repro.core.quant.serving import pack_params, unpack_params
from repro.core.wkv.wkv4 import WKV4State, wkv4_step
from repro.models import layers as L
from repro.models.registry import get_model
from repro.models.rwkv4 import block_decode   # noqa: F401  (datapath ref)

ARCH = "rwkv4-169m"
BATCHES = (1, 8)
DEPTHS = (2, 4, 8)
N_ITERS = 12
N_ROUNDS = 5     # interleaved re-measurements per variant; best-of-rounds
                 # (shared machines: load spikes hit single rounds, not 5)
JSON_PATH = "BENCH_decode.json"


# ---------------------------------------------------------------------------
# PER-OP path: every datapath op is its own jitted device call
# ---------------------------------------------------------------------------


def build_per_op_step(model):
    """decode_step as one launch PER OP (rwkv4).  Same math/dtype sequence
    as models.rwkv4.block_decode, so tokens match the oracle."""
    cfg = model.cfg
    dt = jnp.dtype(cfg.dtype)
    j = jax.jit

    embed = j(lambda emb, t: jnp.take(emb, t[:, 0], axis=0).astype(dt))
    ln = j(lambda p, x: L.apply_norm(p, x[:, None], "layernorm")[:, 0])
    mix = j(lambda h, prev, m: h * m + prev * (1.0 - m))
    mm = j(lambda a, w: a @ w)
    decay = j(lambda td: jnp.exp(td.astype(jnp.float32)))
    wkv = j(lambda a, b, o, k, v, w, u: wkv4_step(
        WKV4State(a.astype(jnp.float32), b.astype(jnp.float32),
                  o.astype(jnp.float32)),
        k.astype(jnp.float32), v.astype(jnp.float32), w,
        u.astype(jnp.float32)))
    gate = j(lambda r, out: jax.nn.sigmoid(r) * out.astype(r.dtype))
    add = j(lambda x, y: x + y.astype(x.dtype))
    sig = j(jax.nn.sigmoid)
    sqrelu = j(lambda k: jnp.square(jax.nn.relu(k)))
    mul = j(lambda a, b: a * b)
    head = j(lambda x, w: x @ w.astype(x.dtype))
    cast = j(lambda s, like: s.astype(like.dtype))

    def step(params, layer_params, state, tokens):
        """state: list of per-layer dicts (host-sliced once, outside)."""
        x = embed(params["embed"], tokens)
        x = ln(params["ln0"], x)
        new_state = []
        for lp, st in zip(layer_params, state):
            h = ln(lp["ln1"], x)
            p = lp["att"]
            r = mm(mix(h, st["att_x"], p["time_mix_r"]), p["wr"])
            k = mm(mix(h, st["att_x"], p["time_mix_k"]), p["wk"])
            v = mm(mix(h, st["att_x"], p["time_mix_v"]), p["wv"])
            w = decay(p["time_decay"])
            nwkv, out = wkv(st["wkv_a"], st["wkv_b"], st["wkv_o"],
                            k, v, w, p["time_first"])
            att = mm(gate(r, out), p["wo"])
            x2 = add(x, att)
            h2 = ln(lp["ln2"], x2)
            p = lp["ffn"]
            rr = sig(mm(mix(h2, st["ffn_x"], p["time_mix_r"]), p["wr"]))
            kk = sqrelu(mm(mix(h2, st["ffn_x"], p["time_mix_k"]), p["wk"]))
            ffn = mul(rr, mm(kk, p["wv"]))
            x = add(x2, ffn)
            new_state.append({
                "att_x": cast(h, st["att_x"]),
                "ffn_x": cast(h2, st["ffn_x"]),
                "wkv_a": cast(nwkv.a, st["wkv_a"]),
                "wkv_b": cast(nwkv.b, st["wkv_b"]),
                "wkv_o": cast(nwkv.o, st["wkv_o"])})
        x = ln(params["ln_f"], x)
        return head(x, params["head"])[:, None], new_state

    return step


# ---------------------------------------------------------------------------
# HBM bytes/token (weight stream measured from the ACTUAL arrays;
# activation/state round-trips analytic — see docs/kernels.md §bandwidth)
# ---------------------------------------------------------------------------


def hbm_bytes_per_token(cfg, batch: int, params, prep) -> dict:
    """Bytes/token per decode path.

    Weight stream: every path reads each weight once per step (XLA/Pallas
    keep them HBM-resident) EXCEPT the embedding table, which is a
    batch-row gather — `batch` rows at the stored dtype, not a full-table
    scan.  The per-path weight bytes come straight from
    `common.tree_hbm_bytes` over the tree that path actually consumes:
    the raw (fp or packed) tree for per-op / mono / fused-block, the
    prepared megakernel tree (per-dtype contiguous slabs + aux const
    maps) for fused-model — so bf16 (2 B), Δ-PoT W8 codes (1 B), W4
    nibble pairs (0.5 B) and VQ indices (1 B + codebook) are priced at
    their true stored sizes, and a new weight plane changes the number
    without anyone editing a formula here.

    Activation/state traffic stays analytic per path: per-op round-trips
    every intermediate (written by one launch, read by the next) — ~18
    (B, D)-sized activations + the F-wide FFN pair per layer, plus the
    state twice per launch touching it.  Monolithic fuses the elementwise
    chains but still materializes every matmul output (6 D-wide + 1
    F-wide per layer) between its kernels, plus the state both ways.
    Fused-block writes only the new state and the block output — but the
    residual still crosses HBM between the L launches.  Fused-model
    eliminates those L round-trips too: the residual enters and leaves
    HBM exactly once per step."""
    from benchmarks.common import tree_hbm_bytes
    D, F, Lc, V = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.vocab

    def weight_stream(tree):
        emb = tree["embed"]
        isz = jnp.dtype(emb.dtype).itemsize
        return tree_hbm_bytes(tree) - int(emb.size) * isz + batch * D * isz

    w_tree, w_mega = weight_stream(params), weight_stream(prep)
    state = Lc * 5 * batch * D * 2          # bf16 state leaves
    act = batch * D * 2
    per_layer_int = 18 * act + 2 * batch * F * 2
    per_op = w_tree + Lc * (per_layer_int * 2 + state // Lc * 2)
    per_layer_mm = (6 * act + batch * F * 2) * 2    # matmul outs, w+r
    mono = w_tree + state * 2 + Lc * per_layer_mm + 2 * act + batch * V * 4
    fused_block = w_tree + state * 2 + Lc * act * 2 + batch * V * 4
    fused_model = w_mega + state * 2 + 2 * act + batch * V * 4
    return {"per_op": per_op / batch,
            "mono": mono / batch,
            "fused_block": fused_block / batch,
            "fused_model": fused_model / batch}


# ---------------------------------------------------------------------------
# One (depth, batch) sweep cell
# ---------------------------------------------------------------------------


def _carried(step):
    """Wrap (state -> (logits, state)) into a state-carrying closure the
    shared timing helper can call repeatedly."""
    def run():
        run.state = step(run.state)[1]
        return run.state
    return run


def _measure(variants, states, batch: int, iters: int,
             rounds: int = N_ROUNDS) -> dict:
    """tok/s per variant: `rounds` interleaved passes over all variants,
    best-of-rounds per variant (median within a pass, max across passes) —
    interleaving keeps shared-machine load drift from skewing the RATIOS
    between variants, which is what the gates consume."""
    tok_s = {name: 0.0 for name in variants}
    for _ in range(rounds):
        for name, step in variants.items():
            step.state = states[name]
            tok_s[name] = max(tok_s[name],
                              tokens_per_s(step, batch, iters=iters))
    return tok_s


def bench_depth(cfg, batch: int, iters: int, records: list,
                rounds: int = N_ROUNDS) -> dict:
    """Time every variant at one (depth, batch) cell; returns fp tok/s by
    variant name (for the gates)."""
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (batch, 1)), jnp.int32)
    st0 = model.init_decode_state(batch, 0, jnp.bfloat16)

    per_op_step = build_per_op_step(model)
    cast = model.cast_params(params)
    layer_params = [jax.tree_util.tree_map(lambda p: p[i], cast["blocks"])
                    for i in range(cfg.n_layers)]
    st_list = [jax.tree_util.tree_map(lambda s: s[i], st0)
               for i in range(cfg.n_layers)]
    mono = jax.jit(model.decode_step)
    fused_b = jax.jit(model.decode_step_fused)
    fused_m = jax.jit(model.decode_step_fused_model)
    # megakernel serving form: weights chunked once, outside the step
    prep = model.prepare_fused_model_params(params)

    # --- token equivalence before timing -----------------------------------
    l_po, _ = per_op_step(cast, layer_params, st_list, toks)
    l_mono, _ = mono(params, st0, toks, jnp.int32(0))
    l_fb, _ = fused_b(params, st0, toks, jnp.int32(0))
    l_fm, _ = fused_m(prep, st0, toks, jnp.int32(0))
    assert np.array_equal(np.argmax(np.asarray(l_po, np.float32), -1),
                          np.argmax(np.asarray(l_mono, np.float32), -1))
    assert np.array_equal(np.asarray(l_mono, np.float32),
                          np.asarray(l_fb, np.float32))
    assert np.array_equal(np.asarray(l_mono, np.float32),
                          np.asarray(l_fm, np.float32))

    # --- fp variants (state carried across steps, like the engine) ---------
    hbm = hbm_bytes_per_token(cfg, batch, params, prep)
    variants = {
        "per_op": _carried(lambda s: per_op_step(cast, layer_params, s,
                                                 toks)),
        "mono": _carried(lambda s: mono(params, s, toks, jnp.int32(0))),
        "fused_block": _carried(lambda s: fused_b(params, s, toks,
                                                  jnp.int32(0))),
        "fused_model": _carried(lambda s: fused_m(prep, s, toks,
                                                  jnp.int32(0))),
    }
    states = {name: (st_list if name == "per_op" else st0)
              for name in variants}
    tok_s = _measure(variants, states, batch, iters, rounds)
    for name in variants:
        records.append({
            "variant": name, "quant": "fp", "batch": batch,
            "n_layers": cfg.n_layers, "tok_s": round(tok_s[name], 3),
            "us_per_step": round(batch * 1e6 / tok_s[name], 1),
            "hbm_bytes_per_token": hbm[name],
        })
    emit(f"fused_decode/{cfg.name}/L{cfg.n_layers}/batch{batch}/fp",
         batch * 1e6 / tok_s["fused_model"],
         f"per_op_tok_s={tok_s['per_op']:.1f};"
         f"mono_tok_s={tok_s['mono']:.1f};"
         f"fused_block_tok_s={tok_s['fused_block']:.1f};"
         f"fused_model_tok_s={tok_s['fused_model']:.1f};"
         f"model_vs_block={tok_s['fused_model']/tok_s['fused_block']:.2f}x;"
         f"block_vs_per_op={tok_s['fused_block']/tok_s['per_op']:.2f}x;"
         f"hbm_bytes_tok_model={hbm['fused_model']:.3g}")
    return tok_s


def bench_quantized(cfg, batch: int, iters: int, records: list,
                    rounds: int = N_ROUNDS):
    """Δ-PoT W8 variants: per-op path unpacks the tree inside the jit; the
    fused paths stream uint8 codes into the kernel."""
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    packed = pack_params(params)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (batch, 1)), jnp.int32)
    st0 = model.init_decode_state(batch, 0, jnp.bfloat16)

    mono_q = jax.jit(lambda p, s, t: model.decode_step(
        unpack_params(p), s, t, jnp.int32(0)))
    fused_bq = jax.jit(lambda p, s, t: model.decode_step_fused(
        p, s, t, jnp.int32(0)))
    fused_mq = jax.jit(lambda p, s, t: model.decode_step_fused_model(
        p, s, t, jnp.int32(0)))
    prep_q = model.prepare_fused_model_params(packed)
    l_mq, _ = mono_q(packed, st0, toks)
    l_bq, _ = fused_bq(packed, st0, toks)
    l_mq2, _ = fused_mq(prep_q, st0, toks)
    assert np.array_equal(np.asarray(l_mq, np.float32),
                          np.asarray(l_bq, np.float32))
    assert np.array_equal(np.asarray(l_mq, np.float32),
                          np.asarray(l_mq2, np.float32))

    hbm = hbm_bytes_per_token(cfg, batch, packed, prep_q)
    variants = {
        "mono": _carried(lambda s: mono_q(packed, s, toks)),
        "fused_block": _carried(lambda s: fused_bq(packed, s, toks)),
        "fused_model": _carried(lambda s: fused_mq(prep_q, s, toks)),
    }
    tok_s = _measure(variants, {name: st0 for name in variants},
                     batch, iters, rounds)
    for name in variants:
        records.append({
            "variant": name, "quant": "dpot_w8", "batch": batch,
            "n_layers": cfg.n_layers, "tok_s": round(tok_s[name], 3),
            "us_per_step": round(batch * 1e6 / tok_s[name], 1),
            "hbm_bytes_per_token": hbm[name],
        })
    emit(f"fused_decode/{cfg.name}/L{cfg.n_layers}/batch{batch}/dpot_w8",
         batch * 1e6 / tok_s["fused_model"],
         f"mono_tok_s={tok_s['mono']:.1f};"
         f"fused_block_tok_s={tok_s['fused_block']:.1f};"
         f"fused_model_tok_s={tok_s['fused_model']:.1f};"
         f"model_vs_block={tok_s['fused_model']/tok_s['fused_block']:.2f}x;"
         f"hbm_bytes_tok_model={hbm['fused_model']:.3g}")


# ---------------------------------------------------------------------------


def run(smoke: bool = False, json_out: bool = False) -> bool:
    base = get_model(ARCH, smoke=True).cfg
    depths = DEPTHS[:1] if smoke else DEPTHS
    iters = 3 if smoke else N_ITERS
    rounds = 2 if smoke else N_ROUNDS
    records: list[dict] = []
    gate_cell = {}                 # batch-8 fp tok/s at the deepest depth
    for depth in depths:
        cfg = dataclasses.replace(base, n_layers=depth,
                                  name=f"{base.name}-L{depth}")
        for batch in BATCHES:
            tok_s = bench_depth(cfg, batch, iters, records, rounds)
            if batch == 8 and depth == depths[-1]:
                gate_cell = tok_s
        if depth == depths[0]:     # quantized sweep at the base depth
            for batch in BATCHES:
                bench_quantized(cfg, batch, iters, records, rounds)

    gates = {
        "fused_block_vs_per_op_batch8": {
            "speedup": round(gate_cell["fused_block"]
                             / gate_cell["per_op"], 3),
            "target": 1.5},
        "fused_model_vs_fused_block_batch8": {
            "speedup": round(gate_cell["fused_model"]
                             / gate_cell["fused_block"], 3),
            "target": 1.0},
    }
    ok = True
    for name, g in gates.items():
        g["pass"] = g["speedup"] >= g["target"]
        ok = ok and g["pass"]
        print(f"gate: {name} = {g['speedup']:.2f}x "
              f"(target >= {g['target']}x) -> "
              f"{'PASS' if g['pass'] else 'FAIL'}")

    if json_out:
        write_bench_json(JSON_PATH, {
            "bench": "fused_decode",
            "arch": base.name,
            "backend": jax.default_backend(),
            "smoke": smoke,
            "batches": list(BATCHES),
            "depths": list(depths),
            "iters": iters,
            "records": records,
            "gates": gates,
        })
    # CI smoke exists to pin the script + JSON schema, not shared-runner
    # timing — gates are recorded above but only enforced on full runs.
    return ok or smoke


def main() -> int:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="minimal sweep for CI: one depth, few iterations; "
                         "gates reported but not enforced")
    ap.add_argument("--json", action="store_true",
                    help=f"write {JSON_PATH} (machine-readable records)")
    args = ap.parse_args()
    return 0 if run(smoke=args.smoke, json_out=args.json) else 1


if __name__ == "__main__":
    raise SystemExit(main())
