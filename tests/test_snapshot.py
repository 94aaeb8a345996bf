"""Crash-safe serving: tick-boundary snapshots with bit-identical resume.

The contract under test (docs/operations.md): killing the engine at ANY
tick and restoring from its newest committed snapshot yields, for every
request, a concatenated pre-crash + post-restore stream (`handle.resumed
+ handle.tokens`) that is BITWISE equal to a never-crashed oracle run —
greedy and seeded-sampled lanes alike, across fp + packed Δ-PoT, rwkv4 +
rwkv6, per-op and fused paths, speculative decode, prefix-cache lanes
and the 8-virtual-device pool.  Around that oracle: the integrity layer
(param checksums refuse corrupted planes, NaN/Inf sentinels quarantine
and requeue poisoned lanes losslessly), automatic fused→per-op path
fallback (DegradedMode events, streams unchanged), and the store's
refusal of torn/uncommitted snapshot directories.
"""
import dataclasses
import os

import jax
import numpy as np
import pytest

from repro.checkpoint.store import save_checkpoint
from repro.models.registry import get_model
from repro.runtime.monitor import (EngineCrash, ServingCounters,
                                   ServingFaultInjector)
from repro.serving import (IntegrityError, ServingEngine, SnapshotConfig,
                           load_snapshot)
from repro.serving.snapshot import (EngineSnapshot, make_rng,
                                    param_checksums, rng_state,
                                    tree_checksums, verify_param_checksums)

MULTI = len(jax.devices()) >= 8

N_TOKENS = 8
CRASH_TICK = 5


@pytest.fixture(scope="module")
def rwkv4():
    model = get_model("rwkv4-169m", smoke=True)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params


@pytest.fixture(scope="module")
def rwkv6():
    model = get_model("rwkv6-7b", smoke=True)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params


def _prompts(n, start=3):
    return [[start + i, 7, 11 + i, 2, 9, 5] for i in range(n)]


def _submit_all(engine, prompts, n_tokens=N_TOKENS):
    """Even lanes greedy, odd lanes seeded-sampled: resume parity must
    hold for both token-selection paths (the sampled lanes replay their
    serialized mid-stream RNG state)."""
    return [engine.submit(p, max_new_tokens=n_tokens,
                          temperature=(0.9 if i % 2 else 0.0), seed=11 + i)
            for i, p in enumerate(prompts)]


def _oracle_streams(model, params, prompts, n_tokens=N_TOKENS, **kw):
    eng = ServingEngine(model, params=params, prefill_chunk=4,
                        max_batch=len(prompts), **kw)
    hs = _submit_all(eng, prompts, n_tokens)
    eng.run()
    assert all(h.outcome == "finished" for h in hs)
    return {h.rid: list(h.tokens) for h in hs}


def _crash_and_restore(model, params, prompts, tmp_path, *,
                       crash_tick=CRASH_TICK, every=2, n_tokens=N_TOKENS,
                       **kw):
    """Run with snapshots + a crash fault, restore, drain; returns
    (per-rid resumed+restored streams, restored engine)."""
    inj = ServingFaultInjector(
        schedule={crash_tick: [("crash_at_tick", None)]})
    eng = ServingEngine(model, params=params, prefill_chunk=4,
                        max_batch=len(prompts), fault_injector=inj,
                        snapshot=SnapshotConfig(directory=str(tmp_path),
                                                every=every), **kw)
    _submit_all(eng, prompts, n_tokens)
    with pytest.raises(EngineCrash):
        eng.run()
    eng.snapshot_manager.wait()
    assert inj.fired == [(crash_tick, "crash_at_tick", None)]

    restored = ServingEngine.restore(str(tmp_path), params=params)
    handles = restored.handles              # run() pops finished lanes
    restored.run()
    if restored.snapshot_manager is not None:
        restored.snapshot_manager.wait()
    streams = {rid: h.resumed + h.tokens for rid, h in handles.items()}
    assert restored.counters.restores == 1
    assert restored.counters.resumed_lanes == len(prompts)
    return streams, restored


# ---------------------------------------------------------------------------
# Checksums, RNG streams, counters: the serialization primitives
# ---------------------------------------------------------------------------


def test_tree_checksums_dedupe_scalars_and_sensitivity():
    a = np.arange(6, dtype=np.float32)
    tree = {"w": a, "alias": a, "n": 3, "flag": True}
    cks = tree_checksums(tree)
    assert set(cks) == set(tree_checksums(tree))
    assert cks == tree_checksums(tree)              # deterministic
    # aliased leaves hash once and identically
    alias_keys = [k for k in cks if "alias" in k]
    w_keys = [k for k in cks if "'w'" in k or "w" in k and "alias" not in k]
    assert alias_keys and w_keys
    assert cks[alias_keys[0]] == cks[w_keys[0]]
    # a single flipped element changes exactly that plane's checksum
    b = a.copy()
    b[2] += 1
    cks2 = tree_checksums({"w": b, "alias": a, "n": 3, "flag": True})
    assert cks2[w_keys[0]] != cks[w_keys[0]]
    assert cks2[alias_keys[0]] == cks[alias_keys[0]]


def test_verify_param_checksums_names_planes_and_counts(rwkv4):
    model, params = rwkv4
    eng = ServingEngine(model, params=params, max_batch=1)
    ref = param_checksums(eng.plan.prepared)
    verify_param_checksums(eng.plan.prepared, ref)  # clean: no raise
    bad_ref = dict(ref)
    key = sorted(bad_ref)[0]
    bad_ref[key] ^= 0xFFFF
    counters = ServingCounters()
    with pytest.raises(IntegrityError, match="1 plane"):
        verify_param_checksums(eng.plan.prepared, bad_ref,
                               counters=counters, where="startup")
    assert counters.checksum_failures == 1


def test_rng_stream_serialization_is_bit_exact():
    gen = np.random.default_rng(123)
    gen.random(17)                                  # advance mid-stream
    clone = make_rng(rng_state(gen))
    assert clone is not gen
    assert np.array_equal(gen.random(64), clone.random(64))
    assert rng_state(None) is None and make_rng(None) is None


def test_counters_state_roundtrip():
    c = ServingCounters()
    c.on_tick(active=2, queued=1)
    c.on_snapshot(0.25)
    c.on_quarantine(7)
    c.on_checksum_failure(2)
    fresh = ServingCounters()
    fresh.load_state(c.state_dict())

    def _static(d):             # elapsed_s is a live wall clock
        return {k: v for k, v in d.items() if k != "elapsed_s"}

    assert _static(fresh.state_dict()) == _static(c.state_dict())
    assert fresh.snapshot()["quarantined_lanes"] == 1
    assert fresh.snapshot()["checksum_failures"] == 2


# ---------------------------------------------------------------------------
# Store refusals: torn, uncommitted and foreign directories
# ---------------------------------------------------------------------------


def test_load_snapshot_refuses_empty_and_torn_dirs(tmp_path):
    with pytest.raises(FileNotFoundError):
        load_snapshot(str(tmp_path))
    # a torn write (no COMMIT) is what a crash mid-save leaves: it must
    # be invisible, so an otherwise-empty dir still has no snapshot
    tmp = tmp_path / ".tmp-step_00000004"
    tmp.mkdir()
    np.save(tmp / "leaf.npy", np.zeros(3))
    with pytest.raises(FileNotFoundError):
        load_snapshot(str(tmp_path))


def test_load_snapshot_refuses_foreign_checkpoints(tmp_path):
    # a committed checkpoint that is NOT an engine snapshot (no snapshot
    # meta) must be refused loudly, not half-restored
    save_checkpoint(str(tmp_path), 4, {"w": np.zeros(3)},
                    meta={"unrelated": True})
    with pytest.raises(ValueError):
        load_snapshot(str(tmp_path))


def test_capture_requires_build_plan_provenance(rwkv4):
    from repro.serving import build_plan
    model, params = rwkv4
    plan = build_plan(model, params, prefill_chunk=4)
    plan.build_config = None                        # hand-built plan
    eng = ServingEngine(model, plan=plan, max_batch=1)
    with pytest.raises(RuntimeError, match="build_config"):
        EngineSnapshot.capture(eng, 0)


def test_save_refuses_corrupted_params(rwkv4, tmp_path):
    """verify_interval_s=0.0 re-checksums before EVERY save: corrupt the
    reference (stand-in for a flipped param plane) and the save must
    raise IntegrityError instead of committing a poisoned snapshot."""
    model, params = rwkv4
    eng = ServingEngine(
        model, params=params, max_batch=2, prefill_chunk=4,
        snapshot=SnapshotConfig(directory=str(tmp_path), every=2,
                                verify_interval_s=0.0))
    mgr = eng.snapshot_manager
    key = sorted(mgr.reference_checksums)[0]
    mgr.reference_checksums[key] ^= 0xFFFF
    _submit_all(eng, _prompts(2), 4)
    with pytest.raises(IntegrityError):
        eng.run()
    assert eng.counters.checksum_failures >= 1
    with pytest.raises(FileNotFoundError):          # nothing committed
        load_snapshot(str(tmp_path))


# ---------------------------------------------------------------------------
# The resume oracle: crash at a tick, restore, bitwise stream parity
# ---------------------------------------------------------------------------


def test_crash_resume_rwkv4_per_op(rwkv4, tmp_path):
    model, params = rwkv4
    prompts = _prompts(3)
    oracle = _oracle_streams(model, params, prompts)
    streams, restored = _crash_and_restore(model, params, prompts,
                                           tmp_path)
    assert streams == oracle
    # the restored engine is healthy: it serves new work afterwards
    h = restored.submit(prompts[0], max_new_tokens=3)
    restored.run()
    assert h.outcome == "finished" and len(h.tokens) == 3


def test_crash_resume_quantized_fused(rwkv4, tmp_path):
    model, params = rwkv4
    prompts = _prompts(3)
    kw = dict(quantized=True, fused_decode=True, fused_prefill=True)
    oracle = _oracle_streams(model, params, prompts, **kw)
    streams, _ = _crash_and_restore(model, params, prompts, tmp_path,
                                    **kw)
    assert streams == oracle


def test_crash_resume_rwkv6_chunked(rwkv6, tmp_path):
    model, params = rwkv6
    prompts = _prompts(2)
    kw = dict(fused_prefill=True)
    oracle = _oracle_streams(model, params, prompts, **kw)
    streams, _ = _crash_and_restore(model, params, prompts, tmp_path,
                                    **kw)
    assert streams == oracle


def test_crash_resume_speculative(rwkv4, tmp_path):
    model, params = rwkv4
    prompts = _prompts(3)
    kw = dict(speculative=2)
    oracle = _oracle_streams(model, params, prompts, **kw)
    streams, restored = _crash_and_restore(model, params, prompts,
                                           tmp_path, **kw)
    assert streams == oracle
    assert restored.scheduler._spec_snapshot is None


def test_crash_resume_prefix_cache(rwkv4, tmp_path):
    """Cache lanes: warm the cache, then crash while cached-suffix
    requests are mid-flight — the snapshot carries the cache manifest,
    so the restored engine re-leases the same entries and the streams
    stay bitwise equal to the never-crashed cache run."""
    model, params = rwkv4
    warm = [1, 2, 3, 4, 5, 6, 7, 8]
    prompts = [warm + [20 + i] for i in range(3)]

    def drive(engine):
        w = engine.submit(warm, max_new_tokens=2)
        engine.run()
        assert w.outcome == "finished"
        hs = _submit_all(engine, prompts)
        return hs

    oracle_eng = ServingEngine(model, params=params, prefill_chunk=4,
                               max_batch=3, prefix_cache=True)
    ohs = drive(oracle_eng)
    oracle_eng.run()
    oracle = {h.rid: list(h.tokens) for h in ohs}

    inj = ServingFaultInjector(
        schedule={CRASH_TICK + 3: [("crash_at_tick", None)]})
    eng = ServingEngine(model, params=params, prefill_chunk=4,
                        max_batch=3, prefix_cache=True, fault_injector=inj,
                        snapshot=SnapshotConfig(directory=str(tmp_path),
                                                every=2))
    hs = drive(eng)
    with pytest.raises(EngineCrash):
        eng.run()
    eng.snapshot_manager.wait()

    restored = ServingEngine.restore(str(tmp_path), params=params)
    assert restored.prefix_cache is not None
    handles = restored.handles
    restored.run()
    streams = {rid: h.resumed + h.tokens for rid, h in handles.items()
               if rid in oracle}
    assert streams == {h.rid: oracle[h.rid] for h in hs}
    restored.prefix_cache.check_state()


@pytest.mark.skipif(not MULTI, reason="needs >= 8 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
def test_crash_resume_multi_device(rwkv4, tmp_path):
    from repro.launch.mesh import make_serving_mesh
    model, params = rwkv4
    prompts = _prompts(8)
    mesh = make_serving_mesh(8)
    oracle = _oracle_streams(model, params, prompts, n_tokens=6,
                             mesh=mesh)
    streams, restored = _crash_and_restore(model, params, prompts,
                                           tmp_path, n_tokens=6,
                                           mesh=make_serving_mesh(8))
    assert streams == oracle
    # restore's mesh="auto" rebuilt the recorded 8-device topology
    assert restored.plan.build_config["mesh_devices"] == 8


try:
    from hypothesis import given, settings, strategies as st
except ImportError:   # optional dep: property tests importorskip at run time
    from conftest import hypothesis_stubs
    given, settings, st = hypothesis_stubs()


# upper bound 8: with 6-token prompts and 8 new tokens the last lane
# finishes during tick 8, so tick 8 is the last tick whose top-of-tick
# fault hook still fires — a later "crash" would never trigger
@given(crash_tick=st.integers(min_value=2, max_value=8))
@settings(max_examples=5, deadline=None)
def test_crash_resume_any_tick_property(crash_tick):
    """The tentpole property: the crash tick is adversarial — ANY tick
    with a committed snapshot behind it resumes bit-identically (the
    snapshot cadence guarantees the newest committed step is at most
    `every` ticks stale; the replay from there is deterministic)."""
    import tempfile
    model = get_model("rwkv4-169m", smoke=True)
    params = model.init_params(jax.random.PRNGKey(0))
    prompts = _prompts(3)
    oracle = _oracle_streams(model, params, prompts)
    with tempfile.TemporaryDirectory() as d:
        try:
            streams, _ = _crash_and_restore(
                model, params, prompts, d, crash_tick=crash_tick)
        except FileNotFoundError:
            # crash before the first committed snapshot: refusing to
            # restore is the correct outcome — nothing to resume from
            assert crash_tick <= 2
            return
    assert streams == oracle


# ---------------------------------------------------------------------------
# Sentinels: NaN/Inf quarantine replays losslessly
# ---------------------------------------------------------------------------


def test_sentinel_quarantine_replays_bit_identically(rwkv4):
    model, params = rwkv4
    prompts = _prompts(3)
    oracle = _oracle_streams(model, params, prompts)
    inj = ServingFaultInjector(
        schedule={3: [("corrupt_state_leaf", 0)]})   # payload = rid 0
    eng = ServingEngine(model, params=params, prefill_chunk=4,
                        max_batch=3, fault_injector=inj, sentinel_every=1)
    hs = _submit_all(eng, prompts)
    eng.run()
    assert eng.counters.quarantined_lanes == 1
    assert {h.rid: list(h.tokens) for h in hs} == oracle
    assert all(h.resumed == [] for h in hs)          # replay, not resume
    assert eng.pool.n_free == eng.pool.max_slots
    assert not eng.scheduler.slots and not eng.scheduler.queue


def test_sentinel_off_by_default(rwkv4):
    model, params = rwkv4
    eng = ServingEngine(model, params=params, max_batch=1)
    assert eng.scheduler.sentinel_every == 0


# ---------------------------------------------------------------------------
# Degraded mode: fused-path faults demote to the per-op twin
# ---------------------------------------------------------------------------


def _flaky(fn, fail_times):
    calls = {"n": 0}

    def wrapped(*args):
        calls["n"] += 1
        if calls["n"] <= fail_times:
            raise RuntimeError(f"injected dispatch failure {calls['n']}")
        return fn(*args)

    return wrapped, calls


def test_path_fallback_demotes_after_limit(rwkv4):
    model, params = rwkv4
    prompts = _prompts(3)
    kw = dict(fused_decode=True, fused_prefill=True)
    oracle = _oracle_streams(model, params, prompts, **kw)
    eng = ServingEngine(model, params=params, prefill_chunk=4,
                        max_batch=3, path_fault_limit=2, **kw)
    eng.scheduler.decode_fn, _ = _flaky(eng.scheduler.decode_fn, 2)
    hs = _submit_all(eng, prompts)
    eng.run()
    assert {h.rid: list(h.tokens) for h in hs} == oracle
    assert eng.scheduler.demoted == frozenset({"decode"})
    assert eng.counters.path_fallbacks == 1
    (ev,) = eng.counters.degraded_events
    assert (ev["kind"], ev["failures"], ev["to_path"]) == \
        ("decode", 2, "per_op")
    assert ev["from_path"] == eng.plan.decode_desc.name
    # demotion is sticky: later work keeps serving on the per-op twin
    h = eng.submit(prompts[0], max_new_tokens=3)
    eng.run()
    assert h.outcome == "finished" and len(h.tokens) == 3


def test_compile_error_propagates_without_demotion(rwkv4):
    """A program that fails to trace or compile is a bug, not a device
    fault: it raises out of the scheduler on the first call — no retry, no
    demotion to the per-op twin that would hide it."""
    from repro.kernels.common import CompileError, exact_jit
    model, params = rwkv4
    eng = ServingEngine(model, params=params, prefill_chunk=4, max_batch=2,
                        fused_decode=True, path_fault_limit=2)
    calls = {"n": 0}

    def broken(state, tokens, mask):
        calls["n"] += 1
        raise ValueError("trace-time bug")

    eng.scheduler.decode_fn = exact_jit(broken)
    _submit_all(eng, _prompts(2))
    with pytest.raises(CompileError):
        eng.run()
    assert calls["n"] == 1                       # traced once, not retried
    assert eng.scheduler.demoted == frozenset()
    assert eng.counters.path_fallbacks == 0


def test_path_fault_below_limit_retries_without_demotion(rwkv4):
    model, params = rwkv4
    prompts = _prompts(2)
    kw = dict(fused_decode=True)
    oracle = _oracle_streams(model, params, prompts, **kw)
    eng = ServingEngine(model, params=params, prefill_chunk=4,
                        max_batch=2, path_fault_limit=2, **kw)
    eng.scheduler.decode_fn, calls = _flaky(eng.scheduler.decode_fn, 1)
    hs = _submit_all(eng, prompts)
    eng.run()
    assert {h.rid: list(h.tokens) for h in hs} == oracle
    assert eng.scheduler.demoted == frozenset()
    assert eng.counters.path_fallbacks == 0
    assert calls["n"] > 1                            # retried the primary


# ---------------------------------------------------------------------------
# Torn writes: restore falls back to the newest committed step
# ---------------------------------------------------------------------------


def test_torn_write_falls_back_to_committed_step(rwkv4, tmp_path):
    model, params = rwkv4
    prompts = _prompts(3)
    oracle = _oracle_streams(model, params, prompts)
    inj = ServingFaultInjector(schedule={
        5: [("torn_snapshot_write", None)],
        6: [("crash_at_tick", None)]})
    eng = ServingEngine(model, params=params, prefill_chunk=4,
                        max_batch=3, fault_injector=inj,
                        snapshot=SnapshotConfig(directory=str(tmp_path),
                                                every=2))
    _submit_all(eng, prompts)
    with pytest.raises(EngineCrash):
        eng.run()
    eng.snapshot_manager.wait()
    assert any(n.startswith(".tmp-step_") for n in os.listdir(tmp_path))
    step, meta = load_snapshot(str(tmp_path))
    assert step == 4 and meta["tick"] == 4           # torn step 5 skipped

    restored = ServingEngine.restore(str(tmp_path), params=params)
    handles = restored.handles
    restored.run()
    assert {rid: h.resumed + h.tokens
            for rid, h in handles.items()} == oracle


def test_restore_refuses_wrong_params(rwkv4, tmp_path):
    model, params = rwkv4
    eng = ServingEngine(model, params=params, prefill_chunk=4,
                        max_batch=2,
                        snapshot=SnapshotConfig(directory=str(tmp_path),
                                                every=2))
    _submit_all(eng, _prompts(2), 4)
    eng.run()
    eng.snapshot_manager.wait()
    other = model.init_params(jax.random.PRNGKey(99))
    with pytest.raises(IntegrityError):
        ServingEngine.restore(str(tmp_path), params=other)
