"""Continuous-batching engine tests: slot pool reuse, interleaved
prefill+decode equivalence vs the sequential loop (bit-identical), and
fixed-shape no-recompile behavior (jit trace counts)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.serve import sequential_decode
from repro.models.registry import get_model
from repro.serving import SamplingParams, ServingEngine, SlotStatePool


@pytest.fixture(scope="module")
def rwkv4():
    model = get_model("rwkv4-169m", smoke=True)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params


class TestSlotStatePool:
    def test_free_list_admission_eviction_reuse(self, rwkv4):
        model, _ = rwkv4
        pool = SlotStatePool(model, 3)
        assert (pool.n_free, pool.n_active) == (3, 0)
        a, b, c = pool.acquire(), pool.acquire(), pool.acquire()
        assert (a, b, c) == (0, 1, 2)       # lowest-numbered first
        assert pool.acquire() is None       # full
        pool.release(b)
        assert pool.n_free == 1
        assert pool.acquire() == b          # freed slot is reused
        with pytest.raises(ValueError):
            pool.release(99)
        pool.release(a)
        with pytest.raises(ValueError):     # double-free
            pool.release(a)

    def _fill(self, pool, tag: float):
        """A batch-1 lane tree holding `tag` in every element."""
        return jax.tree_util.tree_map(
            lambda a: jnp.full_like(a, tag).astype(a.dtype), pool._fresh)

    def _assert_lane_is(self, pool, slot: int, tag: float):
        for leaf in jax.tree_util.tree_leaves(pool.read_slot(slot)):
            assert np.all(np.asarray(leaf, np.float32) == tag), \
                f"slot {slot} lost its state (expected {tag})"

    def _interleave(self, pool, steps: int, seed: int = 0):
        """Deterministic interleaved admit/evict/cancel churn: every live
        slot carries a unique tag written at admission; after every
        release-or-admit step the free list must stay duplicate-free and
        consistent with the live set, and NO live slot's state may change
        — i.e. slot reuse never aliases live state, no matter how
        fragmented the free list gets."""
        rng = np.random.default_rng(seed)
        live: dict[int, float] = {}
        next_tag = 1.0
        for _ in range(steps):
            evict = live and (pool.n_free == 0 or rng.random() < 0.45)
            if evict:
                slot = int(rng.choice(sorted(live)))   # cancel mid-life
                del live[slot]
                pool.release(slot)
            else:
                slot = pool.acquire()
                assert slot is not None and slot not in live
                pool.write_slot(slot, self._fill(pool, next_tag))
                live[slot] = next_tag
                next_tag += 1.0
            assert len(set(pool._free)) == len(pool._free)
            assert pool.n_active == len(live)
            assert set(pool._free).isdisjoint(live)
        for slot, tag in live.items():
            self._assert_lane_is(pool, slot, tag)

    def test_fragmentation_interleaved_churn_never_aliases(self, rwkv4):
        model, _ = rwkv4
        pool = SlotStatePool(model, 4)
        self._interleave(pool, steps=80)

    def test_fragmentation_under_sharded_pool(self, rwkv4):
        """Same churn on a pool whose slot axis is sharded over a serving
        mesh (1 device here; all 8 under the CI multi-device leg):
        per-lane dynamic-slice addressing must keep working across shard
        boundaries, and `decode_state_batch_axes` must stay consistent
        with the placed leaves — the slot axis is still where the axes
        tree says it is, and only that axis may be sharded."""
        from repro.launch.mesh import make_serving_mesh
        from repro.parallel.sharding import pool_shardings
        model, _ = rwkv4
        n_dev = len(jax.devices())
        n_slots = max(4, n_dev)
        mesh = make_serving_mesh(n_dev)
        state_ab = jax.eval_shape(
            lambda: model.init_slot_state(n_slots, 0, jnp.bfloat16))
        sh = pool_shardings(model.decode_state_axes(), state_ab, mesh)
        pool = SlotStatePool(model, n_slots, shardings=sh)
        axes = model.decode_state_batch_axes()
        leaves = jax.tree_util.tree_leaves(pool.state)
        assert len(axes) == len(leaves)
        for leaf, ax in zip(leaves, axes):
            assert leaf.shape[ax] == n_slots
            spec = tuple(leaf.sharding.spec) + (None,) * leaf.ndim
            assert all(s is None for i, s in enumerate(spec[:leaf.ndim])
                       if i != ax), "non-slot axis got sharded"
        self._interleave(pool, steps=60, seed=3)

    @pytest.mark.parametrize("arch", ["rwkv4-169m", "rwkv6-7b",
                                      "zamba2-7b"])
    def test_slot_read_write_roundtrip(self, arch):
        """Slot addressing is derived from decode_state_axes naming, so it
        must work across wkv4 (L,B,D), wkv6 (L,B,H,N,N) and the hybrid's
        nested ssd/conv/kv layouts."""
        model = get_model(arch, smoke=True)
        pool = SlotStatePool(model, 3, max_len=8)
        lane = jax.tree_util.tree_map(
            lambda a: jnp.full_like(a, 7).astype(a.dtype), pool._fresh)
        pool.write_slot(1, lane)
        got = pool.read_slot(1)
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(lane)):
            np.testing.assert_array_equal(np.asarray(g, np.float32),
                                          np.asarray(w, np.float32))
        # neighbours untouched
        for other in (0, 2):
            for leaf in jax.tree_util.tree_leaves(pool.read_slot(other)):
                assert not np.all(np.asarray(leaf, np.float32) == 7.0)
        pool.reset_slot(1)
        for g, f in zip(jax.tree_util.tree_leaves(pool.read_slot(1)),
                        jax.tree_util.tree_leaves(pool._fresh)):
            np.testing.assert_array_equal(np.asarray(g, np.float32),
                                          np.asarray(f, np.float32))


class TestEngineEquivalence:
    def test_interleaved_matches_sequential(self, rwkv4):
        """More requests than slots, ragged prompt lengths spanning chunk
        boundaries: every request's greedy output must be bit-identical to
        decoding it alone in the sequential loop."""
        model, params = rwkv4
        V = model.cfg.vocab
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, V, size=n).tolist()
                   for n in (3, 9, 17, 5, 1)]
        engine = ServingEngine(model, params=params, max_batch=3,
                               prefill_chunk=4)
        handles = [engine.submit(p, max_new_tokens=6) for p in prompts]
        engine.run()
        for h, p in zip(handles, prompts):
            assert h.done
            assert h.tokens == sequential_decode(model, params, p, 6,
                                                 width=3)

    def test_stream_yields_all_tokens(self, rwkv4):
        model, params = rwkv4
        engine = ServingEngine(model, params=params, max_batch=2,
                               prefill_chunk=4)
        h1 = engine.submit([1, 2, 3], max_new_tokens=5)
        h2 = engine.submit([4, 5], max_new_tokens=5)
        got = list(engine.stream(h1))
        assert got == h1.tokens and len(got) == 5
        engine.run()
        assert h2.done and len(h2.tokens) == 5

    def test_temperature_sampling_and_eos(self, rwkv4):
        model, params = rwkv4
        engine = ServingEngine(model, params=params, max_batch=2,
                               prefill_chunk=4)
        h = engine.submit([1, 2, 3], SamplingParams(
            max_new_tokens=8, temperature=0.9, seed=13))
        engine.run()
        assert len(h.tokens) == 8
        # eos cuts generation short (use the first sampled token as eos)
        engine2 = ServingEngine(model, params=params, max_batch=2,
                                prefill_chunk=4)
        h2 = engine2.submit([1, 2, 3], max_new_tokens=8,
                            eos_token=h.tokens[0], temperature=0.9,
                            seed=13)
        engine2.run()
        assert h2.tokens == [h.tokens[0]]

    def test_cancel_frees_slot(self, rwkv4):
        model, params = rwkv4
        engine = ServingEngine(model, params=params, max_batch=1,
                               prefill_chunk=4)
        h1 = engine.submit([1, 2, 3], max_new_tokens=50)
        h2 = engine.submit([4, 5, 6], max_new_tokens=3)
        engine.step()
        assert engine.pool.n_free == 0
        assert engine.cancel(h1)
        snap = engine.run()
        assert h1.done and h2.done and len(h2.tokens) == 3
        # cancellation is not a completion: no bogus latency sample
        assert snap["cancelled"] == 1 and snap["finished"] == 1
        assert len(engine.counters.latency_s) == 1

    def test_rejects_zero_token_budget(self, rwkv4):
        model, params = rwkv4
        engine = ServingEngine(model, params=params, max_batch=1,
                               prefill_chunk=4)
        with pytest.raises(ValueError):
            engine.submit([1, 2], max_new_tokens=0)


class TestNoRecompile:
    def test_two_traces_total(self, rwkv4):
        """Admission, retirement, ragged prompts, queue churn — the engine
        must keep exactly one trace per device program (fixed shapes)."""
        model, params = rwkv4
        engine = ServingEngine(model, params=params, max_batch=3,
                               prefill_chunk=4)
        rng = np.random.default_rng(1)
        V = model.cfg.vocab
        for wave in range(3):
            hs = [engine.submit(
                rng.integers(0, V, size=int(rng.integers(1, 11))).tolist(),
                max_new_tokens=int(rng.integers(1, 5)))
                for _ in range(4)]
            engine.run()
            assert all(h.done for h in hs)
        assert engine.trace_counts == {"decode": 1, "prefill": 1}

    def test_quantized_runs_and_no_recompile(self, rwkv4):
        model, params = rwkv4
        engine = ServingEngine(model, params=params, max_batch=2,
                               prefill_chunk=4, quantized=True)
        hs = [engine.submit([1, 2, 3, 4, 5], max_new_tokens=4)
              for _ in range(3)]
        engine.run()
        assert all(h.done and len(h.tokens) == 4 for h in hs)
        assert engine.trace_counts == {"decode": 1, "prefill": 1}

    def test_counters_snapshot(self, rwkv4):
        model, params = rwkv4
        engine = ServingEngine(model, params=params, max_batch=2,
                               prefill_chunk=4)
        engine.submit([1, 2, 3], max_new_tokens=3)
        engine.submit([4, 5], max_new_tokens=2)
        snap = engine.run()
        assert snap["admitted"] == snap["finished"] == 2
        assert snap["decode_tokens"] == 5
        assert snap["prefill_tokens"] == 5
        assert snap["peak_active_slots"] <= 2
        assert len(engine.counters.ttft_s) == 2
        assert len(engine.counters.latency_s) == 2
