"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Interpret mode runs a kernel's math on the CPU but checks none of the
chip's rules (tile alignment, 32-bit matmul accumulators, scoped VMEM),
so these tests ask the TPU compiler itself, for a described `v5e:2x2`
chip with nothing attached, at RWKV-4 169M widths (d_model 768, d_ff 3072,
vocab 50,277) and a batch of 8, and at the benchmark's larger shapes
(RWKV-4 1.5B, RWKV-6 7B).  Nothing runs: a pass says the kernels compile,
not what they compute or how fast.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and a test run's workers
all import every test file.  Where it cannot be described the fixture
skips.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.quant.serving import pack_params
from repro.kernels.fused_prefill import dpot_chunk_matmul
from repro.kernels.wkv4 import wkv4_pallas
from repro.models import rwkv4
from repro.models.registry import get_model

B, T, D, F, V = 8, 16, 768, 3072, 50277


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to a persistent cache cannot be read back
    # without the chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args):
    """Compile `fn` for the described chip; returns the executable."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # a kernel is in there
    return compiled


@pytest.mark.parametrize("b,t,c", [(B, T, D), (32, 64, 768),
                                   (32, 64, 2048)])
def test_wkv4_sequence_kernel(one_chip, b, t, c):
    """The masked WKV-4 scan of the chunked prefill, bf16-snapped carry:
    at this file's batch of 8, and at the benchmark cells' shapes, 32
    slots x 64-token chunks at 169M and 1.5B widths."""
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=one_chip)
    valid = jax.ShapeDtypeStruct((b, t), jnp.bool_, sharding=one_chip)
    _compile(lambda k, v, w, u, a, b_, o, ok: wkv4_pallas(
        k, v, w, u, a, b_, o, valid=ok, carry_dtype="bfloat16",
        interpret=False),
        f32(b, t, c), f32(b, t, c), f32(c), f32(c),
        f32(b, c), f32(b, c), f32(b, c), valid)


@pytest.mark.parametrize("m,k,n", [(B * T, D, D), (B * T, D, F),
                                   (B * T, F, D), (B, D, V), (B * T, D, V)])
def test_w8_chunk_matmul(one_chip, m, k, n):
    """The prefill's in-kernel-decode W8 matmul, including the odd
    50,277-wide vocabulary head (ragged last column block)."""
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    _compile(lambda x, w, s: dpot_chunk_matmul(x, w, s, dt="bfloat16",
                                               interpret=False),
             sds((m, k), jnp.bfloat16), sds((k, n), jnp.uint8),
             sds((1, n), jnp.float32))


@pytest.mark.parametrize("m,k,n", [(256, 8192, 2048), (256, 4096, 14336),
                                   (256, 14336, 4096), (256, 4096, 320)])
def test_w8_chunk_matmul_wide(one_chip, m, k, n):
    """The same kernel at 1.5B's and 7B's widths, with a 256-row tile as
    in a 32 x 64 chunk: full-K tiles from K = 8192 need more than the
    default scoped VMEM, which the kernel asks for; RWKV-6's 320-wide
    ddlerp LoRA is one ragged-free block."""
    test_w8_chunk_matmul(one_chip, m, k, n)


def test_wkv6_sequence_kernel(one_chip):
    """The masked WKV-6 scan of the chunked prefill, bf16-snapped carry,
    at RWKV-6 7B's shape in the benchmark: 32 slots x 64-token chunks,
    64 heads of 64."""
    from repro.kernels.wkv6 import wkv6_seq_pallas
    b, t, h, n = 32, 64, 64, 64
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=one_chip)
    valid = jax.ShapeDtypeStruct((b, t), jnp.bool_, sharding=one_chip)
    _compile(lambda r, k, v, w, u, s, ok: wkv6_seq_pallas(
        r, k, v, w, u, s, valid=ok, carry_dtype="bfloat16",
        interpret=False),
        f32(b, t, h, n), f32(b, t, h, n), f32(b, t, h, n), f32(b, t, h, n),
        f32(h, n), f32(b, h, n, n), valid)


@pytest.mark.parametrize("quantized", [True, False], ids=["w8", "bf16"])
def test_decode_megakernel_stream(one_chip, quantized):
    """The whole-model decode megakernel, weights streamed layer by layer
    (the TPU binding), on W8 codes and on bf16 weights."""
    model = get_model("rwkv4-169m", smoke=False)
    cfg = model.cfg
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    if quantized:
        params = jax.eval_shape(pack_params, params)
    prepared = jax.eval_shape(
        lambda p: rwkv4.prepare_fused_model_params(p, cfg), params)
    state = jax.eval_shape(
        lambda: model.init_slot_state(B, 0, jnp.bfloat16))
    place = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    tokens = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    _compile(lambda p, s, t: rwkv4.decode_step_fused_model(
        p, s, t, 0, cfg, weights="stream", interpret=False),
        place(prepared), place(state), tokens)
