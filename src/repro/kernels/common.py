"""Shared kernel utilities."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.runtime.monitor import span


class CompileError(RuntimeError):
    """A program failed to trace, lower or compile.  That is a fault of the
    code or of its shapes, never of the device: serving re-raises it
    instead of retrying or demoting the path (serving.scheduler)."""


def f32_dot(x, w):
    """`x @ w` accumulated in f32 and rounded once to the operands' result
    dtype — the rounding XLA gives a bf16 `x @ w`, in the only form a
    compiled Pallas TPU kernel lowers (Mosaic takes 32-bit accumulators
    only)."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(
        jnp.result_type(x, w))


def exact_jit(fn, donate_argnums=()):
    """jit with XLA's excess-precision folding DISABLED: every trace-level
    rounding (e.g. a bf16 op's output, or a `x.astype(bf16)`) is real in
    the compiled program instead of being elided into a wider consumer.

    Why this exists: two programs with the same per-element op semantics
    but different structure (a per-token scan vs a chunk-shaped
    restructuring of the same math) normally are NOT bitwise comparable,
    because XLA decides per fusion context which low-precision roundings
    to skip.  Pinning `xla_allow_excess_precision=False` makes the rounding
    behavior equal to the trace — structure-independent — which is what
    lets the fused chunked-prefill path be BIT-identical to the per-op
    scan-of-`decode_step` oracle (the serving engine compiles both its
    prefill programs through this; see docs/serving.md).  Compilation is
    AOT (`lower().compile()`) because compiler options only attach there;
    the wrapper lowers lazily on first call and caches the executable;
    a lowering and compile runs under a `serve.compile` host span, so a
    compile during serving shows in a profiler trace.
    """
    jitted = jax.jit(fn, donate_argnums=donate_argnums)
    cache = {}

    def call(*args):
        # keyed on the flattened avals so new shapes/dtypes recompile,
        # like jax.jit would (positional args only)
        key = tuple((leaf.shape, str(leaf.dtype)) if hasattr(leaf, "shape")
                    else leaf
                    for leaf in jax.tree_util.tree_leaves(args))
        if key not in cache:
            try:
                with span("serve.compile"):
                    cache[key] = jitted.lower(*args).compile(
                        compiler_options={
                            "xla_allow_excess_precision": False})
            except Exception as e:
                raise CompileError(
                    f"{getattr(fn, '__name__', fn)!s} failed to compile: "
                    f"{e}") from e
        return cache[key](*args)
    return call


def interpret_default(interpret: bool | None) -> bool:
    """Pallas kernels compile for the TPU; on the CPU backend (tests,
    `JAX_PLATFORMS=cpu`) they run in interpret mode, which executes the
    kernel body as XLA ops — the correctness-validation path.  Any other
    backend is an error: interpreting there would hide the device."""
    if interpret is None:
        backend = jax.default_backend()
        if backend not in ("cpu", "tpu"):
            raise RuntimeError(
                f"Pallas kernels run compiled on 'tpu' or interpreted on "
                f"'cpu'; the default backend is {backend!r}")
        return backend == "cpu"
    return interpret
