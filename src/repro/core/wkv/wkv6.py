"""RWKV-6 "Finch" WKV: linear attention with data-dependent per-channel decay.

Per head with head dim N (key) / N (value):

    y_t = r_t @ (S_{t-1} + diag(u) (k_t ⊗ v_t))
    S_t = diag(w_t) S_{t-1} + k_t ⊗ v_t            w_t ∈ (0,1)^N per token

Shapes:
    r, k, w : (B, T, H, N)    v : (B, T, H, N)    u : (H, N)
    state S : (B, H, N, N)    output : (B, T, H, N)

Three evaluation forms:
  * wkv6_step    — O(N²) per token (decode)
  * wkv6_scan    — scan over T (reference; exact)
  * wkv6_chunked — chunked sub-quadratic form used for long prefill/training;
    intra-chunk work is dense matmul (MXU-friendly) with log-space decay
    ratios for stability, inter-chunk state is carried like the scan.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def wkv6_init_state(batch: int, heads: int, head_dim: int,
                    dtype=jnp.float32) -> jnp.ndarray:
    return jnp.zeros((batch, heads, head_dim, head_dim), dtype)


def decay_update(w_col, state, kv):
    """diag(w) S + k⊗v, rounded in an order no compiler can change.

    A compiler may contract a multiply into the add that consumes it (a
    fused multiply-add skips the product's rounding; XLA:CPU does so in
    some fusions and not in others), so two programs of the same trace
    may round differently — which flips a bf16-stored state element now
    and then.
    So the decay w (f32) is split into w_hi, w rounded to bf16, and the
    exact remainder w_lo = w - w_hi (at most 16 significant bits), and
    the update is (w_hi S + k⊗v) + w_lo S.  With the state stored in
    bf16 and k, v bf16 values, every product here is exact in f32, so
    contracted or not, each add rounds once and the bits are the same.
    `w_col` is w with a trailing axis of 1 (the rows of S)."""
    w_hi = w_col.astype(jnp.bfloat16).astype(jnp.float32)
    w_lo = w_col - w_hi
    return (w_hi * state + kv) + w_lo * state


def wkv6_step(state: jnp.ndarray, r, k, v, w, u):
    """One decode step. r,k,v,w: (B,H,N); u: (H,N); state: (B,H,N,N)."""
    kv = k[..., :, None] * v[..., None, :]               # (B,H,N,N)
    y = jnp.einsum("bhn,bhnm->bhm", r, state + u[..., :, None] * kv)
    new_state = decay_update(w[..., :, None], state, kv)
    return new_state, y


def wkv6_scan(r, k, v, w, u, state=None):
    """Reference scan. r,k,v,w: (B,T,H,N); u: (H,N) -> (B,T,H,N), state."""
    B, T, H, N = r.shape
    if state is None:
        state = wkv6_init_state(B, H, N, jnp.float32)
    f32 = lambda x: x.astype(jnp.float32)

    def body(S, rkvw):
        rt, kt, vt, wt = rkvw
        S, y = wkv6_step(S, rt, kt, vt, wt, f32(u))
        return S, y

    rs = jnp.moveaxis(f32(r), 1, 0)
    ks = jnp.moveaxis(f32(k), 1, 0)
    vs = jnp.moveaxis(f32(v), 1, 0)
    ws = jnp.moveaxis(f32(w), 1, 0)
    final, ys = jax.lax.scan(body, state, (rs, ks, vs, ws))
    return jnp.moveaxis(ys, 0, 1).astype(r.dtype), final


def wkv6_chunked(r, k, v, w, u, state=None, *, chunk: int = 64,
                 subchunk: int = 16):
    """Chunked form: O(T·N²) state path + O(T·C·N) intra-chunk matmuls.

    Stability design: every exponent that reaches `exp` is <= 0, so the
    computation can only *underflow to zero* (which is also the true limit),
    never overflow.  A naive separable split r·e^{L_t} × k·e^{-L_i} is NOT
    stable — e^{-L_i} overflows under strong decay even though the ratio for
    nearby (t, i) pairs is O(1) — so the intra-chunk part uses the two-level
    scheme of chunked linear attention:

      * target sub-chunk a (rows t ∈ a) re-references decays to the
        sub-chunk start: r'_t = r_t e^{L_{t-1} − L_start[a]}  (exponent <= 0)
      * keys from strictly earlier positions: k'_i = k_i e^{L_start[a] − L_i}
        (i < start of a ⇒ exponent <= 0), masked to −inf before exp elsewhere
      * the diagonal S×S block is evaluated with the exact per-pair
        exponent tensor (small: S×S×N), masked strictly-lower before exp.
    """
    B, T, H, N = r.shape
    if T % chunk != 0:
        raise ValueError(f"T={T} not divisible by chunk={chunk}")
    C = chunk
    S_sub = min(subchunk, C)
    if C % S_sub != 0:
        raise ValueError(f"chunk={C} not divisible by subchunk={S_sub}")
    n_sub = C // S_sub
    G = T // C
    if state is None:
        state = wkv6_init_state(B, H, N, jnp.float32)
    f32 = lambda x: x.astype(jnp.float32)
    # (G, B, C, H, N)
    resh = lambda x: jnp.moveaxis(f32(x).reshape(B, G, C, H, N), 1, 0)
    rs, ks, vs, ws = resh(r), resh(k), resh(v), resh(w)
    u32 = f32(u)

    NEG = jnp.float32(-1e30)
    # strict-lower mask for the diagonal sub-chunk block
    diag_mask = jnp.tril(jnp.ones((S_sub, S_sub), bool), k=-1)
    positions = jnp.arange(C)

    def body(S, x):
        rc, kc, vc, wc = x                       # (B,C,H,N)
        logw = jnp.log(jnp.maximum(wc, 1e-38))   # (B,C,H,N)
        L = jnp.cumsum(logw, axis=1)             # inclusive  (B,C,H,N)
        Lprev = L - logw                         # exclusive: L_{t-1}
        # ---- inter-chunk: exponent Lprev <= 0, stable
        r_dec = rc * jnp.exp(Lprev)
        y = jnp.einsum("bchn,bhnm->bchm", r_dec, S)
        # ---- intra-chunk, per target sub-chunk (static unrolled loop)
        y_intra = []
        for a in range(n_sub):
            lo, hi = a * S_sub, (a + 1) * S_sub
            L_start = Lprev[:, lo:lo + 1]        # (B,1,H,N) cum thru lo-1
            r_loc = rc[:, lo:hi] * jnp.exp(Lprev[:, lo:hi] - L_start)
            # earlier keys, masked to -inf at i >= lo BEFORE the exp
            expo = L_start - L                   # (B,C,H,N), <=0 for i<lo
            expo = jnp.where((positions < lo)[None, :, None, None],
                             expo, NEG)
            k_rel = kc * jnp.exp(expo)
            att = jnp.einsum("bshn,bchn->bhsc", r_loc, k_rel)  # (B,H,S,C)
            ya = jnp.einsum("bhsc,bchn->bshn", att, vc)
            # diagonal block: exact pairwise exponents (strictly lower)
            D = Lprev[:, lo:hi, None] - L[:, None, lo:hi]  # (B,S,S,H,N)
            D = jnp.where(diag_mask[None, :, :, None, None], D, NEG)
            att_d = jnp.einsum("bshn,bihn,bsihn->bhsi",
                               rc[:, lo:hi], kc[:, lo:hi], jnp.exp(D))
            ya = ya + jnp.einsum("bhsi,bihn->bshn", att_d, vc[:, lo:hi])
            y_intra.append(ya)
        y = y + jnp.concatenate(y_intra, axis=1)
        # ---- bonus (current token)
        y = y + jnp.einsum("bchn,bchn->bch", rc * u32[None, None], kc
                           )[..., None] * vc
        # ---- state update: exponents Ltot - L <= 0 and Ltot <= 0, stable
        Ltot = L[:, -1:, :, :]                   # (B,1,H,N)
        k_fut = kc * jnp.exp(Ltot - L)           # e^{L_C - L_i} k_i
        S_new = jnp.exp(Ltot[:, 0])[..., None] * S + jnp.einsum(
            "bchn,bchm->bhnm", k_fut, vc)
        return S_new, y

    final, ys = jax.lax.scan(body, state, (rs, ks, vs, ws))
    # ys: (G, B, C, H, N) -> (B, T, H, N)
    out = jnp.moveaxis(ys, 0, 1).reshape(B, T, H, N)
    return out.astype(r.dtype), final
