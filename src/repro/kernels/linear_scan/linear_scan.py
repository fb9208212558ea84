"""Chunked diagonal-decay linear recurrence in Pallas (Mamba2 / RWKV6 engine).

Semantics (per batch b, head h; state h ∈ R^{K×V}):

    h_t = exp(w_t) ⊙_K h_{t-1} + k_t ⊗ v_t            (w_t ≤ 0, per-channel)

    mode="inclusive" (Mamba2/SSD, GLA):   y_t = q_t · h_t
    mode="bonus"     (RWKV6):             y_t = q_t · (h_{t-1} + diag(u) k_t ⊗ v_t)

Chunked evaluation: the grid is ``(batch, heads, T / chunk)`` with the chunk
axis innermost; the inter-chunk state carry lives in VMEM scratch across the
sequential grid iterations.  Within a chunk of length C:

    b_t   = Σ_{r≤t} w_r                      (inclusive cumsum, [C, K])
    y     = (q ⊙ e^{β}) @ h_carry            (inter-chunk term; β=b or b−w)
          + Σ_k q[t,k]·k[s,k]·e^{β_t[k]−b_s[k]}·mask(s,t) @ V   (intra)
    carry = e^{b_C} ⊙ carry + (k ⊙ e^{b_C−b})ᵀ @ V

Numerical stability: every exponent above is ≤ 0 (s ≤ t ⇒ β_t ≤ b_s since
w ≤ 0), so there is **no overflow for any decay strength** — unlike the
common q·e^{b} / k·e^{−b} factorization, which explodes for strong decays.
The price is the [C, C, K] broadcast in the intra term (VPU work,
C=64, K≤256 → ≤4 MiB VMEM), a deliberate TPU adaptation: MXU-friendly
factorizations are unstable here, VPU broadcast is not.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64


def _scan_kernel(q_ref, k_ref, v_ref, w_ref, u_ref, y_ref, h_scratch,
                 *, mode: str, chunk: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        h_scratch[...] = jnp.zeros_like(h_scratch)

    q = q_ref[0, 0].astype(jnp.float32)      # [C, K]
    k = k_ref[0, 0].astype(jnp.float32)      # [C, K]
    v = v_ref[0, 0].astype(jnp.float32)      # [C, V]
    w = w_ref[0, 0].astype(jnp.float32)      # [C, K]  (log decay, ≤ 0)
    h0 = h_scratch[...]                      # [K, V]

    b = jnp.cumsum(w, axis=0)                # inclusive cumsum  [C, K]
    if mode == "bonus":
        beta = b - w                         # exclusive: state *before* step t
        strict = True
    else:
        beta = b
        strict = False

    # Inter-chunk contribution: y_inter[t] = (q_t ⊙ e^{β_t}) @ h0.
    y = jax.lax.dot(q * jnp.exp(beta), h0,
                    preferred_element_type=jnp.float32)     # [C, V]

    # Intra-chunk: A[t,s] = Σ_k q[t,k] k[s,k] e^{β_t[k] − b_s[k]}, s<t (or ≤).
    expo = beta[:, None, :] - b[None, :, :]                 # [C, C, K], ≤ 0
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    mask = (s_idx < t_idx) if strict else (s_idx <= t_idx)
    prod = q[:, None, :] * k[None, :, :] * jnp.exp(expo)    # [C, C, K]
    a = jnp.where(mask, jnp.sum(prod, axis=-1), 0.0)        # [C, C]
    y = y + jax.lax.dot(a, v, preferred_element_type=jnp.float32)

    if mode == "bonus":
        u = u_ref[0].astype(jnp.float32)                    # [K]
        diag = jnp.sum(q * u[None, :] * k, axis=-1, keepdims=True)  # [C, 1]
        y = y + diag * v

    # Carry update: h = e^{b_C} ⊙ h0 + (k ⊙ e^{b_C − b})ᵀ @ V.
    b_last = b[-1]                                          # [K]
    k_scaled = k * jnp.exp(b_last[None, :] - b)             # [C, K]
    h_scratch[...] = (jnp.exp(b_last)[:, None] * h0
                      + jax.lax.dot(k_scaled.T, v,
                                    preferred_element_type=jnp.float32))

    y_ref[0, 0] = y.astype(y_ref.dtype)


def linear_scan_fwd(q, k, v, w, u, *, mode: str = "inclusive",
                    chunk: int = DEFAULT_CHUNK,
                    interpret: bool = False) -> jax.Array:
    """Core pallas_call.  Shapes (T already padded to a chunk multiple):

      q, k, w: [batch, heads, T, K]   v: [batch, heads, T, V]   u: [heads, K]
    """
    batch, heads, t, kdim = q.shape
    vdim = v.shape[-1]
    num_chunks = t // chunk
    grid = (batch, heads, num_chunks)

    qkw_spec = pl.BlockSpec((1, 1, chunk, kdim), lambda b, h, c: (b, h, c, 0))
    v_spec = pl.BlockSpec((1, 1, chunk, vdim), lambda b, h, c: (b, h, c, 0))
    u_spec = pl.BlockSpec((1, kdim), lambda b, h, c: (h, 0))

    kernel = functools.partial(_scan_kernel, mode=mode, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[qkw_spec, qkw_spec, v_spec, qkw_spec, u_spec],
        out_specs=v_spec,
        out_shape=jax.ShapeDtypeStruct((batch, heads, t, vdim), q.dtype),
        scratch_shapes=[pltpu.VMEM((kdim, vdim), jnp.float32)],
        interpret=interpret,
    )(q, k, v, w, u)
