"""Flash attention for TPU in Pallas: tiled online-softmax, causal + GQA.

Grid layout: ``(batch, q_heads, num_q_blocks, num_kv_blocks)`` with the KV
block dimension innermost.  TPU grids execute sequentially over the last
axis, so the running softmax statistics (row max ``m``, normalizer ``l``)
and the output accumulator live in VMEM scratch that persists across the KV
iterations of one (b, h, q_block) cell:

  kv_idx == 0        → initialize scratch
  every kv_idx       → one (block_q × block_kv) tile of scores on the MXU,
                        online-softmax rescale, accumulate P·V
  kv_idx == last     → normalize and write the output block

Causal masking skips fully-masked KV blocks by zero-ing their contribution
(index arithmetic keeps the grid static — XLA prunes nothing, but the
written kernel only pays the mask, not a branch).  GQA maps the query head
onto its KV head inside the BlockSpec ``index_map`` — no K/V replication in
HBM, the natural TPU translation of grouped heads.

VMEM budget per cell (block_q = block_kv = 128, head_dim ≤ 256, f32 scratch):
q,k,v,o tiles ≤ 4·128·256·4 B = 512 KiB plus 2·128·4 B statistics — well
inside the ~16 MiB/core VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 128
NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scratch, l_scratch, acc_scratch,
                 *, sm_scale: float, causal: bool, block_q: int, block_kv: int,
                 seq_len: int):
    q_blk = pl.program_id(2)
    kv_blk = pl.program_id(3)
    num_kv = pl.num_programs(3)

    @pl.when(kv_blk == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    q = q_ref[0, 0].astype(jnp.float32)                  # [block_q, d]
    k = k_ref[0, 0].astype(jnp.float32)                  # [block_kv, d]
    v = v_ref[0, 0].astype(jnp.float32)                  # [block_kv, d]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * sm_scale                                      # [block_q, block_kv]

    q_pos = q_blk * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                       (block_q, block_kv), 0)
    kv_pos = kv_blk * block_kv + jax.lax.broadcasted_iota(jnp.int32,
                                                         (block_q, block_kv), 1)
    mask = kv_pos < seq_len                               # padding mask
    if causal:
        mask = mask & (kv_pos <= q_pos)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scratch[...]                               # [block_q, 1]
    l_prev = l_scratch[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # Guard fully-masked rows (all -inf) so exp() stays finite.
    p = jnp.exp(s - jnp.where(m_new <= NEG_INF / 2, 0.0, m_new))
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(jnp.where(m_prev <= NEG_INF / 2, NEG_INF, m_prev - m_new))
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, alpha)

    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc_scratch[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    m_scratch[...] = m_new
    l_scratch[...] = l_new
    acc_scratch[...] = acc

    @pl.when(kv_blk == num_kv - 1)
    def _finalize():
        l = l_scratch[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scratch[...] / l_safe).astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        sm_scale: float, causal: bool,
                        true_kv_len: int | None = None,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_kv: int = DEFAULT_BLOCK_KV,
                        interpret: bool = False) -> jax.Array:
    """Core pallas_call.  Shapes (already padded to block multiples):

      q: [batch, q_heads, seq_q, d]      k, v: [batch, kv_heads, seq_kv, d]

    q_heads must be a multiple of kv_heads (GQA group = q_heads // kv_heads).
    ``true_kv_len`` masks KV padding columns beyond the real sequence.
    """
    batch, q_heads, seq_q, d = q.shape
    _, kv_heads, seq_kv, _ = k.shape
    assert q_heads % kv_heads == 0
    group = q_heads // kv_heads
    num_q = seq_q // block_q
    num_kv = seq_kv // block_kv
    if true_kv_len is None:
        true_kv_len = seq_kv

    grid = (batch, q_heads, num_q, num_kv)

    q_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda b, h, iq, ik: (b, h, iq, 0))
    kv_spec = pl.BlockSpec((1, 1, block_kv, d),
                           lambda b, h, iq, ik: (b, h // group, ik, 0))
    o_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda b, h, iq, ik: (b, h, iq, 0))

    kernel = functools.partial(
        _attn_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_kv=block_kv, seq_len=true_kv_len)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running row max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running normalizer l
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
