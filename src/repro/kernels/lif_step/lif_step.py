"""Fused LIF membrane update in Pallas.

One time step of the BSS-2 LIF dynamics — synaptic-current decay, membrane
integration, threshold, reset — fused into a single VMEM pass.  The jnp
substrate (``repro.snn.neuron``) materializes four intermediate arrays per
step; at 512 neurons × large batches × thousands of steps this is the SNN
substrate's memory-bandwidth hot spot, so the fused kernel is the TPU path.

Tiling: (8, 128) f32 tiles — the native VREG tile — over a (batch, neurons)
grid; purely elementwise, so arithmetic intensity is fixed and the win is
eliminating HBM round-trips between the four intermediate arrays.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_B = 8
BLOCK_N = 128


def _lif_kernel(v_ref, i_ref, drive_ref, v_out_ref, i_out_ref, s_out_ref, *,
                alpha_mem: float, alpha_syn: float, v_leak: float,
                v_th: float, v_reset: float):
    v = v_ref[...]
    i_syn = alpha_syn * i_ref[...] + drive_ref[...]
    v = v + (1.0 - alpha_mem) * (v_leak - v) + (1.0 - alpha_mem) * i_syn
    spikes = (v > v_th).astype(v.dtype)
    v = (1.0 - spikes) * v + spikes * v_reset
    v_out_ref[...] = v
    i_out_ref[...] = i_syn
    s_out_ref[...] = spikes


def lif_step_fwd(v, i_syn, drive, *, alpha_mem: float, alpha_syn: float,
                 v_leak: float = 0.0, v_th: float = 1.0, v_reset: float = 0.0,
                 block_b: int = BLOCK_B, block_n: int = BLOCK_N,
                 interpret: bool = False):
    """Core pallas_call: all inputs f32[batch, n_neurons] (block multiples)."""
    batch, n = v.shape
    grid = (batch // block_b, n // block_n)
    spec = pl.BlockSpec((block_b, block_n), lambda i, j: (i, j))
    kernel = functools.partial(
        _lif_kernel, alpha_mem=alpha_mem, alpha_syn=alpha_syn, v_leak=v_leak,
        v_th=v_th, v_reset=v_reset)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=(spec, spec, spec),
        out_shape=(jax.ShapeDtypeStruct((batch, n), v.dtype),) * 3,
        interpret=interpret,
    )(v, i_syn, drive)
