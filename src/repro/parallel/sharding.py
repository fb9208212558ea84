"""Logical-axis → mesh-axis resolution (2D FSDP × TP, pod-hierarchical).

Mesh axes (launch.mesh):  ``(pod, data, model)`` in production, ``(data,
model)`` single-pod.  Mapping policy:

  * ``model``  — tensor/expert parallelism: attention heads, FFN hidden,
    expert dim, vocab.  This is the *backplane* of the paper's star: dense
    collectives (all-to-all for MoE dispatch, all-reduce for TP partials)
    stay inside the fastest mesh axis, exactly like intra-backplane spikes.
  * ``(pod, data)`` — FSDP: parameters/optimizer state sharded over the data
    axes, all-gathered per layer inside the scan. Gradient reduce-scatter
    crosses pods only once per step — the second-layer hop.

Conflict/divisibility handling: axes are resolved left-to-right; a logical
axis maps to its mesh axes only if the dim is divisible by their product and
none of them is already taken by an earlier dim — otherwise that dim stays
replicated.  This lets one rule set serve all ten architectures (e.g.
grok-1's 8 experts cannot take the 16-way ``model`` axis, so its expert FFN
dim takes it instead; whisper's odd 51865-vocab head stays replicated).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import Param, is_param

# logical axis → mesh axes (tuple = combined axes)
RULES: dict[Any, Any] = {
    "vocab": ("model",),
    "heads": ("model",),
    "ff": ("model",),
    "experts": ("model",),
    "embed": ("pod", "data"),
    "layers": (),
    None: (),
}


def _mesh_sizes(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def resolve_spec(axes: tuple, shape: tuple, mesh: Mesh,
                 rules: dict | None = None) -> P:
    """Resolve logical axes to a PartitionSpec with conflict/divisibility
    fallback."""
    rules = rules or RULES
    sizes = _mesh_sizes(mesh)
    used: set[str] = set()
    out = []
    for axis, dim in zip(axes, shape):
        mesh_axes = tuple(a for a in rules.get(axis, ()) if a in sizes)
        if mesh_axes and not (set(mesh_axes) & used):
            total = math.prod(sizes[a] for a in mesh_axes)
            if dim % total == 0:
                used.update(mesh_axes)
                out.append(mesh_axes if len(mesh_axes) > 1 else mesh_axes[0])
                continue
        out.append(None)
    return P(*out)


def param_shardings(params, mesh: Mesh, rules: dict | None = None):
    """Tree of NamedSharding matching a Param tree (prefix at Param nodes)."""
    return jax.tree.map(
        lambda p: NamedSharding(mesh, resolve_spec(p.axes, p.value.shape,
                                                   mesh, rules)),
        params, is_leaf=is_param)


def _data_axes(mesh: Mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_shardings(cfg: ModelConfig, mesh: Mesh):
    """Sharding for a training/prefill batch dict (by key)."""
    da = _data_axes(mesh)
    b = P(da)

    def spec(key):
        if key == "embeds":
            return NamedSharding(mesh, P(da, None, None))
        return NamedSharding(mesh, P(da, None))

    return spec


def cache_shardings(cfg: ModelConfig, mesh: Mesh, caches):
    """Decode-cache shardings.

    Attention KV caches shard over batch (data axes) and — since small
    kv-head counts often cannot take the 16-way model axis — over the
    *sequence* dim on ``model`` (flash-decoding-style split-K).  When the
    batch itself doesn't divide the data axes (long_500k: batch 1), the
    sequence dim takes the *whole* mesh instead.  SSM states shard heads on
    ``model``.
    """
    da = _data_axes(mesh)
    sizes = _mesh_sizes(mesh)
    model = sizes.get("model", 1)
    da_size = math.prod(sizes[a] for a in da) if da else 1
    full_mesh = (*da, "model")

    def leaf_spec(x):
        shape = x.shape
        b_ok = len(shape) >= 2 and shape[1] % da_size == 0
        b_spec = da if b_ok else None
        if len(shape) == 5:          # KV cache / SSM state [L, B, H|S, ...]
            if not b_ok and shape[3] % (da_size * model) == 0:
                return P(None, None, None, full_mesh, None)
            if shape[2] % model == 0:
                return P(None, b_spec, "model", None, None)
            if shape[3] % model == 0:
                return P(None, b_spec, None, "model", None)
            return P(None, b_spec, None, None, None)
        if len(shape) == 4:
            # MLA latent [L, B, S, lora] or conv state [L, B, K, C]
            if not b_ok and shape[2] % (da_size * model) == 0:
                return P(None, None, full_mesh, None)
            if shape[2] % model == 0:
                return P(None, b_spec, "model", None)
            return P(None, b_spec, None, None)
        if len(shape) == 3:
            return P(None, b_spec, None)
        return P(*([None] * len(shape)))

    return jax.tree.map(lambda x: NamedSharding(mesh, leaf_spec(x)), caches)


def data_sharding_if_divisible(mesh: Mesh, shape: tuple) -> NamedSharding:
    """Batch-dim sharding over the data axes, or replicated if indivisible."""
    da = _data_axes(mesh)
    sizes = _mesh_sizes(mesh)
    da_size = math.prod(sizes[a] for a in da) if da else 1
    lead = da if shape and shape[0] % da_size == 0 else None
    return NamedSharding(mesh, P(lead, *([None] * (len(shape) - 1))))


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Fabric meshes: nested axes, one per hop-graph level
# ---------------------------------------------------------------------------
#
# The exchange fabric (repro.core.fabric) maps every topology level to one
# mesh axis — level 1 (the backplane star) innermost/fastest, the top level
# outermost — generalizing the legacy (pod, data/chip) layout to N levels.
# These helpers derive the mesh from the compiled plan instead of ad-hoc
# axis-name flags; ``fabric.FabricInterconnect`` consumes the same names.


def auto_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto`` — the sharding
    rules here place data by ``PartitionSpec`` and let the compiler
    propagate it (jax 0.9 defaults to Explicit axes)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def fabric_axis_names(plan) -> tuple[str, ...]:
    """Mesh axis names for a fabric plan, leaf level first: fab0, fab1, ..."""
    return tuple(f"fab{i}" for i in range(plan.n_levels))


def fabric_mesh(plan) -> Mesh:
    """Nested device mesh for a ``fabric.FabricPlan``: one axis per level,
    top level outermost (needs ``plan.n_nodes`` devices — use
    ``xla_force_host_platform_device_count`` for CPU tests)."""
    names = fabric_axis_names(plan)
    shape = tuple(lvl.fan_in for lvl in reversed(plan.levels))
    return auto_mesh(shape, tuple(reversed(names)))


def fabric_leaf_index(axis_names: tuple, fan_ins: tuple) -> jax.Array:
    """This shard's global leaf index, in-graph, from its mesh coordinates.

    Leaf-major layout: axis 0 (the backplane star) is innermost/fastest, so
    ``leaf = sum_i axis_index(fab_i) * prod(fan_in[:i])``.  The degraded
    exchange path (``fabric.fabric_exchange`` with per-edge health) uses this
    to look up which health-mask entries govern *this* shard's uplinks and
    downlinks — static replication of the masks plus a per-shard index keeps
    the dead-edge gating inside the partitioned program, identical on every
    mesh shape the plan compiles to.
    """
    leaf = jnp.zeros((), jnp.int32)
    stride = 1
    for name, f in zip(axis_names, fan_ins):
        leaf = leaf + jax.lax.axis_index(name) * stride
        stride *= int(f)
    return leaf


def edge_neighbor_permutes(enables, *, prune: bool
                           ) -> tuple[tuple[tuple[int, int], ...], ...]:
    """Edge-neighbor index maps of one fabric level: the ``ppermute``
    schedule that replaces that level's ``all_gather`` in routed mode.

    Returns one ``((src, dst), ...)`` pair tuple per ring rotation
    ``r = 1..fan_in-1`` — rotation ``r`` ships child slot ``j``'s stream to
    slot ``(j + r) % fan_in``; the own slot (``r = 0``) never travels.
    With ``prune`` (the top level, whose plane feeds no further uplink
    cascade) pairs the static route-enable matrix disables are dropped from
    the schedule, so a disabled edge costs no wire at all; its plane row
    stays zero, which decodes as invalid.  Non-top levels must keep full
    rotations — the ungated cascade aggregates whole entity streams.
    """
    en = np.asarray(enables, dtype=bool)
    f = en.shape[0]
    if en.shape != (f, f):
        raise ValueError(f"enables must be square, got {en.shape}")
    perms = []
    for r in range(1, f):
        pairs = tuple((j, (j + r) % f) for j in range(f)
                      if not prune or en[j, (j + r) % f])
        perms.append(pairs)
    return tuple(perms)


# ---------------------------------------------------------------------------
# Activation sharding constraints (in-graph)
# ---------------------------------------------------------------------------
#
# SPMD propagation alone picks bad layouts when a dim doesn't divide the mesh
# (e.g. smollm's 9 heads on a 16-way model axis replicated whole attention
# score tensors).  Models call ``constrain(x, pattern)`` at layer boundaries;
# inside an ``activation_shardings(mesh)`` scope this becomes
# ``with_sharding_constraint`` with divisibility-checked specs, outside it is
# a no-op (single-device tests never see a mesh).

_ACT_CTX: list = []


class activation_shardings:
    """Context manager enabling in-graph activation constraints."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    def __enter__(self):
        _ACT_CTX.append(self.mesh)
        return self

    def __exit__(self, *exc):
        _ACT_CTX.pop()
        return False


def _axis_ok(dim: int, mesh: Mesh, axes) -> bool:
    sizes = _mesh_sizes(mesh)
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    if not all(a in sizes for a in axes):
        return False
    return dim % math.prod(sizes[a] for a in axes) == 0


def data_shard_count() -> int:
    """Number of data-axis shards in the active activation-sharding scope
    (1 outside a scope — single-device tests and CPU smoke paths)."""
    if not _ACT_CTX:
        return 1
    mesh = _ACT_CTX[-1]
    sizes = _mesh_sizes(mesh)
    return math.prod(sizes[a] for a in _data_axes(mesh))


def constrain(x, pattern: str):
    """Constrain activation sharding by per-dim letter pattern.

    Letters:  b=batch (data axes) · s=sequence (model, fallback only)
              h=heads (model) · d/k/f=feature (unsharded) · v=vocab (model)
              e=experts (model) · c=capacity (data axes) · .=unsharded

    'h' falls back to sharding the *sequence* dim on the model axis when the
    head count doesn't divide it (flash-decoding-style split), keeping score
    tensors partitioned for archs like smollm (9 heads) and phi3 (10 kv).
    """
    if not _ACT_CTX:
        return x
    mesh = _ACT_CTX[-1]
    da = _data_axes(mesh)
    spec: list = [None] * x.ndim
    pat = pattern.replace(" ", "")
    assert len(pat) == x.ndim, (pattern, x.shape)
    used_model = False
    for i, ch in enumerate(pat):
        if ch == "b" and _axis_ok(x.shape[i], mesh, da):
            spec[i] = da
        elif ch in ("h", "v", "e") and not used_model \
                and _axis_ok(x.shape[i], mesh, "model"):
            spec[i] = "model"
            used_model = True
        elif ch == "c" and _axis_ok(x.shape[i], mesh, da) and "b" not in pat:
            spec[i] = da
    if "h" in pat and not used_model:
        # fallback: split the sequence dim (first 's') on the model axis
        for i, ch in enumerate(pat):
            if ch == "s" and x.shape[i] > 1 \
                    and _axis_ok(x.shape[i], mesh, "model"):
                spec[i] = "model"
                used_model = True
                break
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))
