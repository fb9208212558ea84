"""Kernel write-set checker: the pack units and the Pallas grid tilings.

The rank-and-compact write-set at the heart of every pack unit
(``spike_router._pack_indices`` / ``_pack_segmented_indices``) is the one
place a rank bug silently corrupts a *neighbour's* frame — an off-by-one
in the base offsets lands one segment's events inside the next
destination's window with no shape error anywhere.  This pass proves, per
plan capacity constant:

  * ``kernel.scatter-bounds``      — every scatter index lands in
    ``[0, capacity]`` (slot ``capacity`` is the parked overflow);
  * ``kernel.scatter-overlap``     — kept events write *distinct* slots;
  * ``kernel.scatter-order``       — kept slots are the dense arrival
    ranks ``0..k-1`` in stream order (the wire preserves order);
  * ``kernel.scatter-conservation``— kept + dropped == offered;
  * ``kernel.pack-equivalence``    — the segmented unit is bit-exact with
    the global unit on the flattened stream.

The proof is a bounded model check on the *exact* index arithmetic the
kernels run: exhaustive over every occupancy mask for small streams,
structured adversarial masks (empty/full/prefix/suffix/alternating/
segment-aligned) plus a deterministic pseudo-random batch at real sizes.

The second half statically checks the ``pallas_call`` tilings of the
router kernels (``kernel.grid-bounds`` / ``kernel.grid-overlap`` /
``kernel.grid-coverage``): every output BlockSpec's write windows,
enumerated over the whole grid through its index map, must stay in-bounds
and pairwise disjoint (and cover the output, else a warning; a block that
consecutive steps of a declared-sequential grid axis revisit is a resident
accumulator, not an overlap) — plus
``kernel.aliasing``: donated input/output aliases must agree on
shape/dtype.
"""

from __future__ import annotations

import itertools
import math

import numpy as np

from repro.analysis.diagnostics import Diagnostic, WARNING

EXHAUSTIVE_BITS = 10      # <= 2^10 masks enumerated exhaustively
RNG_MASKS = 48            # deterministic random masks at real sizes


def _masks(shape: tuple[int, ...]) -> np.ndarray:
    """Occupancy masks [M, *shape] — exhaustive when small, adversarial
    structured + seeded random otherwise."""
    n = math.prod(shape)
    if n <= EXHAUSTIVE_BITS:
        bits = np.arange(2 ** n)[:, None] >> np.arange(n)[None, :]
        return (bits & 1).astype(np.int32).reshape(-1, *shape)
    rows = [np.zeros(n), np.ones(n)]
    for k in (1, 2, n // 2, n - 1):
        pre = np.zeros(n)
        pre[:k] = 1
        rows.append(pre)
        rows.append(pre[::-1].copy())
    alt = np.zeros(n)
    alt[::2] = 1
    rows.append(alt)
    rows.append(1 - alt)
    if len(shape) == 2:                      # segment-aligned adversaries
        seg = np.zeros(shape)
        seg[::2] = 1                         # every other segment full
        rows.append(seg.reshape(-1))
        seg = np.zeros(shape)
        seg[:, -1] = 1                       # last slot of every segment
        rows.append(seg.reshape(-1))
    rng = np.random.default_rng(0)
    for p in (0.05, 0.3, 0.7):
        rows.extend((rng.random(n) < p).astype(np.int32)
                    for _ in range(RNG_MASKS // 3))
    return np.stack([r.reshape(shape) for r in rows]).astype(np.int32)


def check_pack_writeset(index_fn, shape: tuple[int, ...], capacity: int,
                        path: str, *, reference_fn=None) -> list[Diagnostic]:
    """Model-check one pack unit's scatter map over the mask battery.

    ``index_fn(ok, capacity) -> (idx, keep)`` on ``ok`` of ``shape`` (the
    factored-out write-set of the kernels).  ``reference_fn`` (same
    signature, flattened stream) asserts bit-equivalence — used to pin the
    segmented unit to the global one."""
    import jax

    masks = _masks(shape)
    idx, keep = jax.vmap(lambda ok: index_fn(ok, capacity))(masks)
    idx = np.asarray(idx).reshape(masks.shape[0], -1)
    keep = np.asarray(keep).reshape(masks.shape[0], -1).astype(bool)
    flat = masks.reshape(masks.shape[0], -1)
    diags = []

    def bad(check, msg, m):
        diags.append(Diagnostic(
            check, f"{path}/capacity[{capacity}]",
            f"{msg} (occupancy mask {flat[m].tolist()})"))

    for m in range(masks.shape[0]):
        if diags:
            break                            # first failing mask is enough
        if (idx[m] < 0).any() or (idx[m] > capacity).any():
            bad("kernel.scatter-bounds",
                f"scatter index outside [0, {capacity}]", m)
            continue
        kept = idx[m][keep[m]]
        if (kept >= capacity).any():
            bad("kernel.scatter-bounds",
                "kept event scattered into the overflow slot", m)
            continue
        if np.unique(kept).size != kept.size:
            bad("kernel.scatter-overlap",
                "two kept events write the same output slot — one "
                "destination's event overwrites a neighbour's", m)
            continue
        k = min(int(flat[m].sum()), capacity)
        if not np.array_equal(kept, np.arange(kept.size)):
            bad("kernel.scatter-order",
                "kept slots are not the dense arrival ranks 0..k-1 in "
                "stream order", m)
            continue
        if keep[m].sum() != k or bool((keep[m] & (flat[m] == 0)).any()):
            bad("kernel.scatter-conservation",
                f"kept {int(keep[m].sum())} of {int(flat[m].sum())} "
                f"offered events at capacity {capacity}", m)
            continue
        if reference_fn is not None:
            r_idx, r_keep = reference_fn(flat[m], capacity)
            if (not np.array_equal(np.asarray(r_idx), idx[m])
                    or not np.array_equal(np.asarray(r_keep).astype(bool),
                                          keep[m])):
                bad("kernel.pack-equivalence",
                    "segmented pack disagrees with the global pack on the "
                    "flattened stream", m)
    return diags


def check_pack_units(capacities, path: str = "spike_router"
                     ) -> list[Diagnostic]:
    """Model-check both pack units at each plan-derived capacity."""
    from repro.kernels.spike_router.spike_router import (
        _pack_indices, _pack_segmented_indices)

    diags = []
    for cap in sorted(set(capacities)):
        n = min(2 * cap, 16)
        diags += check_pack_writeset(
            _pack_indices, (n,), cap, f"{path}/_pack_indices")
        seg_shape = (4, max(2, min(cap, 8)))
        diags += check_pack_writeset(
            _pack_segmented_indices, seg_shape, cap,
            f"{path}/_pack_segmented_indices", reference_fn=_pack_indices)
        # exhaustive small shapes — every occupancy pattern
        diags += check_pack_writeset(
            _pack_indices, (8,), min(cap, 5), f"{path}/_pack_indices")
        diags += check_pack_writeset(
            _pack_segmented_indices, (2, 4), min(cap, 5),
            f"{path}/_pack_segmented_indices", reference_fn=_pack_indices)
    return diags


# ---------------------------------------------------------------------------
# Pallas grid tilings: output write windows per grid cell
# ---------------------------------------------------------------------------


def _block_size(dim) -> int:
    """Element extent of one block dim: an int, a ``Blocked`` size, or 1 for
    a squeezed dim (a vmapped grid axis)."""
    if isinstance(dim, (int, np.integer)):
        return int(dim)
    return int(getattr(dim, "block_size", 1))


def _block_windows(bm, grid, max_cells: int = 4096):
    """Yield (cell, start, shape) element windows of one block mapping."""
    from jax.extend import core as jex

    shape = tuple(_block_size(s) for s in bm.block_shape)
    cells = list(itertools.islice(np.ndindex(*grid), max_cells + 1))
    truncated = len(cells) > max_cells
    if truncated:
        cells = cells[:max_cells]
    index_map = jex.jaxpr_as_fun(bm.index_map_jaxpr)
    for cell in cells:
        out = index_map(*(np.int32(i) for i in cell))
        start = tuple(int(b) * s for b, s in zip(out, shape))
        yield cell, start, shape
    if truncated:
        yield None, None, None                # sentinel: enumeration capped


def _sequential_axes(eqn, gm) -> set[int]:
    """Grid axes declared ``"arbitrary"`` (sequential — a reduction axis
    whose output blocks may stay resident as accumulators).  Vmapped grid
    axes are parallel; without declared semantics no axis qualifies."""
    params = (eqn.params.get("compiler_params") or {}).get("mosaic_tpu")
    sem = getattr(params, "dimension_semantics", None)
    if sem is None:
        return set()
    user_axes = [a for a in range(len(gm.grid))
                 if a not in set(gm.vmapped_dims)]
    return {a for a, s in zip(user_axes, sem)
            if str(getattr(s, "value", s)).lower() == "arbitrary"}


def check_pallas_calls(fn, args, path: str) -> list[Diagnostic]:
    """Statically verify every ``pallas_call`` in ``fn``'s jaxpr: output
    write windows in-bounds, disjoint across grid cells, covering the
    output (warning), and donated aliases type-consistent."""
    import jax

    from repro.analysis.jaxprlint import iter_eqns

    closed = jax.make_jaxpr(fn)(*args)
    diags = []
    found = 0
    for eqn in iter_eqns(closed.jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        found += 1
        gm = eqn.params["grid_mapping"]
        grid = tuple(int(g) for g in gm.grid)
        mappings = list(gm.block_mappings)
        n_in, n_out = gm.num_inputs, gm.num_outputs
        sequential = _sequential_axes(eqn, gm)
        outs = mappings[n_in:n_in + n_out]
        for oi, bm in enumerate(outs):
            opath = f"{path}/pallas_call[{found - 1}]/out[{oi}]"
            arr_shape = tuple(bm.array_aval.shape)
            seen: dict[tuple, tuple] = {}
            windows = []
            prev_cell = prev = None
            for cell, start, shape in _block_windows(bm, grid):
                if cell is None:
                    diags.append(Diagnostic(
                        "kernel.grid-bounds", opath,
                        "grid too large to enumerate — write-set "
                        "unverified", WARNING))
                    break
                if start == prev and all(
                        a in sequential for a, (i, j)
                        in enumerate(zip(cell, prev_cell)) if i != j):
                    prev_cell = cell  # revisit along sequential axes only:
                    continue          # a resident accumulator block
                prev_cell, prev = cell, start
                if (any(s < 0 for s in start)
                        or any(s + b > a for s, b, a
                               in zip(start, shape, arr_shape))):
                    diags.append(Diagnostic(
                        "kernel.grid-bounds", opath,
                        f"grid cell {cell} writes window "
                        f"{start}+{shape} outside the {arr_shape} "
                        f"output"))
                    break
                if start in seen:
                    diags.append(Diagnostic(
                        "kernel.grid-overlap", opath,
                        f"grid cells {seen[start]} and {cell} write the "
                        f"same window {start}+{shape} — the later cell "
                        f"silently overwrites the earlier one"))
                    break
                misaligned = any(b and s % b for s, b in zip(start, shape))
                if misaligned and any(
                        _overlaps(start, shape, s2, shape)
                        for s2 in seen):
                    other = next(s2 for s2 in seen
                                 if _overlaps(start, shape, s2, shape))
                    diags.append(Diagnostic(
                        "kernel.grid-overlap", opath,
                        f"unaligned window {start}+{shape} of cell {cell} "
                        f"overlaps the window at {other}"))
                    break
                seen[start] = cell
                windows.append((start, shape))
            else:
                covered = sum(math.prod(s) for _, s in windows)
                total = math.prod(arr_shape)
                if covered < total:
                    diags.append(Diagnostic(
                        "kernel.grid-coverage", opath,
                        f"grid writes {covered} of {total} output "
                        f"elements — the rest stay uninitialized",
                        WARNING))
        aliases = eqn.params.get("input_output_aliases", ()) or ()
        for in_idx, out_idx in aliases:
            iv, ov = eqn.invars[in_idx], eqn.outvars[out_idx]
            if (iv.aval.shape != ov.aval.shape
                    or iv.aval.dtype != ov.aval.dtype):
                diags.append(Diagnostic(
                    "kernel.aliasing",
                    f"{path}/pallas_call[{found - 1}]",
                    f"donated alias in[{in_idx}]→out[{out_idx}] mismatches: "
                    f"{iv.aval.str_short()} vs {ov.aval.str_short()}"))
    if not found:
        diags.append(Diagnostic(
            "kernel.grid-bounds", path,
            "no pallas_call found in the traced program", WARNING))
    return diags


def _overlaps(a_start, a_shape, b_start, b_shape) -> bool:
    return all(sa < sb + db and sb < sa + da
               for sa, da, sb, db in zip(a_start, a_shape, b_start, b_shape))


def check_router_kernels(capacity: int = 8, path: str = "spike_router"
                         ) -> list[Diagnostic]:
    """Trace the shipped router kernels and verify their grid tilings.  The
    shapes span several row blocks and event tiles, so the sequential tile
    axis and its resident accumulator blocks are exercised."""
    import jax.numpy as jnp

    from repro.core.routing import FWD_TABLE_SIZE, REV_TABLE_SIZE
    from repro.kernels.spike_router import spike_router as sr

    n_src, n_dst, cap_in, n_steps = 3, 9, 100, 2
    labels = jnp.zeros((n_src, cap_in), jnp.int32)
    valid = jnp.zeros((n_src, cap_in), jnp.int32)
    fwd = jnp.zeros((n_src, FWD_TABLE_SIZE), jnp.int32)
    rev = jnp.zeros((n_dst, REV_TABLE_SIZE), jnp.int32)
    en = jnp.ones((n_src, n_dst), jnp.int32)
    diags = check_pallas_calls(
        lambda *a: sr.exchange_fwd(*a, capacity=capacity),
        (labels, valid, fwd, rev, en), f"{path}/exchange_fwd")
    s_labels = jnp.zeros((n_steps, n_src, cap_in), jnp.int32)
    s_valid = jnp.zeros((n_steps, n_src, cap_in), jnp.int32)
    diags += check_pallas_calls(
        lambda *a: sr.exchange_stream_fwd(*a, capacity=capacity),
        (s_labels, s_valid, fwd, rev, en), f"{path}/exchange_stream_fwd")
    m_labels = jnp.zeros((n_dst, 3 * cap_in), jnp.int32)
    m_valid = jnp.zeros((n_dst, 3 * cap_in), jnp.int32)
    diags += check_pallas_calls(
        lambda *a: sr.merge_pack_fwd(*a, capacity=capacity),
        (m_labels, m_valid, rev[0]), f"{path}/merge_pack_fwd")
    return diags
