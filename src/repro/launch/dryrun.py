import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this driver
  1. builds the production mesh (16×16 single-pod / 2×16×16 multi-pod),
  2. resolves parameter/batch/cache shardings from the logical-axis rules,
  3. ``jax.jit(step).lower(**ShapeDtypeStructs).compile()`` — no allocation,
  4. prints ``memory_analysis()`` (fits?) and ``cost_analysis()`` (FLOPs,
     bytes), parses the HLO for collective traffic, and
  5. appends the three-term roofline record to a JSON results file
     (resumable: completed cells are skipped on re-run).

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out results.json]
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import roofline as rl
from repro.configs import ARCH_NAMES, get_config
from repro.configs.base import ModelConfig
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import SHAPES, SHAPE_NAMES, cell_supported, input_specs
from repro.models import model as M
from repro.models.layers import Param, is_param
from repro.optim import adamw
from repro.parallel import sharding as shardlib

DEFAULT_OUT = "/root/repo/results/dryrun.json"


def _abstract_params(cfg: ModelConfig):
    """Param tree of ShapeDtypeStructs (init under eval_shape: no allocation)."""
    return jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))


def _data_axes(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def build_cell(cfg: ModelConfig, shape_name: str, mesh):
    """Returns (fn, kwargs_structs, in_shardings dict) for the cell's step."""
    spec = input_specs(cfg, shape_name)
    kind = spec["kind"]
    da = _data_axes(mesh)

    params_struct = _abstract_params(cfg)
    pshard = shardlib.param_shardings(params_struct, mesh)

    def batch_shardings(batch):
        return jax.tree.map(
            lambda x: shardlib.data_sharding_if_divisible(mesh, x.shape),
            batch)

    if kind == "train":
        opt_struct = jax.eval_shape(lambda p: adamw.init(p), params_struct)
        opt_cfg = adamw.AdamWConfig()

        def train_step(params, opt_state, batch):
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: M.train_loss(p, batch, cfg), has_aux=True)(params)
            new_params, new_opt, om = adamw.update(params, grads, opt_state,
                                                   opt_cfg)
            return new_params, new_opt, {**metrics, **om, "loss": loss}

        opt_shard = adamw.AdamWState(
            step=shardlib.replicated(mesh),
            m=jax.tree.map(lambda s: s, pshard, is_leaf=lambda x: isinstance(
                x, NamedSharding)),
            v=jax.tree.map(lambda s: s, pshard, is_leaf=lambda x: isinstance(
                x, NamedSharding)))
        args = (params_struct, opt_struct, spec["batch"])
        shardings = (pshard, opt_shard, batch_shardings(spec["batch"]))
        return train_step, args, shardings

    if kind == "prefill":
        def prefill_step(params, batch):
            logits, caches, _ = M.prefill(params, batch, cfg)
            return logits, caches

        args = (params_struct, spec["batch"])
        shardings = (pshard, batch_shardings(spec["batch"]))
        return prefill_step, args, shardings

    # decode.  The cache argument is donated: the dynamic-update-slice
    # writes in place instead of copying the multi-GB cache every token.
    def serve_step(params, tokens, caches, index, *extra):
        enc = extra[0] if extra else None
        logits, new_caches = M.decode_step(params, tokens, caches, index, cfg,
                                           encoder_out=enc)
        return logits, new_caches

    cache_shard = shardlib.cache_shardings(cfg, mesh, spec["caches"])
    args = [params_struct, spec["tokens"], spec["caches"], spec["index"]]
    shardings = [pshard,
                 shardlib.data_sharding_if_divisible(mesh,
                                                     spec["tokens"].shape),
                 cache_shard,
                 shardlib.replicated(mesh)]
    if "encoder_out" in spec:
        args.append(spec["encoder_out"])
        shardings.append(shardlib.data_sharding_if_divisible(
            mesh, spec["encoder_out"].shape))
    return serve_step, tuple(args), tuple(shardings)


def probe_configs(cfg: ModelConfig) -> tuple:
    """Shallow *unrolled* probe configs for per-layer cost extrapolation.

    XLA's cost_analysis counts while-loop (scan) bodies once, so the scanned
    full-depth program under-reports FLOPs.  Two unrolled shallow compiles
    give the per-repeating-unit slope: total = c1 + (U − u1)·(c2 − c1)/(u2 − u1).

    Returns (cfg1, u1, cfg2, u2, U_effective_units).
    """
    if cfg.attn_every:                       # zamba2: unit = group of layers
        per = cfg.attn_every
        c1 = dataclasses.replace(cfg, n_layers=2 * per, scan_layers=False)
        c2 = dataclasses.replace(cfg, n_layers=4 * per, scan_layers=False)
        return c1, 2, c2, 4, cfg.n_layers / per
    if cfg.encoder_layers:                   # whisper: unit = enc+dec pair
        c1 = dataclasses.replace(cfg, n_layers=2, encoder_layers=2,
                                 scan_layers=False)
        c2 = dataclasses.replace(cfg, n_layers=4, encoder_layers=4,
                                 scan_layers=False)
        return c1, 2, c2, 4, cfg.n_layers
    dense = cfg.first_dense_layers
    c1 = dataclasses.replace(cfg, n_layers=dense + 2, scan_layers=False)
    c2 = dataclasses.replace(cfg, n_layers=dense + 4, scan_layers=False)
    return c1, 2, c2, 4, cfg.n_layers - dense


def _cell_costs(cfg: ModelConfig, shape_name: str, mesh,
                donate_cache: bool = False) -> dict:
    """Compile one variant; return per-device flops/bytes/collective bytes."""
    from repro.analysis.hlo import total_collective_bytes

    fn, args, shardings = build_cell(cfg, shape_name, mesh)
    donate = (2,) if (donate_cache
                      and SHAPES[shape_name]["kind"] == "decode") else ()
    with mesh, shardlib.activation_shardings(mesh):
        compiled = jax.jit(fn, in_shardings=shardings,
                           donate_argnums=donate).lower(*args).compile()
    cost = compiled.cost_analysis()
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": float(total_collective_bytes(compiled.as_text()))}


def extrapolated_costs(cfg: ModelConfig, shape_name: str, mesh,
                       donate_cache: bool = False) -> dict:
    c1cfg, u1, c2cfg, u2, units = probe_configs(cfg)
    c1 = _cell_costs(c1cfg, shape_name, mesh, donate_cache)
    c2 = _cell_costs(c2cfg, shape_name, mesh, donate_cache)
    out = {}
    for k in ("flops", "bytes", "coll"):
        slope = (c2[k] - c1[k]) / (u2 - u1)
        out[k] = max(c1[k] + (units - u1) * slope, 0.0)
        out[f"{k}_slope_per_unit"] = slope
    out["probe_units"] = [u1, u2, units]
    return out


def _parse_overrides(pairs: list[str]) -> dict:
    """--set key=value pairs → typed config overrides."""
    out = {}
    for pair in pairs or []:
        key, _, val = pair.partition("=")
        for cast in (int, float):
            try:
                val = cast(val)
                break
            except ValueError:
                continue
        if val in ("True", "False"):
            val = val == "True"
        out[key] = val
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             probes: bool = True, overrides: dict | None = None,
             donate_cache: bool = False) -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    ok, reason = cell_supported(cfg, shape_name)
    mesh_desc = "2x16x16" if multi_pod else "16x16"
    cell_id = f"{arch}|{shape_name}|{mesh_desc}"
    if not ok:
        return {"cell": cell_id, "status": "skipped", "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()
    fn, args, shardings = build_cell(cfg, shape_name, mesh)
    donate = (2,) if (donate_cache
                      and SHAPES[shape_name]["kind"] == "decode") else ()

    with mesh, shardlib.activation_shardings(mesh):
        jitted = jax.jit(fn, in_shardings=shardings, donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    print(f"[{cell_id}] memory_analysis: {mem}")
    cost = compiled.cost_analysis()
    print(f"[{cell_id}] cost_analysis (scanned, loop bodies ×1): "
          f"flops={cost.get('flops', 0):.3e} "
          f"bytes={cost.get('bytes accessed', 0):.3e}")

    kind = SHAPES[shape_name]["kind"]
    hlo_text = compiled.as_text()
    roof = rl.analyze(compiled, arch=arch, shape_name=shape_name,
                      shape=SHAPES[shape_name], kind=kind,
                      mesh_desc=mesh_desc, chips=chips, cfg=cfg,
                      hlo_text=hlo_text)
    raw = {"flops": roof.hlo_flops, "bytes": roof.hlo_bytes,
           "coll": roof.coll_bytes}
    if probes:
        # Correct the scan under-count via unrolled shallow probes.
        ext = extrapolated_costs(cfg, shape_name, mesh, donate_cache)
        roof.hlo_flops = ext["flops"]
        roof.hlo_bytes = ext["bytes"]
        roof.coll_bytes = ext["coll"]
        roof.compute_s = ext["flops"] / rl.PEAK_FLOPS
        roof.memory_s = ext["bytes"] / rl.HBM_BW
        roof.collective_s = ext["coll"] / rl.ICI_BW
    from repro.analysis.hlo import collective_schedule
    sched = collective_schedule(hlo_text, limit=12)
    print(rl.format_row(roof))

    return {"cell": cell_id, "status": "ok", "arch": arch,
            "shape": shape_name, "mesh": mesh_desc, "kind": kind,
            "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
            "roofline": roof.to_dict(), "raw_scanned_costs": raw,
            "collective_schedule": sched}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--set", nargs="*", dest="overrides", default=[],
                    help="config overrides, e.g. --set attn_block_kv=512")
    ap.add_argument("--donate-cache", action="store_true",
                    help="donate decode caches (in-place DUS; §Perf)")
    args = ap.parse_args()
    overrides = _parse_overrides(args.overrides)

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    results = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    archs = ARCH_NAMES if (args.all or not args.arch) else [args.arch]
    shapes = SHAPE_NAMES if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = []
    for multi_pod in meshes:
        for arch in archs:
            for shape in shapes:
                mesh_desc = "2x16x16" if multi_pod else "16x16"
                cell_id = f"{arch}|{shape}|{mesh_desc}"
                if results.get(cell_id, {}).get("status") in ("ok", "skipped"):
                    print(f"[{cell_id}] cached, skipping")
                    continue
                print(f"=== {cell_id} ===", flush=True)
                try:
                    rec = run_cell(arch, shape, multi_pod,
                                   overrides=overrides,
                                   donate_cache=args.donate_cache)
                    if overrides:
                        rec["overrides"] = overrides
                    if args.donate_cache:
                        rec["donate_cache"] = True
                except Exception as e:  # noqa: BLE001 — record and continue
                    traceback.print_exc()
                    rec = {"cell": cell_id, "status": "failed",
                           "error": f"{type(e).__name__}: {e}"}
                    failures.append(cell_id)
                results[cell_id] = rec
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    n_ok = sum(1 for r in results.values() if r["status"] == "ok")
    n_skip = sum(1 for r in results.values() if r["status"] == "skipped")
    n_fail = sum(1 for r in results.values() if r["status"] == "failed")
    print(f"\ndone: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    if failures:
        print("failures:", failures)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
