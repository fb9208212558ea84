"""Three-term roofline from a compiled dry-run artifact (TPU v5e targets).

    compute term    = HLO_FLOPs_per_device / 197 TFLOP/s
    memory term     = HLO_bytes_per_device / 819 GB/s
    collective term = collective_bytes_per_device / 50 GB/s ICI

Under SPMD, ``compiled.cost_analysis()`` and the optimized HLO describe the
*per-device* partitioned program (verified against a known sharded matmul),
so each term divides by single-chip peak only.  These equal the global-sum
formulation HLO_total/(chips × peak) exactly when work is evenly sharded —
and when it is not, the per-device view is the correct (slowest-rank) one.
MODEL_FLOPS uses the 6·N·D rule (2·N·D per token forward-only), so the
useful-compute ratio exposes remat/dispatch/replication overheads.
"""

from __future__ import annotations

import dataclasses

from repro.analysis import hlo as hlolib
from repro.configs.base import ModelConfig

PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    coll_bytes: float
    coll_detail: dict
    model_flops: float
    compute_s: float
    memory_s: float
    collective_s: float
    bytes_per_device: dict

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / global HLO FLOPs (remat/redundancy waste)."""
        total = self.hlo_flops * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Ideal model-math time at peak / bound time — the score."""
        ideal = self.model_flops / (self.chips * PEAK_FLOPS)
        return ideal / self.bound_s if self.bound_s else 0.0

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d.update(dominant=self.dominant, useful_ratio=self.useful_ratio,
                 roofline_fraction=self.roofline_fraction,
                 bound_s=self.bound_s)
        return d


def model_flops(cfg: ModelConfig, shape: dict, kind: str) -> float:
    """6·N_active·D for training, 2·N_active·D for forward-only serving."""
    n = cfg.params_per_token_active()
    if kind == "train":
        tokens = shape["global_batch"] * shape["seq_len"]
        return 6.0 * n * tokens
    if kind == "prefill":
        tokens = shape["global_batch"] * shape["seq_len"]
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape["global_batch"]


def analyze(compiled, *, arch: str, shape_name: str, shape: dict, kind: str,
            mesh_desc: str, chips: int, cfg: ModelConfig,
            hlo_text: str | None = None) -> Roofline:
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = hlolib.collective_bytes(text)
    coll_total = sum(v for k, v in coll.items() if not k.startswith("_"))

    mem = compiled.memory_analysis()
    bytes_per_device = {
        "arguments": int(mem.argument_size_in_bytes),
        "outputs": int(mem.output_size_in_bytes),
        "temps": int(mem.temp_size_in_bytes),
        "aliased": int(mem.alias_size_in_bytes),
        "total_live": int(mem.argument_size_in_bytes
                          + mem.output_size_in_bytes
                          + mem.temp_size_in_bytes
                          - mem.alias_size_in_bytes),
    }

    mflops = model_flops(cfg, shape, kind)
    return Roofline(
        arch=arch, shape=shape_name, mesh=mesh_desc, chips=chips,
        hlo_flops=flops, hlo_bytes=nbytes, coll_bytes=float(coll_total),
        coll_detail=coll, model_flops=mflops,
        # cost_analysis/HLO are per-device → divide by single-chip peaks.
        compute_s=flops / PEAK_FLOPS,
        memory_s=nbytes / HBM_BW,
        collective_s=coll_total / ICI_BW,
        bytes_per_device=bytes_per_device,
    )


def format_row(r: Roofline) -> str:
    return (f"{r.arch:24s} {r.shape:12s} {r.mesh:10s} "
            f"compute={r.compute_s*1e3:9.2f}ms mem={r.memory_s*1e3:9.2f}ms "
            f"coll={r.collective_s*1e3:9.2f}ms dom={r.dominant:10s} "
            f"useful={r.useful_ratio:5.2f} roofline={r.roofline_fraction:5.2%}")
