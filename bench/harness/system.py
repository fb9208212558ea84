"""The system under test, built from a configuration file.

The fabric plan comes from the configuration's own numbers through the
program's public topology API (``LevelSpec`` / ``FabricSpec`` /
``compile_fabric``); the network parameters, the routing tables and the row
map are made here, on the device, from the seed in one jitted call.  The
plain reference (``reference.py``) reads the same configuration and the
same bench-made arrays, never anything the program built.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class System:
    cfg: dict                 # the configuration file
    net_cfg: object           # repro.snn.network.NetworkConfig
    plan: object              # repro.core.fabric.FabricPlan
    params: object            # repro.snn.network.NetworkParams
    stdp: object              # repro.snn.plasticity.STDPConfig

    @property
    def n_chips(self) -> int:
        return self.net_cfg.n_chips


def jax_seed(seed: int) -> int:
    """A 31-bit PRNG seed drawn from any non-negative whole number."""
    return int(np.random.default_rng(seed).integers(0, 2**31 - 1))


def wire_label_block(cfg: dict) -> int:
    """Wire labels each chip owns: one per synapse row its events drive.
    Chip ``c`` exports neuron ``j`` as wire label ``c * block + j % block``;
    the blocks of all chips have to fit the wire label space."""
    n = math.prod(cfg["fabric"]["fan_ins"])
    block = cfg["chip"]["n_rows"]
    if n * block > 1 << cfg["labels"]["wire_label_bits"]:
        raise ValueError(f"{n} chips x {block} wire labels do not fit "
                         f"{cfg['labels']['wire_label_bits']} bits")
    return block


def make_arrays(cfg: dict, seed: int):
    """Weights, row signs, scales, row map and routing tables for every
    chip, on the device, in one jitted call."""
    n = math.prod(cfg["fabric"]["fan_ins"])
    ch, lab = cfg["chip"], cfg["labels"]
    R, N = ch["n_rows"], ch["n_neurons"]
    nb, wb = lab["neuron_bits"], lab["wire_label_bits"]
    block = wire_label_block(cfg)
    wmax = (1 << ch["weight_bits"]) - 1
    scale = ch["w_scale_numerator"] / (wmax * math.sqrt(R))

    @jax.jit
    def build(key):
        k_w, k_s = jax.random.split(key)
        weights = jax.random.uniform(k_w, (n, R, N), jnp.float32, 0.0,
                                     ch["weight_init_max"])
        sign = jnp.where(jax.random.uniform(k_s, (n, R))
                         < 1.0 - ch["inhibitory_fraction"], 1.0, -1.0)
        w_scale = jnp.full((n,), scale, jnp.float32)
        # Feed-forward row map: chip d takes neuron j of chip d-1 on row
        # j mod R; every other label has no row (-1).
        label = jnp.arange(1 << 16, dtype=jnp.int32)
        src, j = label >> nb, label & ((1 << nb) - 1)
        dst = jnp.arange(n, dtype=jnp.int32)[:, None]
        row_of_label = jnp.where((src[None] == dst - 1) & (j[None] < N),
                                 j[None] % R, -1).astype(jnp.int32)
        # Forward LUT of chip c: its own labels c << nb | j onto its block
        # of wire labels, enable bit set; every other label disabled.
        fwd = jnp.where((src[None] == dst) & (j[None] < N),
                        (dst * block + j[None] % block) | (1 << wb), 0)
        # Reverse LUT, the same on every chip: wire label c * block + r back
        # to the chip label c << nb | r; labels past the last block disabled.
        wire = jnp.arange(1 << wb, dtype=jnp.int32)
        rev = jnp.where(wire < n * block,
                        ((wire // block) << nb | wire % block) | (1 << 16), 0)
        return (weights, sign, w_scale, row_of_label, fwd.astype(jnp.int32),
                jnp.broadcast_to(rev, (n, 1 << wb)).astype(jnp.int32))

    return build(jax.random.PRNGKey(jax_seed(seed)))


def build(cfg: dict, seed: int) -> System:
    from repro.core import fabric as fablib
    from repro.core.aggregator import RouterState
    from repro.core.latency import LatencyParams
    from repro.core.link import Encoding, LinkConfig
    from repro.snn import chip as chiplib
    from repro.snn import network as netlib
    from repro.snn import neuron as nrn
    from repro.snn.plasticity import STDPConfig

    fab, lat = cfg["fabric"], cfg["latency"]
    fan_ins = tuple(fab["fan_ins"])
    levels = tuple(
        fablib.LevelSpec(fan_in=f, link_capacity=c,
                         extension=bool(fab["extension_top"])
                         and i == len(fan_ins) - 1)
        for i, (f, c) in enumerate(zip(fan_ins, fab["link_capacities"])))
    plan = fablib.compile_fabric(fablib.FabricSpec(levels=levels,
                                                   capacity=fab["capacity"],
                                                   name=cfg["name"]))
    enc = Encoding("config", data_bits=lat["mgt_data_bits"],
                   code_bits=lat["mgt_code_bits"],
                   max_line_rate_gbps=lat["mgt_line_rate_gbps"])
    link = LinkConfig(encoding=enc, line_rate_gbps=lat["mgt_line_rate_gbps"],
                      fixed_latency_ns=lat["mgt_fixed_latency_ns"])
    latency = LatencyParams(
        link=link, l2_link_ns=lat["l2_link_ns"], on_chip_ns=lat["on_chip_ns"],
        cdc_ns_per_fpga=lat["cdc_ns_per_fpga"],
        pack_lut_ns=lat["pack_lut_ns"], mux_arb_ns=lat["mux_arb_ns"],
        n_fpgas=lat["n_fpgas"], cc_interval=lat["cc_interval"],
        cc_stall_ns=lat["cc_stall_ns"])
    neuron = nrn.NeuronParams(**cfg["neuron"])
    chip = chiplib.ChipConfig(n_neurons=cfg["chip"]["n_neurons"],
                              n_rows=cfg["chip"]["n_rows"], neuron=neuron)
    net_cfg = netlib.NetworkConfig(n_chips=math.prod(fan_ins), chip=chip,
                                   capacity=fab["capacity"],
                                   dt_us=lat["dt_us"], latency=latency)
    weights, sign, w_scale, row_of_label, fwd, rev = make_arrays(cfg, seed)
    n = net_cfg.n_chips
    router = RouterState(fwd_tables=fwd, rev_tables=rev,
                         route_enables=~jnp.eye(n, dtype=jnp.bool_))
    params = netlib.NetworkParams(
        chips=chiplib.ChipParams(weights=weights, row_sign=sign,
                                 w_scale=w_scale),
        row_of_label=row_of_label, router=router)
    stdp = STDPConfig(**cfg["stdp"]) if "stdp" in cfg else None
    return System(cfg=cfg, net_cfg=net_cfg, plan=plan, params=params,
                  stdp=stdp)
