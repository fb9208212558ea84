"""The comparison that decides ``correct``.

Each function takes what the timed path produced for one unit of work (a
finished engine session, or one jitted stream call) and runs the plain
reference over it: the fabric rounds event by event on the program's own
spikes, then the neurons, weights and plasticity teacher-forced by those
spikes.  It returns the numbers that are held against the limits:

* ``spike_gap``: the widest distance by which the reference membrane lies
  on the wrong side of the threshold for a spike the program emitted or
  withheld (membrane units; the threshold is 1);
* ``plasticity_gap`` (plastic sessions): the widest difference between the
  program's final traces and weights and the reference's, per leaf, as a
  share of that leaf's largest reference value;
* ``state_gap`` (chained streams): the widest difference of the chained
  neuron state handed to the next call;
* ``fabric_mismatches``: loss counters, timestamps (or a timed session's
  latency statistics), delay-line contents and step counts that differ
  (exact, limit 0);
* ``count_gap`` (sessions of an engine that keeps no spikes): the reference
  runs free on its own spikes; the widest difference of the session's spike
  count and loss counters, as a share of the reference's (at least 1).

With ``control_mode`` set, the same scan also carries the reference at that
lower precision, in the program's place, and reports its numbers under
``control_*``: the control's own threshold decisions measured on the
reference membrane, and its final state against the reference's.

The limits are the configuration's ``limits`` and, for numbers that only a
mix produces, the mix's own ``limits``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import reference as ref

DROP_FIELDS = ("dropped", "uplink_dropped", "unroutable", "rerouted")
LATENCY_STATS = ("median_ns", "p01_ns", "p99_ns", "jitter_ns", "jitter_frac")


class Checker:
    """The reference for one configuration, with its compiled scans.
    ``per_slot``: the program contracts per-slot weight copies (the plastic
    engine) rather than the shared weights."""

    def __init__(self, cfg: dict, row_sign, w_scale, weights, *,
                 per_slot: bool):
        self.cfg = cfg
        self.fabric = ref.FabricRef(cfg)
        self.mode = ref.contraction_mode(cfg, per_slot=per_slot)
        self.row_sign = jnp.asarray(row_sign)
        self.w_scale = jnp.asarray(w_scale)
        self.weights = jnp.asarray(weights)
        self._scans, self._steps = {}, {}

    def _scan(self, plastic: bool, control_mode):
        key = (plastic, control_mode)
        if key not in self._scans:
            self._scans[key] = jax.jit(functools.partial(
                ref.lif_scan, self.cfg, mode=self.mode, plastic=plastic,
                control_mode=control_mode))
        return self._scans[key]

    def _lif(self, state, drives, spikes, live, *, plastic, batch,
             control_mode):
        ctl = (None if control_mode is None else ref.init_lif(
            self.cfg, self.weights, batch, plastic=plastic, mode=control_mode))
        if ctl is not None and state is not None:
            ctl = ctl._replace(**{k: getattr(state, k).astype(ctl.v.dtype)
                                  for k in ("v", "i_syn", "w_adapt")},
                               refrac=state.refrac)
        if state is None:
            state = ref.init_lif(self.cfg, self.weights, batch,
                                 plastic=plastic)
        return self._scan(plastic, control_mode)(
            self.row_sign, self.w_scale, state, jnp.asarray(drives),
            jnp.asarray(spikes), jnp.asarray(live), control=ctl)

    # -- one engine session -------------------------------------------------

    def session(self, stim: np.ndarray, stim_chips, result, pad_to: int, *,
                plastic: bool, timed: bool, control_mode=None) -> dict:
        """``stim``: f32[T, n_stim, R] as submitted; ``result``: the
        engine's ``SessionResult`` (spikes f32[T, n, N] or None, loss
        totals, latency statistics when timed, final per-slot plasticity
        when plastic)."""
        T = stim.shape[0]
        n, R = self.fabric.n, self.fabric.n_rows
        ext = np.zeros((T, n, 1, R), np.float32)
        ext[:, list(stim_chips), 0] = stim
        if result.spikes is None:
            return self._free_session(ext, result, plastic=plastic,
                                      timed=timed, control_mode=control_mode)
        got = np.asarray(result.spikes) > 0.5                 # [T', n, N]
        mism = int(result.steps != T or got.shape[0] != T)
        # A session cut short or run long is compared over its own T steps.
        spikes = np.zeros((T,) + got.shape[1:], bool)
        spikes[:min(T, got.shape[0])] = got[:T]
        rounds = self.fabric.stream(spikes[:, None])          # B = 1
        totals = _round_totals(rounds, timed)
        mism += sum(int(getattr(result, f) != totals[f]) for f in DROP_FIELDS)
        if timed:
            mism += _latency_mismatches(result.latency, totals["latency"])
        d = self.fabric.wire.delay_steps
        drives = np.zeros((pad_to, n, 1, R), np.float32)
        drives[:T] = ext
        drives[d:T, :, 0] += rounds.drive[:T - d, 0]
        spk = np.zeros((pad_to, n, 1, spikes.shape[-1]), bool)
        spk[:T, :, 0] = spikes
        live = np.arange(pad_to) < T
        s, gap, c, cgap = self._lif(None, drives, spk, live, plastic=plastic,
                                    batch=1, control_mode=control_mode)
        out = {"spike_gap": float(gap), "fabric_mismatches": mism}
        if plastic:
            out["plasticity_gap"] = _leaf_gap(_program_plasticity(result),
                                              _plasticity(s))
        if control_mode is not None:
            out["control_spike_gap"] = float(cgap)
            if plastic:
                out["control_plasticity_gap"] = _leaf_gap(_plasticity(c),
                                                          _plasticity(s))
        return out

    def _free_step(self, plastic: bool, mode: str):
        key = (plastic, mode)
        if key not in self._steps:
            step = ref.free_step(self.cfg, mode, plastic)
            self._steps[key] = jax.jit(functools.partial(
                step, self.row_sign, self.w_scale))
        return self._steps[key]

    def _free_run(self, ext: np.ndarray, *, plastic: bool, mode: str,
                  timed: bool) -> dict:
        """The reference running on its own spikes through the session:
        spike count, loss totals, latency statistics, final plasticity."""
        T, n, B, R = ext.shape
        d = self.fabric.wire.delay_steps
        drives = np.concatenate([ext, np.zeros((d, n, B, R), np.float32)])
        state = ref.init_lif(self.cfg, self.weights, B, plastic=plastic,
                             mode=mode)
        step = self._free_step(plastic, mode)
        count, rounds = 0, []
        for t in range(T):
            state, spikes = step(state, jnp.asarray(drives[t]))
            spikes = np.asarray(spikes)                       # [n, B, N]
            count += int(spikes.sum())
            r = self.fabric.route(np.swapaxes(spikes, 0, 1))
            drives[t + d] += np.swapaxes(r.drive, 0, 1)
            rounds.append(r)
        rounds = ref.RoundOut(*(np.stack(f) for f in zip(*rounds)))
        out = _round_totals(rounds, timed)
        out["spike_count"] = count
        out["plasticity"] = _plasticity(state) if plastic else None
        return out

    def _free_session(self, ext, result, *, plastic, timed, control_mode):
        mine = self._free_run(ext, plastic=plastic, mode=self.mode,
                              timed=timed)
        prog = {"spike_count": result.spike_count,
                **{f: getattr(result, f) for f in DROP_FIELDS}}
        if timed:
            prog["latency"] = result.latency
        out = {"count_gap": _count_gap(prog, mine, timed),
               "fabric_mismatches": int(result.steps != ext.shape[0])}
        if plastic:
            out["plasticity_gap"] = _leaf_gap(_program_plasticity(result),
                                              mine["plasticity"])
        if control_mode is not None:
            ctl = self._free_run(ext, plastic=plastic, mode=control_mode,
                                 timed=timed)
            out["control_count_gap"] = _count_gap(ctl, mine, timed)
            if plastic:
                out["control_plasticity_gap"] = _leaf_gap(
                    ctl["plasticity"], mine["plasticity"])
        return out

    # -- one stream call ----------------------------------------------------

    def stream_call(self, ext: np.ndarray, out: dict, state_in: dict | None,
                    *, timed: bool, control_mode=None) -> dict:
        """``ext``: f32[T, n, B, R] drive block of the call; ``out``: host
        copies of its ``StreamOut`` (spikes, loss fields, latency planes,
        final state); ``state_in``: the chained state the call started from
        (None = the fresh state the stream opened with)."""
        T, n, B, R = ext.shape
        spikes = out["spikes"] > 0.5                          # [T, n, B, N]
        rounds = self.fabric.stream(np.swapaxes(spikes, 1, 2))
        tr = lambda a: np.swapaxes(a, 1, 2)                   # [T, B, n] ->
        mism = int((tr(rounds.dropped) != out["dropped"]).sum())
        mism += int((tr(rounds.uplink) != out["uplink_dropped"]).sum())
        mism += int((out["unroutable"] != 0).sum())
        mism += int((out["rerouted"] != 0).sum())
        if timed:
            valid = tr(rounds.valid)
            mism += int((valid != out["latency_valid"]).sum())
            mism += int((np.where(valid, tr(rounds.latency), 0)
                         != out["latency_ns"]).sum())
        d = self.fabric.wire.delay_steps
        delivered = tr(rounds.drive)                          # [T, n, B, R]
        inflight0 = (np.zeros((d, n, B, R), np.float32) if state_in is None
                     else state_in["inflight"])
        drives = ext.copy()
        drives[:d] += inflight0[:min(d, T)]
        drives[d:] += delivered[:T - d]
        mism += int((delivered[T - d:] != out["inflight"]).sum())
        start = None
        if state_in is not None:
            start = ref.init_lif(self.cfg, self.weights, B, plastic=False)
            start = start._replace(**{k: jnp.asarray(state_in[k])
                                      for k in ("v", "i_syn", "w_adapt",
                                                "refrac")})
        s, gap, c, cgap = self._lif(start, drives, spikes, np.ones(T, bool),
                                    plastic=False, batch=B,
                                    control_mode=control_mode)
        state_gap = max(float(np.abs(np.asarray(getattr(s, k), np.float32)
                                     - out[k]).max())
                        for k in ("v", "i_syn", "w_adapt", "refrac"))
        res = {"spike_gap": float(gap), "state_gap": state_gap,
               "fabric_mismatches": mism}
        if control_mode is not None:
            res["control_spike_gap"] = float(cgap)
            res["control_state_gap"] = max(
                float(np.abs(np.asarray(getattr(c, k), np.float32)
                             - np.asarray(getattr(s, k), np.float32)).max())
                for k in ("v", "i_syn"))
        return res


def _plasticity(s) -> list:
    return [np.asarray(a.astype(jnp.float32))[:, 0]
            for a in (s.trace_pre, s.trace_post, s.weights)]


def _program_plasticity(result) -> list:
    return [np.asarray(a) for a in result.plasticity]


def _round_totals(rounds, timed: bool) -> dict:
    """A session's loss totals (and latency statistics) from its rounds."""
    out = {"dropped": int(rounds.dropped.sum()),
           "uplink_dropped": int(rounds.uplink.sum()),
           "unroutable": 0, "rerouted": 0}
    if timed:
        out["latency"] = latency_stats(rounds.latency[rounds.valid])
    return out


def latency_stats(samples: np.ndarray) -> dict:
    """The statistics of a timed session's delivered latencies (ns)."""
    x = np.asarray(samples, np.float64)
    if x.size == 0:
        return {"count": 0}
    p01, med, p99 = np.percentile(x, [1.0, 50.0, 99.0])
    return {"count": int(x.size), "median_ns": med, "p01_ns": p01,
            "p99_ns": p99, "jitter_ns": p99 - p01,
            "jitter_frac": (p99 - p01) / med}


def _latency_mismatches(got: dict | None, want: dict) -> int:
    """Statistics that differ: the count exactly, each percentile by more
    than float32 interpolation can (1e-5 of its size, at least 1e-3 ns)."""
    if got is None or int(got["count"]) != want["count"]:
        return 1
    if not want["count"]:
        return 0
    return sum(int(not abs(float(got[k]) - want[k])
                   <= max(1e-3, 1e-5 * abs(want[k]))) for k in LATENCY_STATS)


def _count_gap(got: dict, want: dict, timed: bool) -> float:
    keys = ["spike_count", *DROP_FIELDS]
    pairs = [(got[k], want[k]) for k in keys]
    if timed:
        pairs.append((got["latency"]["count"], want["latency"]["count"]))
    return max(abs(float(a) - float(b)) / max(abs(float(b)), 1.0)
               for a, b in pairs)


def _leaf_gap(got, want) -> float:
    """Widest per-leaf difference as a share of the leaf's largest value."""
    return max(float(np.abs(g - w).max() / max(np.abs(w).max(), 1e-30))
               for g, w in zip(got, want))


def limits(cfg: dict, traffic: dict) -> dict:
    """The limit of every number a cell compares: the configuration's, and
    the mix's own for numbers that only it produces."""
    return {**cfg["limits"], **traffic.get("limits", {})}


def verdict(readings: dict, limits: dict, prefix: str = ""):
    """The numbers compared, each beside its limit, and whether every one
    holds.  ``prefix="control_"`` judges the control's readings.  A number
    that has no limit is an error of the configuration, not a pass."""
    names = [k[len(prefix):] for k in readings
             if k != "checked" and k.startswith(prefix)
             and (prefix or not k.startswith("control_"))]
    missing = [k for k in names if k not in limits]
    if missing:
        raise KeyError(f"no limit for {missing}: add it to the "
                       "configuration's or the mix's limits")
    checks = {k: {"value": readings[prefix + k], "limit": limits[k]}
              for k in names}
    held = (bool(checks) and readings.get("checked", 0) > 0
            and all(c["value"] <= c["limit"] for c in checks.values()))
    return checks, held


def merge(results: list[dict]) -> dict:
    """Worst reading of each number over the checked units."""
    out = {}
    for r in results:
        for k, v in r.items():
            out[k] = max(out.get(k, v), v)
    return out
