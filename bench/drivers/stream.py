"""Driving path ``stream``: one user runs back-to-back jitted ``run_stream``
calls of ``steps_per_call`` steps at ``batch``, the state chained from call
to call, the drive of call ``k`` being block ``k % pool`` of the drive
blocks staged on the device at set-up.  ``STREAM_AHEAD`` calls stay queued
behind the running one.
"""

from __future__ import annotations

import collections
import time

import jax
import numpy as np

from bench.harness import check as checklib
from bench.harness import system as syslib
from bench.harness import traffic as trafficlib
from bench.harness.driving import Reservoir, span, state_dict

# Stream calls dispatched ahead of the one the host waits for.
STREAM_AHEAD = 2


class Cell:
    unit = "call"

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        if traffic["plastic"]:
            raise ValueError("the stream's comparison has no shared-weight "
                             "plasticity reference")
        self.cfg, self.traffic, self.seed = cfg, traffic, seed

    def setup(self) -> None:
        from repro.snn import network as netlib
        from repro.snn import stream as stlib

        tr = self.traffic
        self.system = syslib.build(self.cfg, self.seed)
        s = self.system
        pool = trafficlib.drive_pool(tr, self.cfg, self.seed)
        # One device array per block, split here: indexing the pool inside
        # the window would compile a slice per index.
        self.pool = jax.block_until_ready(list(pool))

        def stream_call(params, state, drives):
            return stlib.run_stream(params, state, drives, s.net_cfg,
                                    fabric=s.plan, timed=tr["timed"])

        self.fn = jax.jit(stream_call)
        self.program = "jit_stream_call"
        self.state0 = netlib.init_state(s.net_cfg, tr["batch"])
        jax.block_until_ready(self.fn(s.params, self.state0, self.pool[0]))

    def window(self, seconds: float) -> dict:
        params, pool = self.system.params, self.pool
        n_pool = len(pool)
        sample = Reservoir(self.traffic["check"]["calls"] - 1, self.seed)
        state, calls, pending = self.state0, 0, collections.deque()
        first = None
        # When each waited-for call was seen finished: with the queue kept
        # full, successive gaps are the device's time per call.
        done_at = []
        t_open = time.perf_counter()
        while True:
            with span("bench.stream.call"):
                out = self.fn(params, state, pool[calls % n_pool])
            item = (calls, None if calls == 0 else state, out)
            if calls == 0:
                first = item
            else:
                sample.offer(item)
            state, calls = out.state, calls + 1
            # Keep STREAM_AHEAD calls queued behind the running one, so that
            # a stall of the host shorter than that leaves the chip busy.
            pending.append(out)
            if len(pending) > STREAM_AHEAD:
                with span("bench.stream.wait"):
                    jax.block_until_ready(pending.popleft().spikes)
                done_at.append(time.perf_counter())
            if time.perf_counter() - t_open >= seconds:
                break
        with span("bench.stream.wait"):
            jax.block_until_ready(out)
        t_close = time.perf_counter()
        self._kept = [first] + sample.items
        steps = calls * self.traffic["steps_per_call"]
        notes = []
        if len(done_at) > 2:
            gap = np.diff(done_at) * 1e3
            q = np.percentile(gap, [0, 50, 99, 100])
            notes.append("stream call gaps ms: min {:.3f} median {:.3f} "
                         "p99 {:.3f} max {:.3f}".format(*q))
        return {"window_s": t_close - t_open, "completed": calls,
                "failed": 0, "program_calls": calls, "emulated_steps": steps,
                "live_row_steps": steps * self.traffic["batch"],
                "chip_steps": steps * self.system.n_chips
                * self.traffic["batch"], "notes": notes}

    def release(self) -> None:
        """Copy the sampled calls to the host and free the device state."""
        p = self.system.params.chips
        self._ref_arrays = (p.row_sign, p.w_scale, p.weights)
        n_pool = len(self.pool)
        host = []
        for c, state_in, out in self._kept:
            o = {f: np.asarray(getattr(out, f)) for f in (
                "spikes", "latency_ns", "latency_valid", *checklib.DROP_FIELDS)}
            o.update(state_dict(out.state))
            host.append((c, np.asarray(self.pool[c % n_pool]), o,
                         None if state_in is None else state_dict(state_in)))
        self._host = host
        self._kept = None
        self.system = self.pool = self.fn = self.state0 = None

    def check(self, control_mode=None) -> dict:
        checker = checklib.Checker(self.cfg, *self._ref_arrays,
                                   per_slot=False)
        results = [checker.stream_call(ext, o, state_in,
                                       timed=self.traffic["timed"],
                                       control_mode=control_mode)
                   for _, ext, o, state_in in self._host]
        out = checklib.merge(results)
        out["checked"] = len(results)
        return out
