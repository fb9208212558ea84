"""Driving path ``engine``: tenants run sessions through ``EmulationEngine``
``submit`` / ``step`` / ``collect``.

The mix's ``arrivals`` set the load: a closed loop of clients, each
submitting its next session when it has collected the last, or an open
loop whose sessions arrive on a schedule drawn from the seed and wait in
the engine's queue while every slot is taken.  The mix's ``slots``,
``window``, ``plastic``, ``timed`` and ``keep_spikes`` configure the engine,
and the comparison follows them.  Time-to-result runs from the moment a
session is submitted (closed loop) or due (open loop) to the collect of
its result.
"""

from __future__ import annotations

import time

import numpy as np

from bench.harness import check as checklib
from bench.harness import system as syslib
from bench.harness import traffic as trafficlib
from bench.harness.driving import WARM_INDEX, Reservoir, span


class Cell:
    unit = "session"

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.lengths = trafficlib.session_lengths(traffic, seed)
        self.max_steps = trafficlib.max_session_steps(traffic)
        self.stim_chips = trafficlib.stimulus_chips(traffic, cfg)
        self.closed = traffic["arrivals"]["kind"] == "closed"
        if self.closed and traffic["arrivals"]["clients"] > traffic["slots"]:
            raise ValueError("a closed loop with more clients than slots "
                             "queues sessions; make it an open loop")

    def _stim(self, index: int):
        steps = int(self.lengths[index % len(self.lengths)])
        return trafficlib.session_stimulus(self.traffic, self.cfg, self.seed,
                                           index, steps)

    def setup(self) -> None:
        from repro.runtime.engine import EmulationEngine

        tr = self.traffic
        self.system = syslib.build(self.cfg, self.seed)
        s = self.system
        self.engine = EmulationEngine(
            s.params, s.net_cfg, slots=tr["slots"], max_steps=self.max_steps,
            plan=s.plan, window=tr["window"], stim_chips=self.stim_chips,
            timed=tr["timed"], plasticity=s.stdp if tr["plastic"] else None,
            keep_spikes=tr["keep_spikes"])
        # The window program's HLO module, as the trace names it.
        self.program = "jit_" + self.engine.window_fn.__name__
        # One throw-away session of one window: compiles the window program
        # (one shape for every session length) and the row extraction the
        # window's collects use.
        warm = trafficlib.session_stimulus(tr, self.cfg, self.seed,
                                           WARM_INDEX, tr["window"])
        sid = self.engine.submit(warm)
        self.engine.drain()
        self.engine.collect(sid)

    def window(self, seconds: float) -> dict:
        eng, tr = self.engine, self.traffic
        w = tr["window"]
        sample = Reservoir(tr["check"]["sessions"], self.seed)
        longest = None
        # Uncollected sessions in submission order, [sid, steps left]: the
        # engine admits in that order, so the first `eng.active` of them
        # hold the slots.
        pending, submitted, ttr, step_s = [], {}, [], []
        index, failed, live_steps, steps = 0, 0, 0, 0

        def submit(t_start):
            nonlocal index
            stim = self._stim(index)
            with span("bench.engine.submit"):
                sid = eng.submit(stim)
            submitted[sid] = (index, t_start)
            pending.append([sid, stim.shape[0]])
            index += 1

        t_open = time.perf_counter()
        if self.closed:
            for _ in range(tr["arrivals"]["clients"]):
                submit(time.perf_counter())
        else:
            gaps = trafficlib.arrival_gaps(tr, self.seed)
            n_due, due = 0, 0.0
        while time.perf_counter() - t_open < seconds:
            if not self.closed:
                now = time.perf_counter() - t_open
                while due <= now:
                    submit(t_open + due)
                    due += gaps[n_due % len(gaps)]
                    n_due += 1
                if not eng.active and not eng.queued:
                    time.sleep(max(0.0, min(due, seconds) - now))
                    continue
            for item in pending[:eng.active]:
                live_steps += min(w, item[1])
                item[1] = max(0, item[1] - w)
            t_step = time.perf_counter()
            with span("bench.engine.step"):
                eng.step()
            step_s.append(time.perf_counter() - t_step)
            steps += 1
            for sid in eng.done:
                with span("bench.engine.collect"):
                    res = eng.collect(sid)
                t = time.perf_counter()
                k, t_sub = submitted.pop(sid)
                pending[:] = [p for p in pending if p[0] != sid]
                ttr.append(t - t_sub)
                failed += res.steps != self.lengths[k % len(self.lengths)]
                item = (k, res)
                sample.offer(item)
                if longest is None or res.steps > longest[1].steps:
                    longest = item
                if self.closed:
                    submit(time.perf_counter())
        t_close = time.perf_counter()
        kept = {k: r for k, r in sample.items}
        if longest is not None:
            kept.setdefault(*longest)
        self._kept = kept
        notes = [f"sessions submitted {index}, completed {len(ttr)}, "
                 f"left running or queued {len(pending)}"]
        if step_s:
            # Host wall time of each step() call: a slow stretch of the host
            # shows as a shift of the upper quantiles.
            q = np.percentile(np.asarray(step_s) * 1e3, [0, 25, 50, 75, 100])
            notes.append("engine step ms: min {:.1f} q1 {:.1f} median {:.1f} "
                         "q3 {:.1f} max {:.1f}".format(*q))
        return {"window_s": t_close - t_open, "completed": len(ttr),
                "failed": int(failed), "ttr_s": ttr, "program_calls": steps,
                "emulated_steps": steps * w, "live_row_steps": live_steps,
                "notes": notes}

    def release(self) -> None:
        """Free the program's device state before the reference runs."""
        p = self.system.params.chips
        self._ref_arrays = (p.row_sign, p.w_scale, p.weights)
        del self.engine
        self.system = None

    def check(self, control_mode=None) -> dict:
        tr = self.traffic
        checker = checklib.Checker(self.cfg, *self._ref_arrays,
                                   per_slot=tr["plastic"])
        results = [checker.session(self._stim(k), self.stim_chips, res,
                                   self.max_steps, plastic=tr["plastic"],
                                   timed=tr["timed"],
                                   control_mode=control_mode)
                   for k, res in sorted(self._kept.items())]
        out = checklib.merge(results)
        out["checked"] = len(results)
        return out
