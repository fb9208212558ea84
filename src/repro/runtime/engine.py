"""Emulation-as-a-service: the batched multi-tenant streaming engine.

The paper's multi-chip system is shared silicon driven by experiment-control
FPGAs: many independent experiments ride one physical fabric, and throughput
is experiments completed, not steps of one run.  ``EmulationEngine`` is the
software twin — S concurrent tenant *sessions* run as rows of the existing
batch axis of ONE compiled ``snn.stream.run_stream`` window program over a
shared ``FabricPlan``:

* ``submit()`` places a tenant's stimulus into a free slot's row of the
  host-side stimulus buffer; the slot's state reset rides along inside the
  next ``step()`` as a traced per-slot reset mask, so admitting a fresh
  session costs no device work at all (in particular no per-row copy of
  the full batched state — at S slots that would be O(S^2) traffic per
  drain).  A checkpoint-restored row (``runtime.elastic``) is the one case
  with real per-row payload and is inserted with ``dynamic_update_slice``
  at a traced slot index — neither path ever recompiles;
* ``step()`` advances every occupied slot one window through the fabric
  (composable with ``timed=`` / ``overlap=`` / ``plasticity=`` / routed
  exchange plans) — idle slots and finished sessions' tail steps are
  masked (``run_stream(slot_mask=...)``) so they emit no events, cost no
  drop accounting and freeze their plasticity rows;
* ``collect()`` returns a finished session's spikes plus per-tenant
  accounting (spike counts, all four drop fields, and in a timed engine
  the latency percentiles, summarised per session on the host by
  ``snn.stream.masked_latency_stats`` in float64 with no device program)
  and frees the slot;
* ``evict()`` checkpoints the tenant's row (ROADMAP: "evict = checkpoint a
  tenant's row") — resubmitting with ``restore_from=`` resumes bit-exactly.

Sessions are structurally isolated: the exchange is vmapped over the batch
axis, so slot b's events can never reach slot b'.  Per-slot online
plasticity (``plasticity=STDPConfig(...)``) gives every session its own
evolving weight copy (``SlotPlasticityState``) — the shared-array stream
state would batch-mean tenants into each other — and is bit-exact with S
independent batch-1 runs (the engine benchmark's hard parity gate).

A FIFO request queue with admission-on-free-slot (continuous-batching
style, after MaxText's prefill/insert/generate engine) sits on top; the CLI
demo is ``launch/serve_emulation.py`` and the throughput is measured by the
benchmark cells ``ext4case_96chip.engine_plastic`` and, timed,
``backplane_12chip.engine_timed_plastic`` (``bench/run.py``).

The host loop records its work as ``jax.profiler.TraceAnnotation`` spans,
with counters as their arguments, which a profiler trace records on the
clock of the device's ops: ``engine.step`` (``live`` slot-steps that advanced a
session, ``slot_steps``, ``finished``), ``engine.upload`` (``bytes``),
``engine.wait``, ``engine.readback`` (``bytes``, ``what``),
``engine.account``, ``engine.finalize`` (``session``), ``engine.latency``
(``samples``: a timed session's delivered events, inside its finalize) and
``engine.admit`` (``session``, ``queued_ms``).  The window program's own
work outside ``run_stream`` runs under the named scope ``slots``.  With no
profiler running a span costs about a microsecond.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime import elastic
from repro.snn import network as netlib
from repro.snn import plasticity as plaslib
from repro.snn import stream as stlib


@dataclasses.dataclass
class SessionResult:
    """Per-tenant accounting of one finished (or evicted) session."""

    session_id: int
    steps: int                     # emulated steps delivered to the tenant
    spikes: np.ndarray | None      # f32[steps, n_chips, n_neurons]
    #                                (None in accounting-only engines)
    spike_count: int
    dropped: int                   # egress + congestion drops (summed)
    uplink_dropped: int            # compact-before-gather uplink overflow
    unroutable: int                # lost to dead edges, no surviving route
    rerouted: int                  # delivered over extension-lane detours
    latency: dict[str, float] | None   # masked per-slot percentile stats
    #                                (incl. ``count``; None when untimed)
    plasticity: Any | None         # final per-session plasticity row
    #                                (traces + evolved weights, batch axis
    #                                squeezed; None when non-plastic)
    submitted_at: float
    finished_at: float
    evicted_to: str | None = None  # checkpoint directory when evicted

    @property
    def time_to_result_s(self) -> float:
        return self.finished_at - self.submitted_at


@dataclasses.dataclass
class _Session:
    """Host-side accumulator for one occupied slot."""

    sid: int
    length: int
    submitted_at: float
    delivered: int = 0                 # steps accounted so far
    spike_windows: list = dataclasses.field(default_factory=list)
    spike_count: float = 0.0
    drops: dict = dataclasses.field(default_factory=lambda: {
        "dropped": 0, "uplink_dropped": 0, "unroutable": 0, "rerouted": 0})
    lat_samples: list = dataclasses.field(default_factory=list)


class EmulationEngine:
    """S sessions as batch rows of one compiled window program.

    Args:
      params / cfg: the shared network (every tenant runs the same compiled
        topology — the whole point: one program, many experiments).
      slots: number of concurrent sessions S (the batch axis size).
      max_steps: stimulus-buffer length per slot (longest admissible
        session).
      plan: a compiled ``FabricPlan`` (or None for the default star).
      window: steps advanced per ``step()`` call — the scheduling quantum;
        insert/evict/collect happen at window boundaries.
      stim_chips: which chips a tenant's stimulus drives (the stimulus
        buffer only stores these rows — a 96-chip fabric with chip-0
        stimulus does not buffer 96x the payload).
      timed / overlap / use_fused: forwarded to ``run_stream``.
      plasticity: an ``STDPConfig`` switches on *per-slot* online
        plasticity (``SlotPlasticityState``).  Note the per-slot weight
        copies cost S times the shared array — size the chip config
        accordingly at large S.
      keep_spikes: when False, the window program returns per-slot reduced
        accounting only (spike counts + drop sums) instead of the full
        spike rasters — the high-throughput mode for large S.
    """

    def __init__(self, params: netlib.NetworkParams,
                 cfg: netlib.NetworkConfig, *, slots: int, max_steps: int,
                 plan=None, window: int = 8,
                 stim_chips: Sequence[int] = (0,),
                 timed: bool = False, overlap: bool = False,
                 use_fused: bool | None = None,
                 plasticity=None, keep_spikes: bool = True):
        if window < 1 or max_steps < window:
            raise ValueError("need window >= 1 and max_steps >= window")
        self.params, self.cfg, self.plan = params, cfg, plan
        self.slots, self.window = slots, window
        self.max_steps = max_steps
        self.stim_chips = tuple(stim_chips)
        self.timed, self.plasticity = timed, plasticity
        self.keep_spikes = keep_spikes

        self._state = netlib.init_state(cfg, slots)
        self._plast = (netlib.init_slot_plasticity(params, slots)
                       if plasticity is not None else None)
        n_stim = len(self.stim_chips)
        # Host-side: admissions mutate one row in place (free) and the whole
        # buffer rides into the jitted step — a few MB per call, vs. a
        # device-side update-slice per admission.  Pad by one window so the
        # final partial window's dynamic slice never clamps (masked anyway,
        # but clamping would skew the slice).
        self._stim = np.zeros((slots, max_steps + window, n_stim,
                               cfg.chip.n_rows), np.float32)
        # Slots admitted fresh since the last step(): their state reset to
        # the init row happens inside the next window program call.
        self._pending_reset = np.zeros((slots,), bool)
        self._cursor = np.zeros((slots,), np.int32)
        self._length = np.zeros((slots,), np.int32)
        self._sessions: list[_Session | None] = [None] * slots
        self._queue: deque = deque()
        self._results: dict[int, SessionResult] = {}
        self._next_sid = 0
        self._fingerprint = elastic.stream_fingerprint(
            cfg, fabric=plan, plasticity=plasticity)
        self._row_like = netlib.init_state(cfg, 1)
        self._row_plast_like = (netlib.init_slot_plasticity(params, 1)
                                if plasticity is not None else None)
        stim_idx = np.asarray(self.stim_chips, np.int32)

        def _row_select(sel, axis):
            # where(sel-along-`axis`, fresh, current) for one state leaf.
            def pick(fresh, cur):
                shape = [1] * cur.ndim
                shape[axis] = slots
                return jnp.where(sel.reshape(shape), fresh, cur)
            return pick

        def _step(params, state, plast, stim, cursor, mask, reset):
            with jax.named_scope("slots"):
                # Freshly admitted slots start from the init row; folding
                # the reset in here (one select over the state) keeps
                # admission O(state) per window instead of O(state) per
                # admitted session.
                init = netlib.init_state(cfg, slots)
                state = netlib.NetworkState(
                    chips=jax.tree.map(_row_select(reset, 1), init.chips,
                                       state.chips),
                    inflight=_row_select(reset, 2)(init.inflight,
                                                   state.inflight))
                if plast is not None:
                    plast = jax.tree.map(
                        _row_select(reset, 1),
                        netlib.init_slot_plasticity(params, slots), plast)
                # Per-slot window slice of the stimulus buffer at each
                # slot's own cursor, gated by the (occupancy x
                # remaining-length) mask.
                win = jax.vmap(lambda s, c: jax.lax.dynamic_slice_in_dim(
                    s, c, window, 0))(stim, cursor)
                win = jnp.where(mask.T[:, :, None, None], win, 0.0)
                drives = jnp.zeros((window, cfg.n_chips, slots,
                                    cfg.chip.n_rows), jnp.float32)
                drives = drives.at[:, stim_idx].set(
                    win.transpose(1, 2, 0, 3))
            out = stlib.run_stream(
                params, state, drives, cfg, fabric=plan, timed=timed,
                overlap=overlap, use_fused=use_fused,
                plasticity=plasticity, plasticity_state=plast,
                slot_mask=mask)
            if keep_spikes:
                payload = out._replace(state=self._row_like,  # not hauled
                                       plasticity=None)
            else:
                with jax.named_scope("slots"):
                    payload = {
                        "spike_count": out.spikes.sum(axis=(0, 1, 3)),
                        "dropped": out.dropped.sum(axis=(0, 1)),
                        "uplink_dropped": out.uplink_dropped.sum(
                            axis=(0, 1)),
                        "unroutable": out.unroutable.sum(axis=(0, 1)),
                        "rerouted": out.rerouted.sum(axis=(0, 1)),
                    }
                if timed:
                    payload["latency_ns"] = out.latency_ns
                    payload["latency_valid"] = out.latency_valid
            return out.state, out.plasticity, payload

        def _insert(state, plast, slot, row_state, row_plast):
            # Checkpoint-restore path only: the one admission with real
            # per-row payload (fresh rows are handled by the reset mask).
            chips = jax.tree.map(
                lambda a, r: jax.lax.dynamic_update_slice_in_dim(a, r, slot,
                                                                 1),
                state.chips, row_state.chips)
            inflight = jax.lax.dynamic_update_slice_in_dim(
                state.inflight, row_state.inflight, slot, 2)
            if plast is not None:
                plast = jax.tree.map(
                    lambda a, r: jax.lax.dynamic_update_slice_in_dim(
                        a, r, slot, 1), plast, row_plast)
            return (netlib.NetworkState(chips=chips, inflight=inflight),
                    plast)

        def _extract(state, plast, slot):
            chips = jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, 1),
                state.chips)
            inflight = jax.lax.dynamic_slice_in_dim(state.inflight, slot, 1,
                                                    2)
            row_plast = (None if plast is None else jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, 1),
                plast))
            return (netlib.NetworkState(chips=chips, inflight=inflight),
                    row_plast)

        # The window program.  The network parameters are an argument, not a
        # closure constant: at the published chip size they are ~50 MB per
        # fabric, which would otherwise be baked into the compiled program.
        self.window_fn = jax.jit(_step)
        self._insert_fn = jax.jit(_insert)
        self._extract_fn = jax.jit(_extract)

    # -- introspection ------------------------------------------------------

    @property
    def active(self) -> int:
        """Occupied slots."""
        return sum(s is not None for s in self._sessions)

    @property
    def queued(self) -> int:
        return len(self._queue)

    @property
    def done(self) -> tuple[int, ...]:
        """Session ids with an uncollected result."""
        return tuple(self._results)

    # -- request path -------------------------------------------------------

    def submit(self, stimulus, *, restore_from: str | None = None) -> int:
        """Queue one session; admitted into a slot as soon as one is free.

        ``stimulus``: f32[T, n_rows] (single stim chip) or
        f32[T, len(stim_chips), n_rows] — T <= max_steps emulation steps.
        ``restore_from``: a checkpoint directory written by ``evict`` —
        the session resumes from its checkpointed row (cursor, state and
        plasticity restored; the stimulus must be the original full
        schedule).  Returns the session id.
        """
        stim = np.asarray(stimulus, np.float32)
        if stim.ndim == 2:
            stim = stim[:, None, :]
        if stim.shape[1:] != (len(self.stim_chips), self.cfg.chip.n_rows):
            raise ValueError(
                f"stimulus must be [T, {len(self.stim_chips)}, "
                f"{self.cfg.chip.n_rows}], got {stim.shape}")
        if stim.shape[0] > self.max_steps:
            raise ValueError(f"session length {stim.shape[0]} exceeds "
                             f"max_steps={self.max_steps}")
        sid = self._next_sid
        self._next_sid += 1
        self._queue.append((sid, stim, restore_from, time.time()))
        self._admit()
        return sid

    def _admit(self) -> None:
        while self._queue:
            free = next((i for i, s in enumerate(self._sessions)
                         if s is None), None)
            if free is None:
                return
            sid, stim, restore_from, t_sub = self._queue.popleft()
            with jax.profiler.TraceAnnotation(
                    "engine.admit", session=sid,
                    queued_ms=(time.time() - t_sub) * 1e3):
                if restore_from is None:
                    # Fresh session: no device work now — the slot's reset
                    # to the init row rides inside the next step() call.
                    self._pending_reset[free] = True
                    start = 0
                else:
                    ck = elastic.restore_stream_checkpoint(
                        restore_from, self._row_like,
                        plasticity_like=self._row_plast_like,
                        expect_fingerprint=self._fingerprint)
                    self._state, self._plast = self._insert_fn(
                        self._state, self._plast, jnp.int32(free), ck.state,
                        ck.plasticity)
                    self._pending_reset[free] = False
                    start = ck.step
                self._stim[free] = 0.0
                self._stim[free, :stim.shape[0]] = stim
                self._cursor[free] = start
                self._length[free] = stim.shape[0]
                # ``delivered`` counts steps emulated by *this* engine run
                # — a restored session resumes at cursor=start but its
                # result only carries the post-restore windows (stitch with
                # the evicted partial result for the full raster).
                self._sessions[free] = _Session(
                    sid=sid, length=stim.shape[0], submitted_at=t_sub)

    # -- advance ------------------------------------------------------------

    def step(self) -> int:
        """Advance every occupied slot one window; finalize sessions whose
        cursor reached their length and admit queued requests into the
        freed slots.  Returns the number of sessions finished this call."""
        occ = np.array([s is not None for s in self._sessions])
        if not occ.any():
            return 0
        remaining = np.where(occ, self._length - self._cursor, 0)
        with jax.profiler.TraceAnnotation(
                "engine.step",
                live=int(np.minimum(remaining, self.window).sum()),
                slot_steps=self.slots * self.window,
                finished=int((occ & (remaining <= self.window)).sum())):
            mask = (np.arange(self.window)[:, None] < remaining[None, :])
            reset = self._pending_reset.copy()
            host = (self._stim, self._cursor, mask, reset)
            with jax.profiler.TraceAnnotation(
                    "engine.upload", bytes=sum(a.nbytes for a in host)):
                args = [jnp.asarray(a) for a in host]
            self._state, self._plast, payload = self.window_fn(
                self.params, self._state, self._plast, *args)
            # Only the resets this call materialized — _admit below may flag
            # new ones for the *next* window.
            self._pending_reset &= ~reset
            with jax.profiler.TraceAnnotation("engine.wait"):
                jax.block_until_ready(payload)
            self._account(payload, remaining)
            self._cursor = np.where(
                occ, np.minimum(self._cursor + self.window, self._length),
                self._cursor)
            finished = 0
            for slot in range(self.slots):
                if occ[slot] and self._cursor[slot] >= self._length[slot]:
                    self._finalize(slot)
                    finished += 1
            self._admit()
        return finished

    def window_args(self) -> tuple:
        """Arguments of an all-masked call of ``window_fn`` on the real
        shapes — what ``warm`` runs; ``window_fn.lower(*window_args())`` is
        the window program for inspection or ahead-of-time compilation."""
        return (self.params, self._state, self._plast,
                jnp.asarray(self._stim), jnp.asarray(self._cursor),
                jnp.zeros((self.window, self.slots), bool),
                jnp.zeros((self.slots,), bool))

    def warm(self) -> None:
        """Compile the window program on the real shapes without advancing
        any session (all-masked step; the returned state is discarded) —
        call before timing so the clock never includes jit compilation."""
        jax.block_until_ready(self.window_fn(*self.window_args())[0])

    @staticmethod
    def _readback(tree, what: str):
        """Host copies of the device arrays of ``tree``, under one
        ``engine.readback`` span."""
        nbytes = sum(a.nbytes for a in jax.tree.leaves(tree))
        with jax.profiler.TraceAnnotation("engine.readback", bytes=nbytes,
                                          what=what):
            return jax.tree.map(np.asarray, tree)

    def _account(self, payload, remaining) -> None:
        drop_keys = ("dropped", "uplink_dropped", "unroutable", "rerouted")
        with jax.profiler.TraceAnnotation("engine.account"):
            if self.keep_spikes:
                wanted = ("spikes", *drop_keys)
                if self.timed:
                    wanted += ("latency_ns", "latency_valid")
                host = self._readback(
                    {k: getattr(payload, k) for k in wanted}, "raster")
            else:
                host = self._readback(payload, "counts")
            lat, lat_valid = host.get("latency_ns"), host.get("latency_valid")
            for slot, sess in enumerate(self._sessions):
                if sess is None or remaining[slot] <= 0:
                    continue
                w = int(min(self.window, remaining[slot]))
                if self.keep_spikes:
                    spikes = host["spikes"][:w, :, slot]
                    sess.spike_windows.append(spikes)
                    sess.spike_count += float(spikes.sum())
                    for k in drop_keys:
                        sess.drops[k] += int(host[k][:, :, slot].sum())
                else:
                    sess.spike_count += float(host["spike_count"][slot])
                    for k in drop_keys:
                        sess.drops[k] += int(host[k][slot])
                if lat is not None:
                    sess.lat_samples.append(
                        lat[:, :, slot][lat_valid[:, :, slot]])
                sess.delivered += w

    # -- completion ---------------------------------------------------------

    def _session_latency(self, sess: _Session):
        if not self.timed:
            return None
        samples = (np.concatenate(sess.lat_samples)
                   if sess.lat_samples else np.zeros((0,), np.int32))
        with jax.profiler.TraceAnnotation("engine.latency",
                                          samples=samples.size):
            return stlib.masked_latency_stats(
                samples, np.ones(samples.shape, bool), strict=False)

    def _session_plasticity(self, slot: int):
        if self._plast is None:
            return None
        if self._pending_reset[slot]:
            # Admitted but never stepped: the device row is still the
            # previous tenant's — the true row is the init row.
            row = self._row_plast_like
        else:
            _, row = self._extract_fn(self._state, self._plast,
                                      np.int32(slot))
        row = self._readback(row, "plasticity")
        return jax.tree.map(lambda a: a[:, 0], row)

    def _result_of(self, slot: int, *, evicted_to=None) -> SessionResult:
        sess = self._sessions[slot]
        spikes = None
        if self.keep_spikes:
            spikes = (np.concatenate(sess.spike_windows, axis=0)
                      if sess.spike_windows
                      else np.zeros((0, self.cfg.n_chips,
                                     self.cfg.chip.n_neurons), np.float32))
        return SessionResult(
            session_id=sess.sid, steps=sess.delivered, spikes=spikes,
            spike_count=int(sess.spike_count), latency=self._session_latency(
                sess), plasticity=self._session_plasticity(slot),
            submitted_at=sess.submitted_at, finished_at=time.time(),
            evicted_to=evicted_to, **sess.drops)

    def _finalize(self, slot: int) -> None:
        with jax.profiler.TraceAnnotation(
                "engine.finalize", session=self._sessions[slot].sid):
            result = self._result_of(slot)
            self._results[result.session_id] = result
            self._sessions[slot] = None

    def collect(self, session_id: int) -> SessionResult:
        """Pop a finished session's result (KeyError while still running)."""
        return self._results.pop(session_id)

    def evict(self, session_id: int, ckpt_dir: str) -> SessionResult:
        """Checkpoint a running tenant's row and free its slot.

        The row (chip states, in-flight delay-line slice, plasticity
        traces + evolved weights) goes through the crash-consistent
        ``runtime.elastic`` writer with the engine's fingerprint; the
        returned partial ``SessionResult`` carries the output so far and
        ``evicted_to=ckpt_dir``.  Resubmit the original stimulus with
        ``restore_from=ckpt_dir`` to resume bit-exactly.
        """
        slot = next((i for i, s in enumerate(self._sessions)
                     if s is not None and s.sid == session_id), None)
        if slot is None:
            raise KeyError(f"session {session_id} is not running")
        if self._pending_reset[slot]:
            # Admitted but never stepped: checkpoint the init row (the
            # device row is still the previous tenant's).
            row_state, row_plast = self._row_like, self._row_plast_like
            self._pending_reset[slot] = False
        else:
            row_state, row_plast = self._extract_fn(
                self._state, self._plast, jnp.int32(slot))
        elastic.save_stream_state(
            ckpt_dir, int(self._cursor[slot]), row_state,
            plasticity=row_plast, fingerprint=self._fingerprint,
            metadata={"session_length": int(self._length[slot])})
        result = self._result_of(slot, evicted_to=ckpt_dir)
        self._sessions[slot] = None
        self._admit()
        return result

    def drain(self) -> dict[int, SessionResult]:
        """Step until every running and queued session finishes; returns
        (without popping) the result map."""
        while self.active or self._queue:
            self.step()
        return dict(self._results)
