"""The one traffic generator: reads a mix's parameters from its data file.

A mix is JSON; the driving path its ``path`` key names reads these keys:

* ``session_steps`` (engine): ``{"law", "min", "max", "strata"}`` with the
  law ``fixed``, ``uniform`` or ``log_uniform``: one cycle of ``strata``
  session lengths at evenly spaced quantiles of the law;
* ``stimulus``: ``{"chips", "rate"}``, Poisson spikes at ``rate`` on every
  synapse row of the listed chips (``"all"``: every chip), optionally
  ``"skew": {"law": "zipf", "s": s}``: the k-th chip of the list is driven
  at ``rate * w_k`` with ``w_k`` proportional to ``(k + 1) ** -s`` and of
  mean 1 (capped at a probability of 1), so the total drive stays that of
  ``rate``; a stream mix also gives the ``pool`` of drive blocks it stages;
* ``arrivals`` (engine): ``{"kind": "closed", "clients": c}``, each client
  submitting its next session when it has collected the last, or
  ``{"kind": "open", "rate_per_s": r, "burst": b, "strata": k}``: bursts of
  ``b`` sessions whose gaps are the ``k`` quantiles of an exponential law
  of mean ``b / r``.

Every seed draws the same work in a different order: lengths and gaps are
fixed strata of the stated law, shuffled by the seed, and each stimulus is
Poisson at the stated rates, drawn from ``(seed, index)``.  So two seeds
differ in which spikes arrive when, not in how much there is to do.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness.system import jax_seed


def _strata(k: int) -> np.ndarray:
    return (np.arange(k) + 0.5) / k


def session_lengths(traffic: dict, seed: int) -> np.ndarray:
    """One cycle of session lengths: the strata of the stated law in an
    order drawn from the seed.  Session ``k`` has length
    ``lengths[k % len(lengths)]``."""
    s = traffic["session_steps"]
    lo, hi, k = s["min"], s["max"], s.get("strata", 1)
    q = _strata(k)
    if s["law"] == "fixed":
        if lo != hi:
            raise ValueError("a fixed session length has min == max")
        lengths = np.full(k, lo, np.float64)
    elif s["law"] == "uniform":
        lengths = lo + (hi - lo) * q
    elif s["law"] == "log_uniform":
        lengths = lo * (hi / lo) ** q
    else:
        raise ValueError(f"unknown session length law {s['law']!r}")
    lengths = np.rint(lengths).astype(np.int64)
    return np.random.default_rng([seed, 1]).permutation(lengths)


def max_session_steps(traffic: dict) -> int:
    return int(traffic["session_steps"]["max"])


def stimulus_chips(traffic: dict, cfg: dict) -> tuple[int, ...]:
    chips = traffic["stimulus"]["chips"]
    if chips == "all":
        return tuple(range(math.prod(cfg["fabric"]["fan_ins"])))
    return tuple(int(c) for c in chips)


def chip_rates(traffic: dict, cfg: dict) -> np.ndarray:
    """f64[n_stim_chips]: the spike probability per row and step of each
    stimulated chip, in the order of ``stimulus_chips``."""
    st = traffic["stimulus"]
    n = len(stimulus_chips(traffic, cfg))
    weight = np.ones(n)
    skew = st.get("skew")
    if skew is not None:
        if skew["law"] != "zipf":
            raise ValueError(f"unknown stimulus skew {skew['law']!r}")
        weight = (np.arange(n) + 1.0) ** -float(skew["s"])
        weight *= n / weight.sum()
    return np.minimum(st["rate"] * weight, 1.0)


def session_stimulus(traffic: dict, cfg: dict, seed: int, index: int,
                     steps: int) -> np.ndarray:
    """f32[steps, n_stim_chips, n_rows]: Poisson spikes at the mix's rates
    on the stimulated chips' synapse rows, for session ``index``."""
    rates = chip_rates(traffic, cfg)
    rng = np.random.default_rng([seed, 2, index])
    shape = (steps, len(rates), cfg["chip"]["n_rows"])
    return (rng.random(shape) < rates[None, :, None]).astype(np.float32)


def arrival_gaps(traffic: dict, seed: int) -> np.ndarray:
    """One cycle of gaps (s) between successive arrivals of an open loop,
    in an order drawn from the seed: within a burst the gap is 0."""
    a = traffic["arrivals"]
    if a["kind"] != "open":
        raise ValueError("only an open loop has arrival gaps")
    burst, k = int(a.get("burst", 1)), int(a.get("strata", 64))
    mean = burst / float(a["rate_per_s"])
    gaps = -mean * np.log1p(-_strata(k))
    gaps = np.random.default_rng([seed, 4]).permutation(gaps)
    out = np.zeros((k, burst))
    out[:, -1] = gaps
    return out.ravel()


def drive_pool(traffic: dict, cfg: dict, seed: int) -> jax.Array:
    """f32[pool, steps, n_chips, batch, n_rows]: the stream's drive blocks,
    Poisson at the mix's rates on every row of the stimulated chips, made
    on the device in one jitted call."""
    st = traffic["stimulus"]
    n = math.prod(cfg["fabric"]["fan_ins"])
    p = np.zeros((n,), np.float32)
    p[list(stimulus_chips(traffic, cfg))] = chip_rates(traffic, cfg)
    shape = (st["pool"], traffic["steps_per_call"], n, traffic["batch"],
             cfg["chip"]["n_rows"])

    @jax.jit
    def make(key, p):
        u = jax.random.uniform(key, shape)
        return (u < p[None, None, :, None, None]).astype(jnp.float32)

    return make(jax.random.PRNGKey(jax_seed(seed) ^ 0x5EED), jnp.asarray(p))
