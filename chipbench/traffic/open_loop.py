"""Open-loop request traffic, made from a seed.

A mix file (``traffic/<mix>.json``) gives the shape of the traffic:
``arrivals`` ("poisson"), and ``prompt`` and ``output`` length
distributions (``lognormal`` with ``median``, ``sigma``, ``min``, ``max``,
or ``uniform`` with ``min``, ``max``).  The cell fixes the rate.

Every seed gets the same schedule: each segment (the ramp before the
window, the window, the tail after it) holds one stratified set of lengths
and inter-arrival gaps -- the distribution's quantiles at (i + 0.5) / n --
in one order drawn once from ``ORDER_SEED``.  The run's seed draws the
token ids (and, in ``drivers/serve.py``, the weights), not the schedule.
A window of tens of seconds holds only some hundred requests, and chat
requests live for tens of seconds; on a TPU v5e, seeds that reordered
the same set moved a run's 95th percentile of time to first token by
38 % (IQR over median) against a few % between two runs of one order, so
the order is fixed and the tail reads the same work from run to run.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Arrival:
    """One request: when it is due (seconds from traffic start) and its
    prompt and output lengths."""

    due: float
    prompt_len: int
    output_len: int
    in_window: bool


def _ppf(spec: dict):
    """Quantile function of a length distribution, clipped and rounded."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        mu, sigma = math.log(spec["median"]), float(spec["sigma"])
        std = NormalDist()
        f = lambda u: math.exp(mu + sigma * std.inv_cdf(u))
    elif spec["dist"] == "uniform":
        f = lambda u: lo + u * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return lambda u: int(min(max(round(f(u)), lo), hi))


ORDER_SEED = 20240718          # the one order of every schedule


def stratified(n: int, ppf, rng) -> np.ndarray:
    """The n quantiles of ``ppf`` at (i + 0.5) / n, in an order drawn from
    ``rng``."""
    return rng.permutation(np.array([ppf((i + 0.5) / n) for i in range(n)]))


def _gaps(n: int, seconds: float, rng) -> np.ndarray:
    """Exponential inter-arrival gaps (Poisson arrivals), stratified and
    scaled so that the n gaps fill ``seconds`` exactly."""
    g = stratified(n, lambda u: -math.log(1.0 - u), rng)
    return g * (seconds / g.sum())


def schedule(mix: dict, rate: float, ramp_s: float, window_s: float,
             tail_s: float) -> list:
    """Arrivals for ramp, window and tail at ``rate`` requests/s, in due
    order; ``in_window`` marks those due inside the window."""
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    p_ppf, o_ppf = _ppf(mix["prompt"]), _ppf(mix["output"])
    fixed = np.random.default_rng(ORDER_SEED)
    out, t0 = [], 0.0
    for seconds, in_window in ((ramp_s, False), (window_s, True),
                               (tail_s, False)):
        n = max(1, round(rate * seconds))
        gaps = _gaps(n, seconds, fixed)
        plen = stratified(n, p_ppf, fixed)
        olen = stratified(n, o_ppf, fixed)
        due = t0 + np.cumsum(gaps) - gaps            # all in [t0, t0+s)
        for d, p, o in zip(due, plen, olen):
            out.append(Arrival(float(d), int(p), int(o), in_window))
        t0 += seconds
    return out


def lateness(submitted: list, due: list) -> dict:
    """How late the generator handed requests to the server (seconds)."""
    late = sorted(max(0.0, s - d) for s, d in zip(submitted, due))
    if not late:
        return {"n": 0, "p50": 0.0, "max": 0.0}
    return {"n": len(late), "p50": late[len(late) // 2], "max": late[-1]}
