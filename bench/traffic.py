"""The one traffic generator: reads a mix's parameters
(``bench/traffic/<mix>.json``) and the run's seed, and yields requests.

Every request is a contiguous slice of an image pool made from the seed,
so the reference needs to run once per pool image, not once per request.

Keys of a mix:

* ``loop``: ``"closed"`` — the harness keeps ``backlog_requests``
  requests waiting at the front door, so the backlog is never empty
  (MLPerf Offline); or ``"open"`` — requests are due on a schedule that
  does not wait for the server (MLPerf Server);
* ``microbatch``: rows per microbatch the server is built with;
* ``replicas``, ``stages`` (optional, 1 each): the front door's
  replicas, each a pipeline of ``stages`` chips, over the cell's chips;
* ``pool_images``: images in the pool;
* ``size_mix``: ``[[rows, weight], ...]``, rows per request;
* open loop only: ``rate_rps``, requests per second over the window, or
  ``phases``: ``[{"seconds": d, "rate_rps": r}, ...]``, a piecewise
  constant rate that repeats over the window (bursts).

Every seed gets the same work in another order: the sizes are the mix's
shares rounded to whole requests, and the gaps between arrivals are the
quantiles of the exponential distribution (a Poisson process's gaps,
stratified), both shuffled by the seed.  So runs with different seeds
offer the same number of requests and rows in the same window.  This
generator, like ``serving/loadgen.poisson_plan`` of the program, draws
Poisson arrivals with a size mix over an image pool.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request: rows ``[off, off + n)`` of the pool, due ``due``
    seconds after the window opens (open loop; None for closed)."""
    off: int
    n: int
    due: float | None = None


def sizes(mix: dict, count: int, rng) -> np.ndarray:
    """``count`` request sizes in the mix's shares (largest remainder),
    in an order drawn from ``rng``."""
    rows = np.asarray([r for r, _ in mix["size_mix"]], dtype=int)
    w = np.asarray([w for _, w in mix["size_mix"]], dtype=float)
    if (rows < 1).any() or (w <= 0).any():
        raise ValueError(f"bad size_mix {mix['size_mix']}")
    if rows.max() > mix["pool_images"]:
        raise ValueError("a request is larger than the image pool")
    exact = w / w.sum() * count
    n = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - n))[:count - n.sum()]:
        n[i] += 1
    return rng.permutation(np.repeat(rows, n))


def _offsets(mix: dict, ns: np.ndarray, rng) -> np.ndarray:
    return np.asarray([rng.integers(0, mix["pool_images"] - n + 1)
                       for n in ns], dtype=int)


def _stratified_gaps(count: int, rng) -> np.ndarray:
    u = (np.arange(count) + 0.5) / count
    return rng.permutation(-np.log1p(-u))


def open_plan(mix: dict, seconds: float, rng) -> list:
    """Every request due in a window of ``seconds``, in due order."""
    phases = mix.get("phases") or [{"seconds": seconds,
                                    "rate_rps": mix["rate_rps"]}]
    dues, t0 = [], 0.0
    while t0 < seconds:
        for ph in phases:
            d = min(ph["seconds"], seconds - t0)
            if d <= 0:
                break
            count = int(round(ph["rate_rps"] * d))
            if count:
                gaps = _stratified_gaps(count, rng)
                # the phase's arrivals fill it: the k-th of `count` falls
                # at k/(count+1) of the way through on average
                t = np.cumsum(gaps) / gaps.sum() * d * count / (count + 1)
                dues.extend(t0 + t)
            t0 += d
    ns = sizes(mix, len(dues), rng)
    offs = _offsets(mix, ns, rng)
    return [Planned(int(o), int(n), float(t))
            for t, o, n in zip(dues, offs, ns)]


def closed_stream(mix: dict, rng, block: int = 64):
    """An endless stream of requests for a closed loop, drawn a block at
    a time so that every block holds the mix's shares."""
    while True:
        ns = sizes(mix, block, rng)
        for o, n in zip(_offsets(mix, ns, rng), ns):
            yield Planned(int(o), int(n))


def image_pool(key, mix: dict, in_hw: int, in_ch: int = 3) -> np.ndarray:
    """The pool as float32 host memory, drawn on the device in one call:
    standard normal pixels."""
    import jax
    draw = jax.jit(lambda k: jax.random.normal(
        k, (mix["pool_images"], in_hw, in_hw, in_ch)))
    return np.asarray(draw(key))
