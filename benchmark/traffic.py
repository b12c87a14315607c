"""The one generator of traffic: a mix is a data file of parameters.

Every seed is given the same work. Lengths and gaps between arrivals are
the quantiles of their distributions at fixed, evenly spaced probabilities,
arranged once by the mix's own ``pattern_seed`` into a cycle that lasts
exactly one window. The run's seed chooses where in the cycle the window
starts (another order of the same arrivals and sizes) and the token values.
So every window holds each request of the cycle exactly once, and the
spread between runs is the system's, not the generator's: on the chip,
arrangements drawn anew for every seed moved ``ttft_p95_ms`` between 200 and
417 ms while two runs of one arrangement agreed within 3% (PR 25). A closed
loop that says ``"arrange": "by_client"`` deals its lengths to the clients by
strata instead, so that its rounds are alike (``arranged_by_client``).

Imports numpy only: the load generator's process must never import JAX.
"""

from __future__ import annotations

import math
import statistics
from typing import Any, Dict, List

import numpy as np

_MASK = 0xFFFFFFFF


def rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one purpose (``stream``) of one run's seed, for any
    non-negative seed however large."""
    return np.random.default_rng([stream, seed & _MASK, seed >> 32])


def _quantile_points(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def prompt_lengths(spec: Dict[str, Any], n: int, pattern_seed: int,
                   by_client: int = 0) -> np.ndarray:
    """``n`` prompt lengths: the quantiles of the mix's distribution,
    clipped to its limits, in the pattern's order; with ``by_client``
    clients, in ``arranged_by_client``'s order."""
    dist = spec["dist"]
    if dist == "lognormal":
        normal = statistics.NormalDist()
        z = np.array([normal.inv_cdf(u) for u in _quantile_points(n)])
        lengths = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif dist == "fixed":
        lengths = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lengths = np.clip(np.rint(lengths), spec["min"], spec["max"]).astype(int)
    if by_client:
        return arranged_by_client(lengths, by_client, pattern_seed)
    return rng(pattern_seed, 1).permutation(lengths)


def arranged_by_client(lengths: np.ndarray, clients: int, pattern_seed: int
                       ) -> np.ndarray:
    """A closed loop's cycle whose rounds are alike by construction
    (``"arrange": "by_client"``). The ``r x clients`` lengths are sorted and
    cut into ``clients`` strata of ``r`` neighbours; client ``c`` sends the
    stratum the pattern's permutation gives it, one member a round,
    ascending in an even stratum and descending in an odd one. So every
    round holds one length of every stratum, a client's requests are nearly
    equal, and the rotation a seed picks reorders rounds that carry the
    same tokens within about a per cent, at any step time. Item
    ``j * clients + c`` is client ``c``'s request of round ``j``."""
    r, rest = divmod(len(lengths), clients)
    if rest or not r:
        raise ValueError(f"arrange by_client: {len(lengths)} lengths are "
                         f"not whole rounds of {clients} clients")
    strata = np.sort(lengths).reshape(clients, r)
    strata[1::2] = strata[1::2, ::-1].copy()
    return strata[rng(pattern_seed, 1).permutation(clients)].T.reshape(-1)


def clients_arranged(traffic: Dict[str, Any]) -> int:
    """The clients a mix arranges its cycle by, 0 where it has no
    ``"arrange"``: ``prompt_lengths``' ``by_client``."""
    arrange = traffic.get("arrange")
    if arrange is None:
        return 0
    if arrange != "by_client":
        raise ValueError(f"unknown arrangement {arrange!r}")
    return int(traffic["clients"])


def arrival_cycle(rate_rps: float, seconds: float, pattern_seed: int
                  ) -> np.ndarray:
    """One cycle of Poisson arrivals: ``rate_rps * seconds`` instants in
    [0, seconds), the gaps between them (and round the end of the cycle)
    being the exponential distribution's quantiles in the pattern's order,
    scaled so that the cycle lasts ``seconds`` exactly."""
    n = max(1, int(round(rate_rps * seconds)))
    gaps = rng(pattern_seed, 2).permutation(-np.log1p(-_quantile_points(n)))
    gaps *= seconds / gaps.sum()
    return np.cumsum(gaps) - gaps[0]


def start_index(seed: int, n: int) -> int:
    """Where in a cycle of ``n`` the run of this seed starts."""
    return int(rng(seed, 3).integers(n))


def prompt_tokens(seed: int, index: int, length: int, vocab_size: int
                  ) -> List[int]:
    """The token ids of request ``index``."""
    return rng(seed, 1000 + index).integers(
        0, vocab_size, int(length)).tolist()


def token_rows(seed: int, rows: int, seq_len: int, vocab_size: int
               ) -> np.ndarray:
    """``rows`` training sequences of ``seq_len`` + 1 tokens (a sequence
    and its shifted labels)."""
    return rng(seed, 4).integers(0, vocab_size, (rows, seq_len + 1),
                                 dtype=np.int32)


def request_plan(traffic: Dict[str, Any], seconds: float, seed: int
                 ) -> Dict[str, Any]:
    """What the load generator sends for a window of ``seconds``: the loop,
    each request's prompt length and, in an open loop, the instant it is
    due, counted from the start of the window. The window holds one whole
    cycle, starting at the seed's place in it; the pre-roll before the
    window (negative instants) is the end of the cycle before."""
    preroll = float(traffic["preroll_s"])
    pattern = int(traffic["pattern_seed"])
    if traffic["loop"] == "open":
        at = arrival_cycle(traffic["rate_rps"], seconds, pattern)
        n = len(at)
        lengths = prompt_lengths(traffic["prompt_len"], n, pattern)
        k = start_index(seed, n)
        order = (k + np.arange(n)) % n
        due = (at[order] - at[k]) % seconds
        before = [(i, d) for i, d in ((int(i), float((at[i] - at[k])
                                                     % seconds - seconds))
                                      for i in order[::-1]) if d >= -preroll]
        before.reverse()
        return {"loop": "open", "preroll_s": preroll,
                "due_s": [d for _, d in before] + due.tolist(),
                "lengths": [int(lengths[i]) for i, _ in before]
                + lengths[order].tolist()}
    if traffic["loop"] == "closed":
        n, clients = int(traffic["n_lengths"]), int(traffic["clients"])
        lengths = prompt_lengths(traffic["prompt_len"], n, pattern,
                                 clients_arranged(traffic))
        # client c sends items c, c + clients, ...: a start that is a
        # multiple of the clients keeps each round's set of prompts
        start = clients * start_index(seed, max(1, n // clients))
        return {"loop": "closed", "clients": clients,
                "lengths": np.roll(lengths, -start).tolist(),
                "preroll_s": preroll}
    raise ValueError(f"unknown loop {traffic['loop']!r}")


def percentile(values: List[float], q: float) -> float:
    """Linear interpolation between order statistics (numpy's default)."""
    if not values:
        raise ValueError("no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
