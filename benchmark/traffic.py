"""The one general traffic generator: a traffic mix is a data file of
parameters under benchmark/traffic/, and everything sent is drawn from
``--seed`` before the window opens. No jax, no program code."""

from __future__ import annotations

import numpy as np

# streams of the one seed, so that schedule, users and samples are
# independent of each other and of the factor tables
STREAM_SCHEDULE, STREAM_USERS, STREAM_SAMPLE = 11, 12, 13


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def stratified_exponential_schedule(seed: int, rate_qps: float, seconds: float) -> np.ndarray:
    """Due times (s from the phase's start) of an open-loop stream whose
    gaps are exponential in their histogram and NOT a Poisson process.

    Every seed gets the SAME set of gaps in another order: the n + 1 gaps
    are the quantiles of the exponential distribution (stratified: one gap
    from each of n + 1 equal-probability strata), permuted by the seed and
    scaled to fill the phase with exactly n requests. A Poisson process
    would vary the count by sqrt(n) and the burstiness from run to run;
    here the seed moves where the bursts fall and never the amount of work
    nor how bursty it is. Tails read under it are tails under arrivals
    that are smoother, run to run, than users send."""
    n = int(round(rate_qps * seconds))
    if n <= 0:
        raise ValueError(f"rate {rate_qps} over {seconds} s holds no request")
    gaps = -np.log1p(-(np.arange(n + 1) + 0.5) / (n + 1))
    gaps = rng_for(seed, STREAM_SCHEDULE).permutation(gaps)
    due = np.cumsum(gaps)[:n]
    return due * (seconds / float(np.sum(gaps)))


def user_order(seed: int, num_users: int, count: int, kind: str = "uniform-distinct") -> np.ndarray:
    """The users asked about, in the order they are asked."""
    rng = rng_for(seed, STREAM_USERS)
    if kind == "uniform-distinct":
        if count > num_users:
            raise ValueError(f"{count} distinct users asked of {num_users}")
        return rng.permutation(num_users)[:count]
    if kind == "uniform":
        return rng.integers(0, num_users, count)
    raise ValueError(f"unknown user distribution {kind!r}")


def encode_requests(users: np.ndarray, num: int, host: str) -> list[bytes]:
    """One whole HTTP/1.1 keep-alive request per user, as bytes."""
    out = []
    head = (
        "POST /queries.json HTTP/1.1\r\nHost: %s\r\n"
        "Content-Type: application/json\r\nContent-Length: " % host
    ).encode()
    for u in users.tolist():
        body = b'{"user":"u%d","num":%d}' % (u, num)
        out.append(head + b"%d\r\n\r\n" % len(body) + body)
    return out


def sample_indices(seed: int, n: int, count: int) -> np.ndarray:
    """Which of n finished requests the reference checks, sorted."""
    if n <= count:
        return np.arange(n)
    return np.sort(rng_for(seed, STREAM_SAMPLE).choice(n, count, replace=False))
