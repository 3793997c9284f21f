"""Percentiles and per-request latency arithmetic (copied from the repo's
serving benchmark so that later program changes cannot move it)."""

from __future__ import annotations

import math

import numpy as np


def pct(xs, q: float) -> float:
    """``q``-th percentile (numpy's linear rule); +inf entries stand for
    failed requests and rank above every finite one."""
    xs = np.asarray(list(xs), np.float64)
    if xs.size == 0:
        return float("nan")
    return float(np.percentile(xs, q))


def tpot(first_t: float, last_t: float, n_tokens: int) -> float:
    """Mean gap between output tokens of one request (seconds), or nan when
    it has fewer than two."""
    if n_tokens < 2 or not math.isfinite(first_t):
        return float("nan")
    return (last_t - first_t) / (n_tokens - 1)
