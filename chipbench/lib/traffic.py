"""Open-loop serving traffic: arrivals, request widths and task popularity.

``open_loop_schedule`` gives every seed the same multiset of work: the
``n = rate * seconds`` inter-arrival gaps are the quantiles of an
exponential at the offered rate (Poisson arrivals), the widths the quantiles
of a log-uniform law on ``width_lo..width_hi`` columns, and the tasks Zipf
counts over the task list (first task most popular).  The seed only orders
them, so runs with different seeds offer the same load.

``head_of_line`` is the batching rule of the serving load generator: the
head request's task takes every pending request of the same task, up to the
widest bucket in columns.
"""
from __future__ import annotations

import numpy as np

from chipbench.lib.data import derived_seed


def zipf_counts(n: int, n_tasks: int, s: float) -> np.ndarray:
    """Requests per task (rank order), largest remainder of n * 1/r^s."""
    w = 1.0 / np.arange(1, n_tasks + 1, dtype=np.float64) ** s
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    short = n - counts.sum()
    counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
    return counts


def open_loop_schedule(tp: dict, seconds: float, seed: int, n_tasks: int) -> dict:
    rate = float(tp["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    lo, hi = int(tp["width_lo"]), int(tp["width_hi"])
    widths = np.clip(np.floor(np.exp(np.log(lo) + q * (np.log(hi + 1) - np.log(lo)))),
                     lo, hi).astype(np.int64)
    tasks = np.repeat(np.arange(n_tasks), zipf_counts(n, n_tasks, float(tp["zipf_s"])))
    rng = np.random.default_rng(derived_seed(seed, 7))
    return {
        "due": np.cumsum(rng.permutation(gaps)),
        "width": rng.permutation(widths),
        "task": rng.permutation(tasks),
        "offset": rng.integers(0, 2**31, size=n),
    }


def head_of_line(pending: list, keys: list, widths: list, max_cols: int) -> list:
    """The head task's pending requests, in order, up to ``max_cols`` columns."""
    head = keys[pending[0]]
    batch, cols = [], 0
    for i in pending:
        if keys[i] != head:
            continue
        if batch and cols + widths[i] > max_cols:
            break
        batch.append(i)
        cols += widths[i]
    return batch
