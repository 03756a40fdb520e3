"""The one generator of open-loop traffic, driven by a mix file's data.

A mix gives an arrival rate and, for each per-request field, a
categorical distribution:

    {"rate_rps": 160, "work_seed": 7,
     "fields": {"prompt_len": {"values": [256, 1024], "weights": [0.8, 0.2]},
                "device": {"values": [0, 1, 2, 3]}}}

A window of ``seconds`` holds round(rate * seconds) requests. Their
arrival times are a Poisson process given its count: sorted uniform
times. Each field takes its values in exact proportion (largest
remainder), shuffled. All of it is drawn from the mix's ``work_seed``
alone, so every run of a mix replays one trace: the tail of a queue
near its knee swings with the order of its arrivals far more than with
anything the program does. The run's seed draws what each request
carries (its tokens) and the sample the check compares.
"""
from __future__ import annotations

import numpy as np


def exact_counts(weights, n: int) -> np.ndarray:
    """Integer counts summing to n, in proportion to weights."""
    w = np.asarray(weights, np.float64)
    raw = w / w.sum() * n
    counts = np.floor(raw).astype(np.int64)
    rest = n - int(counts.sum())
    order = np.argsort(-(raw - counts), kind="stable")
    counts[order[:rest]] += 1
    return counts


def open_loop(mix: dict, seconds: float) -> list:
    """The mix's requests due in [0, seconds): dicts with ``due`` (s from
    the window's start), ``index`` and one key per field of the mix."""
    rng = np.random.default_rng(int(mix["work_seed"]))
    n = int(round(float(mix["rate_rps"]) * seconds))
    due = np.sort(rng.uniform(0.0, seconds, n))
    reqs = [{"index": i, "due": float(t)} for i, t in enumerate(due)]
    for name in sorted(mix.get("fields", {})):
        spec = mix["fields"][name]
        values = list(spec["values"])
        weights = spec.get("weights", [1.0] * len(values))
        col = np.repeat(np.arange(len(values)), exact_counts(weights, n))
        rng.shuffle(col)
        for r, j in zip(reqs, col):
            r[name] = values[int(j)]
    return reqs


def sample(items: list, k: int, seed: int, key=None) -> list:
    """Up to k items drawn from the seed, always holding the largest by
    ``key`` (the longest request) when a key is given."""
    if not items:
        return []
    rng = np.random.default_rng([seed, 7])
    idx = list(rng.permutation(len(items))[:k])
    if key is not None:
        top = max(range(len(items)), key=lambda i: key(items[i]))
        if top not in idx:
            idx[-1] = top
    return [items[i] for i in sorted(idx)]
