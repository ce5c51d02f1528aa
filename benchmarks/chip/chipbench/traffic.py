"""Open-loop request schedules drawn from a traffic-mix file.

A mix file (``traffic/<mix>.json``) lists phases.  Each phase gives an
arrival rate as a share of the cell's knee, a prompt-length distribution
over buckets, and a clipped lognormal output-length distribution.  The first
phase also covers the warm-up traffic that runs before the measured window.

Every seed gets the same work in another order: a phase of ``n`` requests
takes the ``n`` exponential quantiles as its inter-arrival gaps, bucket
counts by largest remainder, and the ``n`` lognormal quantiles as output
lengths, and only the permutations come from the seed.  So runs with
different seeds differ in which requests share a batch, not in how much
there is to serve.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

ARRIVALS = ("poisson",)


@dataclass
class Requests:
    """A schedule segment: due times (s from the segment's start) and sizes."""
    due: np.ndarray          # (n,) float64, sorted
    prompt_len: np.ndarray   # (n,) int64
    gen: np.ndarray          # (n,) int64 decode steps: output tokens - 1
    phase: np.ndarray        # (n,) int64 index into the mix's phases

    def __len__(self) -> int:
        return len(self.due)


def _counts(probs: dict, n: int) -> dict:
    """Largest-remainder apportionment of ``n`` over ``probs``."""
    keys = sorted(probs, key=int)
    p = np.array([float(probs[k]) for k in keys])
    if np.any(p < 0) or not math.isclose(p.sum(), 1.0, abs_tol=1e-9):
        raise ValueError(f"prompt_len shares must be >= 0 and sum to 1: "
                         f"{probs}")
    raw = p * n
    base = np.floor(raw).astype(int)
    rest = n - base.sum()
    order = np.argsort(-(raw - base), kind="stable")
    base[order[:rest]] += 1
    return {int(k): int(c) for k, c in zip(keys, base)}


def output_quantiles(spec: dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of the clipped lognormal output length."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def draw_phase(phase: dict, n: int, span_s: float, rng) -> Requests:
    """``n`` requests due within ``[0, span_s)``."""
    if n <= 0:
        e = np.zeros(0, np.int64)
        return Requests(np.zeros(0), e, e, e)
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q))
    c = np.cumsum(gaps)
    due = span_s * (c - gaps[0]) / c[-1]
    prompts = np.concatenate([np.full(c_, k, np.int64) for k, c_ in
                              _counts(phase["prompt_len"], n).items()])
    return Requests(due=due, prompt_len=rng.permutation(prompts),
                    gen=rng.permutation(output_quantiles(
                        phase["output_len"], n)) - 1,
                    phase=np.zeros(n, np.int64))


def _concat(parts) -> Requests:
    return Requests(*(np.concatenate([getattr(p, f) for p in parts])
                      for f in ("due", "prompt_len", "gen", "phase")))


def phase_spans(mix: dict, seconds: float) -> list:
    """(start, end) of each phase inside a window of ``seconds``."""
    spans, t = [], 0.0
    for i, ph in enumerate(mix["phases"]):
        last = i == len(mix["phases"]) - 1
        end = seconds if last else min(t + float(ph["window_seconds"]),
                                       seconds)
        spans.append((t, end))
        t = end
    return spans


def schedule(mix: dict, knee_rps: float, seconds: float, warmup: int,
             seed: int):
    """(warm-up requests, window requests) for one run.

    The warm-up holds ``warmup`` requests of the first phase, due from 0 at
    that phase's rate.  The window holds each phase's share of
    ``seconds`` at its own rate."""
    if mix.get("arrivals", "poisson") not in ARRIVALS:
        raise ValueError(f"unknown arrivals {mix.get('arrivals')!r}")
    rng = np.random.default_rng([int(seed), 0x7A11])
    first = mix["phases"][0]
    rate0 = float(first["rate_knee_share"]) * knee_rps
    warm = draw_phase(first, warmup, warmup / rate0, rng)
    parts = []
    for i, (ph, (lo, hi)) in enumerate(zip(mix["phases"],
                                           phase_spans(mix, seconds))):
        rate = float(ph["rate_knee_share"]) * knee_rps
        part = draw_phase(ph, int(round(rate * (hi - lo))), hi - lo, rng)
        part.due = part.due + lo
        part.phase[:] = i
        parts.append(part)
    return warm, _concat(parts)


def prompt_buckets(mix: dict) -> list:
    return sorted({int(k) for ph in mix["phases"] for k in ph["prompt_len"]})


def output_range(mix: dict) -> tuple:
    return (min(int(ph["output_len"]["min"]) for ph in mix["phases"]),
            max(int(ph["output_len"]["max"]) for ph in mix["phases"]))
