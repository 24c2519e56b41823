"""The one general traffic generator. A mix is a data file of parameters in
``traffic/``; nothing here knows a mix by name.

Every seed gets the SAME set of sizes and the same set of gaps between
arrivals, in another order, with other tokens: the sizes are drawn from the
mix's own ``mix_seed`` and only permuted by the run's seed. So runs differ
in what they send when, never in how much work they offer.

Length distributions (``prompt_len``, ``answer_len``):
  {"dist": "uniform", "min": a, "max": b}
  {"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}
``max_total`` clips the answer to ``max_total - prompt`` (the cache's rows).
``shared_prefix`` (optional): {"tokens": n, "groups": g} makes each prompt
start with one of g seeded prefixes of n tokens (longer prompts only).

Generators (``generator``):
  "open_loop":   ``rate_per_s`` Poisson arrivals; ``seconds * rate`` requests
                 exactly, due at fixed offsets whatever the system does.
  "closed_loop": ``clients`` callers, each sending its next request when its
                 last one completed; requests are handed out from one list
                 of ``pool`` sizes, round and round.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    prompt: np.ndarray               # [L] int64 tokens
    max_new: int
    due: float = 0.0                 # open loop: seconds into the window
    # filled in by the runner, on the client's side (host monotonic clock)
    sent: Optional[float] = None
    first_token: Optional[float] = None
    done: Optional[float] = None
    tokens: Optional[np.ndarray] = None
    error: Optional[str] = None
    fut: object = None               # the engine's future, once admitted
    streamed_in_window: int = 0      # tokens at the client when it closed


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        v = rng.integers(lo, hi + 1, n)
    elif spec["dist"] == "lognormal":
        v = np.rint(rng.lognormal(np.log(spec["median"]), spec["sigma"], n))
    else:
        raise ValueError(f"traffic: unknown length distribution "
                         f"{spec['dist']!r}")
    return np.clip(v, lo, hi).astype(np.int64)


def _sizes(mix: dict, n: int):
    """The mix's fixed multiset of (prompt, answer) lengths."""
    rng = np.random.default_rng([int(mix["mix_seed"]), n])
    prompts = _lengths(mix["prompt_len"], n, rng)
    answers = _lengths(mix["answer_len"], n, rng)
    answers = np.minimum(answers, int(mix["max_total"]) - prompts)
    if answers.min() < 1:
        raise ValueError("traffic: a prompt leaves no room for an answer "
                         "under max_total")
    return prompts, answers


def _requests(mix: dict, n: int, seed: int, vocab: int) -> List[Request]:
    prompts, answers = _sizes(mix, n)
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    order = rng.permutation(n)
    shared = mix.get("shared_prefix")
    prefixes = None
    if shared:
        prefixes = rng.integers(1, vocab,
                                (int(shared["groups"]), int(shared["tokens"])),
                                dtype=np.int64)
    out = []
    for i, j in enumerate(order):
        toks = rng.integers(1, vocab, int(prompts[j]), dtype=np.int64)
        if prefixes is not None and len(toks) > prefixes.shape[1]:
            g = int(rng.integers(0, prefixes.shape[0]))
            toks[:prefixes.shape[1]] = prefixes[g]
        out.append(Request(index=i, prompt=toks, max_new=int(answers[j])))
    return out


def open_loop(mix: dict, seed: int, seconds: float,
              vocab: int) -> List[Request]:
    """Requests with their due times, all inside the window."""
    n = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
    reqs = _requests(mix, n, seed, vocab)
    gaps = np.random.default_rng([int(mix["mix_seed"]), n, 1]).exponential(
        1.0, n)
    gaps *= seconds * n / (n + 1.0) / gaps.sum()
    order = np.random.default_rng([int(seed), 0xA221]).permutation(n)
    due = np.cumsum(gaps[order])
    for r, t in zip(reqs, due):
        r.due = float(t)
    return reqs


def closed_loop(mix: dict, seed: int, vocab: int) -> List[Request]:
    """The list the clients take their requests from, in turn."""
    return _requests(mix, int(mix["pool"]), seed, vocab)


def warm_requests(mix: dict, seed: int, vocab: int) -> List[Request]:
    """Requests sent before the window, so that every program and every
    host path the mix takes has run once: one at each length the mix lists
    under ``warm`` (``[prompt, answer]`` pairs)."""
    rng = np.random.default_rng([int(seed), 0x3A23])
    return [Request(index=-1 - i,
                    prompt=rng.integers(1, vocab, int(p), dtype=np.int64),
                    max_new=int(a))
            for i, (p, a) in enumerate(mix.get("warm", []))]
