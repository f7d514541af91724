"""The one traffic generator: a mix is a JSON file of parameters.

Every request is a shared prefix (a document, a system prompt) chosen from
a small set, then a suffix of its own, then an answer of a stated length,
decoded greedily. The file states:

  * ``loop``: ``"closed"`` (``clients`` callers, each sends its next
    request when its answer is complete) or ``"open"`` (Poisson arrivals
    at ``rate_per_s``, sent on schedule whatever the server does);
  * ``prefixes``: ``count``, ``tokens``, ``choice`` (``"uniform"`` or
    ``{"zipf": s}``) and ``prefill_at_setup``;
  * ``suffix_tokens``, ``output_tokens``: ``{"dist": "uniform", "min",
    "max"}`` or ``{"dist": "lognormal", "median", "sigma", "min", "max"}``
    (clipped);
  * ``first_output_tokens`` (closed loop): answer lengths of each
    client's first request, spread so that the window opens on lanes at
    every stage of their answers rather than all at the first token;
  * ``preroll_s`` (open loop): seconds of arrivals before the window;
  * ``shape_seed``, ``pool``: the sizes and gaps are drawn once from
    ``shape_seed``; ``--seed`` only permutes them and draws the token ids,
    so every seed serves the same work in another order. An open loop's
    ``pool`` is its number of arrivals; their gaps are scaled to span
    exactly ``pool / rate_per_s`` seconds from ``-preroll_s``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Req:
    prefix: int                  # which shared prefix
    prompt: np.ndarray           # prefix ++ suffix, int32
    out_len: int
    due: Optional[float] = None  # open loop: seconds after the window opens


def _draw(rng: np.random.Generator, d: Dict, n: int) -> np.ndarray:
    if d["dist"] == "uniform":
        v = rng.integers(d["min"], d["max"] + 1, n)
    elif d["dist"] == "lognormal":
        v = np.round(d["median"] * np.exp(d["sigma"] * rng.standard_normal(n)))
    else:
        raise ValueError(f"unknown distribution {d['dist']!r}")
    return np.clip(v, d["min"], d["max"]).astype(np.int64)


def _choice(rng: np.random.Generator, choice, count: int, n: int):
    if choice == "uniform":
        return rng.integers(0, count, n)
    if isinstance(choice, dict) and "zipf" in choice:
        w = 1.0 / np.arange(1, count + 1) ** choice["zipf"]
        return rng.choice(count, n, p=w / w.sum())
    raise ValueError(f"unknown prefix choice {choice!r}")


class Traffic:
    """The requests of one run, from the mix's parameters and ``--seed``."""

    def __init__(self, spec: Dict, seed: int, vocab: int):
        self.spec = spec
        if spec["loop"] not in ("closed", "open"):
            raise ValueError(f"loop must be closed|open, got {spec['loop']}")
        self.closed = spec["loop"] == "closed"
        n = spec["pool"]
        warm = spec["clients"] if self.closed else 0
        shape = np.random.default_rng(spec["shape_seed"])
        pre = spec["prefixes"]
        prefix_ids = _choice(shape, pre["choice"], pre["count"], n + warm)
        suffix = _draw(shape, spec["suffix_tokens"], n + warm)
        outs = _draw(shape, spec["output_tokens"], n)
        if self.closed:
            outs = np.concatenate(
                [_draw(shape, spec["first_output_tokens"], warm), outs])
        rng = np.random.default_rng(seed)
        # the warm-start requests stay first; each part is permuted alone
        order = np.concatenate([rng.permutation(warm),
                                warm + rng.permutation(n)])
        prefix_ids, suffix, outs = prefix_ids[order], suffix[order], \
            outs[order]
        if self.closed:
            due = [None] * (n + warm)
        else:
            # n Poisson gaps, scaled to sum to exactly n / rate: every seed
            # sends the same n requests over the same span
            gaps = shape.exponential(1.0, n)
            gaps *= n / spec["rate_per_s"] / gaps.sum()
            due = np.cumsum(gaps[rng.permutation(n)]) - spec["preroll_s"]
        self.prefixes = [rng.integers(0, vocab, pre["tokens"]).astype(np.int32)
                         for _ in range(pre["count"])]
        self.requests: List[Req] = []
        for i in range(n + warm):
            tail = rng.integers(0, vocab, suffix[i]).astype(np.int32)
            self.requests.append(Req(
                prefix=int(prefix_ids[i]),
                prompt=np.concatenate([self.prefixes[prefix_ids[i]], tail]),
                out_len=int(outs[i]),
                due=None if due[i] is None else float(due[i])))

    @property
    def prefill_prefixes(self) -> bool:
        return bool(self.spec["prefixes"].get("prefill_at_setup", False))

    @property
    def max_total_tokens(self) -> int:
        """Longest prompt + answer this mix can send (bounds ``max_len``)."""
        s = self.spec
        return (s["prefixes"]["tokens"] + s["suffix_tokens"]["max"]
                + max(s["output_tokens"]["max"],
                      s.get("first_output_tokens", {"max": 0})["max"]))
