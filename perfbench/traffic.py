"""The one traffic generator: a traffic file's parameters plus ``--seed``
give a schedule of requests.

What repeats from run to run is fixed by the FILE, never by the seed:

- the multiset of (prompt length, answer length) pairs — quantiles of the
  distribution the file states, paired by the file's own constant;
- how many requests are due in the window (``round(rate * seconds)``) and
  in the pre-roll, and so the tokens offered and the count per prefill
  bucket.

``--seed`` only permutes the order of the pairs (each repetition of the
multiset by itself), draws the token ids and draws where inside its pacing
interval each arrival falls. So every seed
offers the same work, in another order (PERF.md, "What makes a run repeat").

Kinds of serving traffic:

- ``open_loop``: arrival ``i`` is due at ``(i + u_i) / rate`` seconds with
  ``u_i`` uniform in [0, 1) — one replica's share of a fleet behind a
  round-robin router. ``i`` runs from ``-round(rate * preroll_s)`` (the
  pre-roll, due before the window opens at 0) to ``round(rate*seconds) - 1``.
- ``closed_loop``: no due times; the harness keeps the engine's queue
  ``queue_depth_slots * max_slots`` deep from an endless sequence, each
  repetition of the multiset in a new seeded order.

No jax here: the invariants are tested on the CPU in milliseconds.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass
from typing import Iterator, List, Sequence, Tuple

import numpy as np

_TAG_ORDER, _TAG_TOKENS, _TAG_PHASE = 1, 2, 3


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def quantiles(spec: dict, n: int) -> List[int]:
    """``n`` values at the mid-quantiles ``(k + 0.5) / n`` of the piecewise
    distribution ``spec``: ``knots`` are ``[quantile, value]`` pairs, joined
    in ``space`` ``"log"`` or ``"linear"``."""
    knots = sorted((float(q), float(v)) for q, v in spec["knots"])
    if knots[0][0] != 0.0 or knots[-1][0] != 1.0:
        raise ValueError("knots must run from quantile 0 to quantile 1")
    log = spec.get("space", "linear") == "log"
    qs = [k[0] for k in knots]
    vs = [math.log(k[1]) if log else k[1] for k in knots]
    out = []
    for k in range(n):
        x = float(np.interp((k + 0.5) / n, qs, vs))
        out.append(int(round(math.exp(x) if log else x)))
    return out


def multiset(traffic: dict) -> List[Tuple[int, int]]:
    """The file's fixed list of (prompt, answer) lengths, in the file's
    own canonical order (a shuffle by ``pairing_seed``, so that a prefix of
    it is spread over the whole distribution)."""
    ms = traffic["multiset"]
    n = int(ms["size"])
    prompts, answers = quantiles(ms["prompt"], n), quantiles(ms["answer"], n)
    const = np.random.default_rng([int(ms["pairing_seed"]), 0])
    answers = [answers[i] for i in const.permutation(n)]
    cap = int(ms["max_total"])
    pairs = [(p, min(a, cap - p)) for p, a in zip(prompts, answers)]
    if any(a < 1 for _, a in pairs):
        raise ValueError("a prompt leaves no room for an answer under max_total")
    return [pairs[i] for i in const.permutation(n)]


def repeated(pairs: Sequence, n: int) -> List[List]:
    """``n`` requests as repetitions of the multiset: whole ones, then the
    first ``n % len(pairs)`` of the canonical order. How the work scales
    with ``--seconds``: a function of the file and ``n`` alone. Each
    repetition is shuffled by itself, so the long and the short are spread
    over the run and not left to clump."""
    whole, rest = divmod(n, len(pairs))
    return [list(pairs)] * whole + ([list(pairs[:rest])] if rest else [])


@dataclass(frozen=True)
class Arrival:
    index: int          # position in the pacing sequence; < 0 is pre-roll
    due_s: float        # seconds from the window's opening; None-free
    prompt: Tuple[int, ...]
    max_new_tokens: int

    @property
    def in_window(self) -> bool:
        return self.index >= 0


def _rng(seed: int, tag: int) -> np.random.Generator:
    # a list seeds through SeedSequence: any whole number, however large
    return np.random.default_rng([int(seed), tag])


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> Tuple[int, ...]:
    return tuple(int(t) for t in rng.integers(1, vocab, size=n))


def counts(traffic: dict, seconds: float) -> Tuple[int, int]:
    """(pre-roll arrivals, window arrivals) of an open loop."""
    rate = float(traffic["rate_per_s"])
    return (int(round(rate * float(traffic["preroll_s"]))),
            int(round(rate * float(seconds))))


def open_loop_schedule(traffic: dict, seed: int, seconds: float,
                       vocab: int) -> List[Arrival]:
    """Every arrival of one run, pre-roll first, in due order."""
    rate = float(traffic["rate_per_s"])
    n_pre, n_win = counts(traffic, seconds)
    pairs = multiset(traffic)
    order, toks, phase = (_rng(seed, t) for t in
                          (_TAG_ORDER, _TAG_TOKENS, _TAG_PHASE))
    out = []
    for first, n in ((-n_pre, n_pre), (0, n_win)):
        chosen = [rep[i] for rep in repeated(pairs, n)
                  for i in order.permutation(len(rep))]
        for k, (plen, alen) in enumerate(chosen):
            i = first + k
            out.append(Arrival(i, (i + float(phase.random())) / rate,
                               _tokens(toks, plen, vocab), alen))
    return out


def closed_loop_stream(traffic: dict, seed: int,
                       vocab: int) -> Iterator[Arrival]:
    """Endless requests for a closed loop: the multiset again and again,
    each repetition in a new seeded order. ``due_s`` is 0: a closed loop
    has no schedule, the harness submits when the queue has room."""
    pairs = multiset(traffic)
    order, toks = _rng(seed, _TAG_ORDER), _rng(seed, _TAG_TOKENS)
    i = 0
    while True:
        for j in order.permutation(len(pairs)):
            plen, alen = pairs[j]
            yield Arrival(i, 0.0, _tokens(toks, plen, vocab), alen)
            i += 1


def bucket_for(length: int, buckets: Sequence[int]) -> int:
    """The prefill bucket a prompt of ``length`` pads to (the engine's
    rule: the smallest bucket that holds it)."""
    for b in sorted(buckets):
        if length <= b:
            return b
    raise ValueError(f"prompt of {length} tokens exceeds every bucket")


def buckets_used(traffic: dict, buckets: Sequence[int]) -> List[int]:
    """The prefill buckets this traffic reaches: the shapes to warm."""
    return sorted({bucket_for(p, buckets) for p, _ in multiset(traffic)})


def schedule_bytes(arrivals: Sequence[Arrival]) -> bytes:
    """A schedule as bytes, for the same-seed-same-bytes test."""
    return json.dumps([[a.index, repr(a.due_s), a.prompt, a.max_new_tokens]
                       for a in arrivals]).encode()
