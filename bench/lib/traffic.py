"""Request traffic, drawn from a traffic file and a seed.

A traffic file (``bench/traffic/<name>.json``) holds parameters only:

* ``loop``: ``"open"``: requests arrive on a schedule, at ``rate_per_s``
  (:func:`window_rate` gives the rate at which a window holds one block);
  or ``"closed"``: ``clients`` callers, each of which sends its next
  request ``think_s`` seconds after its last one finished. The loop
  runs ``lead_in_steps`` engine steps before the window opens (the
  lead-in is neither timed nor counted in the set-up), so that the
  window opens on a loaded engine, and at the same point of the step
  sequence in every run: with no think time the sequence is the same
  in every run, and a window that opened at a set time would meet it at
  another phase each time. Request ``i`` is the ``i``-th sent, whichever
  client sends it;
* ``prompt_len`` and ``output_len``: lognormal distributions, each given
  by ``median``, ``sigma`` and the clip ``[min, max]``;
* ``block``: how many consecutive requests form one stratified block.

Lengths and arrival gaps are stratified: each block of ``block`` requests
takes the distribution's quantiles at ``(i + 0.5) / block``, in an order
drawn from the block's index alone. Every seed thus offers the same
schedule of lengths and arrivals; the seed draws the token ids, uniform
over ``[2, vocab)`` (ids 0 and 1 are the pad and end-of-sequence ids of
the served models). A window holds a few dozen requests, and there the
order of the gaps decides the queueing: with the order drawn from the
seed, the p90 of the time to first token would differ between seeds by
far more than between two runs of one seed. A closed loop has no
schedule: with no think time its requests are sent as steps finish, in
an order that the lengths alone decide.
"""
from __future__ import annotations

import json
import math
import pathlib
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np

__all__ = ["LengthDist", "Traffic", "load_traffic", "window_rate"]

_NORMAL = NormalDist()
_STREAM_PROMPT, _STREAM_OUTPUT, _STREAM_GAP, _STREAM_TOKENS = 1, 2, 3, 4


@dataclass(frozen=True)
class LengthDist:
    """A lognormal length distribution, clipped to ``[lo, hi]``."""

    median: float
    sigma: float
    lo: int
    hi: int

    @classmethod
    def from_spec(cls, spec: Dict) -> "LengthDist":
        return cls(float(spec["median"]), float(spec["sigma"]),
                   int(spec["min"]), int(spec["max"]))

    def quantiles(self, n: int) -> np.ndarray:
        """The ``n`` stratified quantiles, rounded and clipped."""
        mu = math.log(self.median)
        q = [math.exp(mu + self.sigma * _NORMAL.inv_cdf((i + 0.5) / n))
             for i in range(n)]
        return np.clip(np.rint(q), self.lo, self.hi).astype(np.int64)


def _unit_gaps(block: int) -> List[float]:
    """A block's stratified arrival gaps at one request a second: the
    exponential distribution's quantiles at ``(k + 0.5) / block``."""
    return [-math.log(1.0 - (k + 0.5) / block) for k in range(block)]


def window_rate(block: int, seconds: float) -> float:
    """The open-loop rate at which exactly one block of requests arrives
    in a window of ``seconds``: the block's last request half its
    smallest gap before the close, the next block's first after it. Every
    seed then offers the window the same requests and gaps."""
    gaps = _unit_gaps(block)
    return (sum(gaps) + min(gaps) / 2) / seconds


class Traffic:
    """The request stream of one traffic file under one seed.

    Request ``i`` has ``prompt_len(i)`` prompt tokens, asks for
    ``output_len(i)`` tokens and arrives ``arrival(i)`` seconds after
    the window opens.
    """

    def __init__(self, spec: Dict, seed: int, vocab: int):
        self.loop = spec["loop"]
        if self.loop not in ("open", "closed"):
            raise ValueError(f"unknown loop {self.loop!r}")
        self.spec = spec
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.block = int(spec.get("block", 64))
        self.prompt = LengthDist.from_spec(spec["prompt_len"])
        self.output = LengthDist.from_spec(spec["output_len"])
        if self.loop == "open":
            self.rate = float(spec["rate_per_s"])
        else:
            self.clients = int(spec["clients"])
            self.think_s = float(spec["think_s"])
            self.lead_in_steps = int(spec["lead_in_steps"])
            if self.clients < 1 or self.think_s < 0 or \
                    self.lead_in_steps < 0:
                raise ValueError(f"a closed loop needs clients >= 1 and "
                                 f"think_s, lead_in_steps >= 0: {spec}")
        self._blocks: Dict[tuple, np.ndarray] = {}
        self._arrivals: List[float] = []

    def _strat(self, stream: int, dist_q: np.ndarray, i: int):
        b, j = divmod(i, self.block)
        key = (stream, b)
        if key not in self._blocks:
            rng = np.random.default_rng([stream, b])
            self._blocks[key] = rng.permutation(dist_q)
        return self._blocks[key][j]

    def prompt_len(self, i: int) -> int:
        return int(self._strat(_STREAM_PROMPT,
                               self.prompt.quantiles(self.block), i))

    def output_len(self, i: int) -> int:
        return int(self._strat(_STREAM_OUTPUT,
                               self.output.quantiles(self.block), i))

    def tokens(self, i: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, _STREAM_TOKENS, i])
        return rng.integers(2, self.vocab, size=self.prompt_len(i),
                            dtype=np.int32)

    def arrival(self, i: int) -> float:
        """Scheduled arrival of request ``i`` (seconds); open loop only."""
        gaps = [g / self.rate for g in _unit_gaps(self.block)]
        while len(self._arrivals) <= i:
            k = len(self._arrivals)
            prev = self._arrivals[-1] if self._arrivals else 0.0
            self._arrivals.append(prev + float(
                self._strat(_STREAM_GAP, np.asarray(gaps), k)))
        return self._arrivals[i]

    def clip_buckets(self, bucket, chunk: Optional[int] = None) -> List:
        """The distinct values of ``bucket(n)`` over every length that a
        prefill launch can take: every prompt length the clip allows or,
        where the engine splits prompts into chunks of at most ``chunk``
        tokens, every chunk of such a prompt; each with the largest such
        length: ``[(bucket, length), ...]`` in increasing order."""
        lens = set()
        for n in range(self.prompt.lo, self.prompt.hi + 1):
            while chunk is not None and n > chunk:
                lens.add(chunk)
                n -= chunk
            lens.add(n)
        out: Dict[int, int] = {}
        for n in sorted(lens):
            out[bucket(n)] = n
        return sorted(out.items())


def load_traffic(root: pathlib.Path, name: str) -> Dict:
    return json.loads((root / "bench" / "traffic" / f"{name}.json")
                      .read_text())
