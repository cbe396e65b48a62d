"""The one traffic generator: every mix is a data file under ``traffic/``.

A serving mix draws prompts and arrivals from ``--seed``.  Token ids follow
the Zipf draw of ``repro.serving.loadgen`` (copied here, so the yardstick
does not move with the program).  Prompt lengths and arrival gaps come in
blocks whose *set* of values is the same for every seed, only their order
changes: two seeds then do the same work, and run-to-run spread is the
system's, not the draw's.  So an open loop's arrivals are not a Poisson
process but its stratified copy: within each block of 128 the gaps are
the quantiles of Exp(rate), shuffled, so every block spans the same
time and the count of arrivals in a window varies less than Poisson's.
"""

from __future__ import annotations

import hashlib
import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

BLOCK = 128    # lengths and gaps are permuted within blocks of this many


def load(path: Path) -> dict:
    doc = json.loads(Path(path).read_text())
    if not isinstance(doc.get("kind"), str):
        raise ValueError(f"{path}: a mix names its kind, the runner that "
                         f"reads it")
    return doc


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    """A numpy generator for any whole-number seed, large ones included."""
    return np.random.default_rng((int(seed) % (1 << 64),) + salt)


def jax_seed(seed: int) -> int:
    """A 31-bit seed for ``jax.random.key`` derived from any seed."""
    digest = hashlib.sha256(f"bench-weights|{int(seed)}".encode()).digest()
    return int.from_bytes(digest[:4], "big") >> 1


def stratified_lengths(lo: int, hi: int, n: int) -> np.ndarray:
    """``n`` lengths spread evenly over ``[lo, hi]``: a fixed multiset."""
    span = hi - lo + 1
    return lo + np.floor((np.arange(n) + 0.5) * span / n).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps at the quantiles of Exp(rate): a Poisson
    process's gaps as a fixed multiset, mean ``1 / rate``."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def zipf_tokens(rng: np.random.Generator, n: int, vocab: int,
                exponent: float) -> np.ndarray:
    """``repro.serving.loadgen.synth_requests``'s token draw."""
    ranks = rng.zipf(exponent, size=n).astype(np.int64)
    return np.minimum(ranks - 1, vocab - 1).astype(np.int32)


@dataclass(frozen=True)
class Prompt:
    rid: int
    tokens: np.ndarray


class PromptStream:
    """Prompts in the order they are sent: ``next()`` gives the next one."""

    def __init__(self, mix: dict, vocab: int, seed: int) -> None:
        self.lo, self.hi = (int(v) for v in mix["prompt_tokens"])
        self.vocab = vocab
        self.exponent = float(mix["zipf_exponent"])
        self._order = rng_for(seed, 1)
        self._tokens = rng_for(seed, 2)
        self._lengths: list[int] = []
        self._rid = 0

    def next(self) -> Prompt:
        if not self._lengths:
            block = stratified_lengths(self.lo, self.hi, BLOCK)
            self._lengths = list(self._order.permutation(block))
        n = int(self._lengths.pop())
        p = Prompt(self._rid, zipf_tokens(self._tokens, n, self.vocab,
                                          self.exponent))
        self._rid += 1
        return p


class ArrivalClock:
    """Open-loop due times, in seconds after the clock's start."""

    def __init__(self, rate: float, seed: int) -> None:
        self.rate = float(rate)
        self._order = rng_for(seed, 3)
        self._gaps: list[float] = []
        self.t = 0.0

    def next(self) -> float:
        if not self._gaps:
            self._gaps = list(self._order.permutation(
                exponential_gaps(self.rate, BLOCK)))
        self.t += float(self._gaps.pop())
        return self.t
