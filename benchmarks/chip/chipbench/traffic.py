"""One general generator of serving traffic from a mix's parameters.

A mix is a JSON file (``traffic/<name>.json``); this module turns it and
a seed into a fixed list of requests. Every seed gets the same schedule:
lengths and gaps are the distribution's quantiles at ``(i + 0.5) / n``
(a stratified draw, not a random one), in one fixed shuffled order. The
seed draws the content: token ids, which requests decode greedily, and
each request's sampling seed. Two seeds thus ask for the same work in
the same order; a window is shorter than a chat request's life, so an
order drawn per seed moved the work that fell inside it by several
percent (measured on the chip).

Parameters (all but ``loop`` optional):

* ``loop``: ``"open"`` (arrivals on a schedule, whatever the system
  does) or ``"closed"`` (``outstanding`` requests in flight; the next is
  sent when one finishes).
* ``rate_per_s`` (open): mean arrival rate; ``floor(rate * seconds)``
  requests are due within the window, their gaps the quantiles of an
  exponential (the gaps of Poisson arrivals).
* ``outstanding``, ``requests`` (closed): requests in flight, and the
  length of the list they are drawn from in order.
* ``prompt_len``, ``output_len``: ``{"median", "sigma", "min", "max"}``
  of a clipped lognormal.
* ``in_flight``: requests already being served when the window opens,
  so that it measures a server in its steady state and not one filling
  up: their output lengths are length-biased, as a steady state holds
  them, and each has progressed a stratified share ``u`` of its output
  (the tokens it has produced join its prompt; it asks for the rest).
  They are sent and admitted during set-up, are never due in the window,
  and count toward ``outstanding`` in a closed loop.
* ``temperature`` and ``greedy_share``: requests sample at the
  temperature, except a share (rounded) that decode greedily; with
  ``temperature`` 0 all are greedy.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

SEED_BITS = 31        # per-request sampling seeds fit an int32
SCHEDULE_SEED = 20251   # the one order of every seed's lengths and gaps


@dataclasses.dataclass
class Item:
    """One request as the generator makes it."""
    index: int
    due_s: float          # offset from the window's start (open loop)
    prompt: np.ndarray    # int32 token ids
    max_new: int
    temperature: float
    sample_seed: int

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


@dataclasses.dataclass
class Plan:
    """The requests of one run, in the order they are sent."""
    loop: str
    items: list
    outstanding: int = 0
    in_flight: list = dataclasses.field(default_factory=list)


def load(path) -> dict:
    return json.loads(Path(path).read_text())


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified draws of a clipped lognormal."""
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    vals = spec["median"] * np.exp(spec.get("sigma", 0.0) * np.array(z))
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(int)


def prompt_bounds(mix: dict) -> tuple[int, int]:
    """Shortest and longest prompt the mix can send."""
    return int(mix["prompt_len"]["min"]), int(mix["prompt_len"]["max"])


def output_bounds(mix: dict) -> tuple[int, int]:
    return int(mix["output_len"]["min"]), int(mix["output_len"]["max"])


def _arrivals(mix: dict, n: int, order) -> np.ndarray:
    """Due times of ``n`` open-loop arrivals: exponential gaps at their
    quantiles, shuffled, summed."""
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    gaps = order.permutation(gaps) / float(mix["rate_per_s"])
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> Plan:
    """The requests of one run of ``mix`` with ``seed`` over a window of
    ``seconds``; token ids are drawn below ``vocab``."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(SCHEDULE_SEED)
    if mix["loop"] == "open":
        n = max(1, int(math.floor(mix["rate_per_s"] * seconds)))
        due = _arrivals(mix, n, order)
    else:
        n = int(mix["requests"])
        due = np.zeros(n)
    plens = order.permutation(_quantiles(mix["prompt_len"], n))
    olens = order.permutation(_quantiles(mix["output_len"], n))
    temp = float(mix.get("temperature", 0.0))
    n_greedy = n if temp == 0.0 else \
        int(round(n * float(mix.get("greedy_share", 0.0))))
    greedy = np.zeros(n, bool)
    greedy[rng.permutation(n)[:n_greedy]] = True
    items = [Item(index=i, due_s=float(due[i]),
                  prompt=rng.integers(0, vocab, int(plens[i]),
                                      dtype=np.int32),
                  max_new=int(olens[i]),
                  temperature=0.0 if greedy[i] else temp,
                  sample_seed=int(rng.integers(2 ** SEED_BITS)))
             for i in range(n)]
    return Plan(loop=mix["loop"], items=items,
                outstanding=int(mix.get("outstanding", 0)),
                in_flight=_in_flight(mix, int(mix.get("in_flight", 0)),
                                     rng, order, vocab, n))


def _length_biased(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified output lengths of the requests in flight at a
    random moment of a steady state: a request is in flight for as long
    as it decodes, so a length ``L`` is met in proportion to ``L``."""
    fine = np.sort(_quantiles(spec, 4096))
    cdf = np.cumsum(fine) / fine.sum()
    return fine[np.searchsorted(cdf, [(i + 0.5) / n for i in range(n)])]


def _in_flight(mix: dict, n: int, rng, order, vocab: int,
               first: int) -> list:
    """``n`` requests a share ``u`` of the way through their output, as
    a steady state holds them (output lengths length-biased)."""
    if not n:
        return []
    plens = order.permutation(_quantiles(mix["prompt_len"], n))
    olens = order.permutation(_length_biased(mix["output_len"], n))
    done = order.permutation([(i + 0.5) / n for i in range(n)])
    temp = float(mix.get("temperature", 0.0))
    greedy = np.zeros(n, bool)
    greedy[rng.permutation(n)[:int(round(
        n * (1.0 if temp == 0.0 else float(mix.get("greedy_share", 0.0)))))]] \
        = True
    out = []
    for i in range(n):
        made = int(done[i] * (olens[i] - 1))
        prompt = rng.integers(0, vocab, int(plens[i]) + made, dtype=np.int32)
        out.append(Item(index=first + i, due_s=-1.0, prompt=prompt,
                        max_new=int(olens[i]) - made,
                        temperature=0.0 if greedy[i] else temp,
                        sample_seed=int(rng.integers(2 ** SEED_BITS))))
    return out
