"""Traffic generator: one general generator for every mix in ``bench/traffic``.

A mix is a JSON file of parameters (see the files beside this one). The
generator gives every seed the same work in the same order: the prompt
lengths, output lengths and inter-arrival gaps are a stratified sample
of the mix's distributions (no randomness), put in one order drawn from
the mix's ``schedule_seed``. The run's seed draws the prompt token ids
alone (and, in the harness, the weights). So two seeds differ in the
tokens, never in the amount, sizes or timing of the work.

Kinds:

* ``open_loop``: requests due over ``lead_s + seconds`` at
  ``rate_per_s``, their gaps the stratified quantiles of an exponential
  distribution (``arrivals: "stratified_exponential"``: the gaps of a
  Poisson process, in a fixed order, not a random draw). The first
  ``lead_s`` seconds of arrivals fill the engine before the window
  opens; they count as set-up.
* ``resident``: ``requests`` requests all due at time 0; the window
  starts once every one of them holds its slot.

``end`` says when a run's ``serve()`` ends: ``stop`` at the window's
end; ``first_token`` once every request due in the window holds its
first token (at most ``drain_cap_s`` past the window; a request with no
token by then has failed); the tokens still being decoded are cut.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist
from typing import List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Spec:
    prompt_len: int
    max_new: int
    due_s: float        # offset from the start of serving (lead-in included)
    tokens: np.ndarray  # (prompt_len,) int32


def load_mix(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "traffic", name + ".json")) as f:
        return json.load(f)


def lead_s(mix: dict) -> float:
    """Seconds of arrivals served before the window opens."""
    return float(mix.get("lead_s", 0.0)) if mix["kind"] == "open_loop" else 0.0


def _stratified(dist: dict, n: int) -> List[int]:
    """``n`` lengths at the stratified quantiles (i + 0.5) / n of ``dist``."""
    if dist["dist"] == "fixed":
        return [int(dist["len"])] * n
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = NormalDist()
    out = []
    for i in range(n):
        v = dist["median"] * math.exp(dist["sigma"] * z.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(v), dist["min"]), dist["max"])))
    return out


def n_requests(mix: dict, seconds: float) -> int:
    if mix["kind"] == "resident":
        return int(mix["requests"])
    if mix["kind"] == "open_loop":
        return max(1, int(round(mix["rate_per_s"] * (lead_s(mix) + seconds))))
    raise ValueError(f"unknown traffic kind {mix['kind']!r}")


def due_offsets(mix: dict, n: int, seconds: float,
                rng: np.random.Generator) -> np.ndarray:
    """Due times in [0, lead_s + seconds): a fixed gap multiset in ``rng``'s
    order."""
    if mix["kind"] == "resident":
        return np.zeros(n)
    if mix["arrivals"] != "stratified_exponential":
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    span = lead_s(mix) + seconds
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    gaps = rng.permutation(gaps) * (span / gaps.sum())
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> List[Spec]:
    """The requests of one run, in due order: the schedule from the mix's
    ``schedule_seed``, the token ids from ``seed``."""
    n = n_requests(mix, seconds)
    sched = np.random.default_rng(mix["schedule_seed"])
    plens = sched.permutation(_stratified(mix["prompt"], n))
    outs = sched.permutation(_stratified(mix["output"], n))
    due = due_offsets(mix, n, seconds, sched)
    rng = np.random.default_rng(seed)
    return [Spec(int(p), int(o), float(t),
                 rng.integers(1, vocab, int(p)).astype(np.int32))
            for p, o, t in zip(plens, outs, due)]
