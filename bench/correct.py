"""The comparison that decides ``correct``.

A sample of the requests served through the window is drawn from the
seed before the window opens (the one with the longest output, then
three others at random, all from the lead-in's arrivals where the mix
has one), and the engine's own logits-recording seam keeps the logit
rows it served for those requests alone; every token it served up to the
end of the run counts, also of a request that the end cut. Once the
window has closed and the engine is freed, each sampled request is run through the plain float32
reference of the configuration's family (``bench/reference/<family>.py``)
as the tokens the engine consumed: its prompt left-padded with token 0
to its bucket, then its served tokens. At every served position the
number compared is the relative error of the program's logit row against
the reference's, ``|prog - ref| / |ref|`` over the vocabulary; the run
is correct when the widest of them is within the limit in the
configuration file.

Why logits and not the greedy tokens' gaps: with the seeded random
weights and the tied embedding, the residual stream stays dominated by
the current token's embedding, so the logits head ranks the current
token first by tens of logits and greedy decoding repeats it. A served
token's gap below the reference's best is then 0 for the program, for
the int4 control and for a decode step that drops its cache update
alike, and separates nothing (PERF.md, correctness).
"""

from __future__ import annotations

import importlib
from typing import Dict, List

import numpy as np

#: sampled requests: with the runs' cut at the window's end (or at the
#: last first token), some hundreds of served tokens
SAMPLE_SEQS = 4


class SampleLog(dict):
    """``engine._logits_log`` that keeps the rows of sampled requests only."""

    class _Sink(list):
        def append(self, row):
            pass

    def __init__(self, rids):
        super().__init__()
        self.keep = set(rids)
        self._sink = self._Sink()

    def setdefault(self, rid, default=None):
        if rid in self.keep:
            return super().setdefault(rid, [] if default is None else default)
        return self._sink


def pre_sample(specs, seed: int, before: float = 0.0) -> List[int]:
    """Indices of the sampled requests, drawn before the window opens.

    Drawn from the requests due before ``before`` (the lead-in: served
    through the window, so each holds some hundreds of tokens at its
    end), or from all where none is: the one with the longest output,
    then the rest at random from the seed.
    """
    pool = [i for i, s in enumerate(specs) if s.due_s < before] \
        or list(range(len(specs)))
    if not pool:
        return []
    longest = max(pool, key=lambda i: (specs[i].max_new, -i))
    rest = [pool[j] for j in np.random.default_rng(seed).permutation(len(pool))
            if pool[j] != longest]
    return [longest] + [int(i) for i in rest[:SAMPLE_SEQS - 1]]


def compare(config: dict, wseed: int, run, length: int,
            control: bool = False) -> Dict[str, float]:
    """Widest relative logit error of the sample (and the control's)."""
    ref = importlib.import_module(f"bench.reference.{config['family']}")
    logs = run.logs
    seqs, rows, prog = [], [], []
    for i in run.sample:
        r, b = run.reqs[i], run.buckets[i]
        n = len(r.out_tokens)
        if n == 0 or len(logs.get(i, ())) != n:
            continue
        pad = np.zeros(b - len(r.prompt), np.int32)
        seqs.append(np.concatenate(
            [pad, r.prompt, np.asarray(list(r.out_tokens)[:-1], np.int32)]))
        rows.append(np.arange(b - 1, b - 1 + n))
        prog.append(np.stack(logs[i]))
    out = {"sampled_requests": len(seqs),
           "sampled_tokens": int(sum(len(r) for r in rows))}
    if not seqs:
        # nothing to compare: not correct
        out["max_logit_rel_err"] = None
        if control:
            out["control_max_logit_rel_err"] = None
        return out
    errs = ref.logit_errors(config, wseed, seqs, rows,
                            np.concatenate(prog).astype(np.float32), length,
                            control=control)
    out["max_logit_rel_err"] = float(errs["program"].max())
    if control:
        out["control_max_logit_rel_err"] = float(errs["control"].max())
    return out


def reference_length(mix: dict, block: int = 512) -> int:
    """The padded length the reference runs a cell's sequences at."""
    return -(-mix["engine"]["max_len"] // block) * block
