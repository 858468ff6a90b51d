"""Speculative-decoding benchmark: draft/verify rounds vs sequential.

The same seeded request burst is served by
:class:`repro.launch.serve.ContinuousBatchingEngine` sequentially
(``spec_k=None``) and speculatively (truncated-layer self-draft + one
fused multi-query verify round per ``spec_k`` tokens,
``cfg.quant.draft_layers`` draft layers), at several slot counts — each
speculative row is compared against the sequential baseline *at its own
slot count*, on the same engine geometry, model, and traffic.

Because acceptance is exact (integer ``==`` against the verify argmax)
the spec engines must reproduce the sequential engine's tokens **bit
for bit**; the benchmark asserts that per request and reports it as
``bitwise`` per row — a speedup row with ``bitwise: false`` is a
correctness bug, not a trade-off.

The sweep shows the classic speculation economics: the win is largest
at slots=1 (the latency-bound regime — per-round fixed costs amortize
across the k verify positions while the sequential lane pays them per
token) and shrinks as slots grow and per-row compute fills the step.
Per row the CSV/JSON report decode throughput, rounds, acceptance
rate, tokens per round, and speedup; ``BENCH_spec.json`` (repo root)
carries the full records.

CPU-container caveat: absolute tok/s are emulation-tier numbers; the
*ratio* is the point. On real accelerators the analogous fixed costs
are kernel launches and the per-step HBM weight/cache streams
(docs/serving.md#speculative-decoding--bitwise-exact-draftverify-rounds).

``REPRO_SPEC_BENCH_FAST=1`` shrinks the sweep to a CI smoke
(sequential + k=2 at slots=1 on a short burst) — same engines, same
bitwise assertion.
"""

from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

_OUT = os.path.join(os.path.dirname(__file__), "..", "BENCH_spec.json")

_MAX_LEN = 128
_BUCKETS = [16]
_N_REQUESTS = 8
_MAX_NEW = 40
# (slots, spec_k, draft_layers); spec_k None = the sequential baseline
_SWEEP = ((1, None, 0), (1, 2, 1), (1, 4, 1), (1, 8, 1), (1, 8, 2),
          (2, None, 0), (2, 8, 1),
          (4, None, 0), (4, 8, 1))
_SWEEP_FAST = ((1, None, 0), (1, 2, 1))


def _fast() -> bool:
    return bool(os.environ.get("REPRO_SPEC_BENCH_FAST"))


def _traffic(cfg, n_requests, max_new, seed=3):
    """Seeded burst: mixed prompt lengths, all admissible at t=0."""
    from repro.launch.serve import Request

    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab,
                                        int(rng.integers(4, 15)))
                    .astype(np.int32),
                    max_new_tokens=max_new)
            for i in range(n_requests)]


def _serve(cfg, mesh, params, dims, slots, spec_k, n_requests, max_new):
    """Best-of-N serves of the same burst on one warmed engine.

    Decode here is host-dispatch-bound, so a busy container can halve a
    single serve's throughput; the max over repeats estimates the
    uncontended rate the same way for every row (sequential and
    speculative alike). The engine's determinism contract makes the
    repeats byte-for-byte replays — asserted below.
    """
    from repro.launch.serve import ContinuousBatchingEngine

    eng = ContinuousBatchingEngine(cfg, mesh, slots=slots,
                                   max_len=_MAX_LEN, params=params,
                                   dims=dims, spec_k=spec_k)
    eng.warmup(_BUCKETS, max_new=4)
    repeats = 1 if _fast() else 3
    best_stats, tokens = None, None
    for _ in range(repeats):
        reqs = _traffic(cfg, n_requests, max_new)
        stats = eng.serve(reqs)
        toks = {r.rid: list(r.out_tokens) for r in reqs}
        assert tokens is None or toks == tokens, \
            "serve repeats diverged — determinism bug"
        tokens = toks
        if (best_stats is None or stats["decode_tok_per_s"]
                > best_stats["decode_tok_per_s"]):
            best_stats = stats
    return best_stats, tokens


def run(csv):
    import jax
    from repro.configs import reduced_config
    from repro.launch.mesh import make_mesh
    from repro.models import init_params
    from repro.quant.config import FP8_MGS_SERVE_PAGED

    q = FP8_MGS_SERVE_PAGED.replace(use_kernel=False, block_m=32,
                                    block_n=32, block_k=32)
    base_cfg = dataclasses.replace(reduced_config("deepseek-7b"), quant=q)
    params, dims = init_params(base_cfg, jax.random.PRNGKey(0))
    mesh = make_mesh((1, 1), ("data", "model"))

    sweep = _SWEEP_FAST if _fast() else _SWEEP
    n_requests = 4 if _fast() else _N_REQUESTS
    max_new = 12 if _fast() else _MAX_NEW

    record = {"n_requests": n_requests, "max_new": max_new,
              "buckets": _BUCKETS, "fast": _fast(), "rows": {}}
    seq = {}          # slots -> (tok/s, tokens) of the sequential row
    best = (0.0, None)
    for slots, spec_k, dl in sweep:
        if spec_k is None:
            name, cfg = f"slots{slots}_sequential", base_cfg
        else:
            name = f"slots{slots}_k{spec_k}_dl{dl}"
            cfg = dataclasses.replace(
                base_cfg, quant=q.replace(draft_layers=dl))
        stats, tokens = _serve(cfg, mesh, params, dims, slots, spec_k,
                               n_requests, max_new)
        row = {"slots": slots,
               "decode_tok_per_s": stats["decode_tok_per_s"],
               "decode_tokens": stats["decode_tokens"],
               "steps": stats["steps"]}
        if spec_k is None:
            seq[slots] = (row["decode_tok_per_s"], tokens)
            derived = f"steps={stats['steps']}"
        else:
            sp = stats["spec"]
            seq_tps, seq_tokens = seq[slots]
            bitwise = tokens == seq_tokens
            assert bitwise, (
                f"{name}: speculative tokens diverged from the "
                f"sequential baseline — exact-acceptance bug")
            row.update(
                acceptance_rate=sp["acceptance_rate"],
                tokens_per_round=stats["decode_tokens"]
                / max(stats["steps"], 1),
                speedup_vs_sequential=row["decode_tok_per_s"] / seq_tps,
                bitwise=bitwise)
            if row["speedup_vs_sequential"] > best[0]:
                best = (row["speedup_vs_sequential"], name)
            derived = (f"speedup={row['speedup_vs_sequential']:.2f}x "
                       f"acc={sp['acceptance_rate']:.2f} "
                       f"tpr={row['tokens_per_round']:.2f} "
                       f"bitwise={'yes' if bitwise else 'NO'}")
        record["rows"][name] = row
        csv.add(f"spec/{name}",
                1e6 / max(row["decode_tok_per_s"], 1e-9), derived)
    record["best_speedup"] = best[0]
    record["best_config"] = best[1]
    csv.add("spec/best", 0.0,
            f"{best[1]}={best[0]:.2f}x over sequential at equal slots")
    if not _fast():
        # the CI smoke must not clobber the tracked full-sweep record
        with open(_OUT, "w") as f:
            json.dump(record, f, indent=1)
        csv.add("spec/record_file", 0.0, os.path.abspath(_OUT))
