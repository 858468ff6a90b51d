"""Find a cell's knee once, on the chip: the highest offered rate that the
engine sustains without a growing backlog.

    python3 bench/sweep.py --workload <name> --rates 0.7,0.85,1.0 --seconds 51

One engine, built once; for each rate the cell's mix (its lead-in
included, so the engine is loaded when the window opens) is offered at
that rate and cut at the window's end. A rate is sustained when the
requests waiting for admission at the window's end are at most one more
than at its start, and the later half of the window's requests waited no
more than twice as long for their first token (90th percentile) as the
earlier half. Prints one JSON line per rate. Not run by the benchmark's
own runs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run as bench_run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    bench_run.configure_jax()
    from bench import cell as cell_mod
    from bench import traffic
    bench, cell, config, mix = cell_mod.load_cell(args.workload)
    cell_mod.require_chip(cell["chips"], cell_mod.load_peaks())
    engine = cell_mod.build_engine(cell_mod.model_config(config), mix,
                                   cell_mod.weight_seed(args.seed))
    for rate in (float(r) for r in args.rates.split(",")):
        m = dict(mix, rate_per_s=rate, end="stop")
        specs = traffic.generate(m, args.seed, args.seconds,
                                 config["vocab_size"])
        run = cell_mod.drive(engine, specs, m, args.seconds)
        due = cell_mod.due_times(run)
        win = cell_mod.window_requests(run)
        first = [r.out_tokens.t[0] if r.out_tokens.t else float("inf")
                 for r in run.reqs]

        def waiting(at):
            return sum(1 for d, f in zip(due, first) if d <= at < f)

        # a request never admitted waits for ever
        waits = [first[i] - due[i] for i in win]
        half = len(waits) // 2
        early, late = waits[:half], waits[half:]
        h = cell_mod.host_metrics(run, m)
        w0, w1 = waiting(run.t_start), waiting(run.t_end)
        ok = (w1 <= w0 + 1 and bool(late) and
              cell_mod.nearest_rank(late, 0.9)
              <= 2 * max(cell_mod.nearest_rank(early, 0.9), 1e-3))
        print(json.dumps({
            "rate_per_s": rate, "requests": len(win),
            "waiting_at_start": w0, "waiting_at_end": w1,
            "live_slots_mean": h["live_slots_mean"],
            "ttft_p50_s": cell_mod.nearest_rank(waits, 0.5),
            "ttft_p90_early_s": cell_mod.nearest_rank(early, 0.9),
            "ttft_p90_late_s": cell_mod.nearest_rank(late, 0.9),
            "itl_p99_ms": h["itl_p99_ms"], "output_tok_s": h["output_tok_s"],
            "sustained": ok}), flush=True)
        reset(engine)
    return 0


def reset(engine):
    """Drop the requests a cut window left in their slots: every slot and
    block free again, as after a drained ``serve()``."""
    import collections
    import jax.numpy as jnp
    c = engine.cache
    engine.cache = dict(c, pos=jnp.zeros_like(c["pos"]),
                        block_table=jnp.zeros_like(c["block_table"]))
    engine.alloc = type(engine.alloc)(engine.n_blocks)
    engine._free_slots = collections.deque(range(engine.slots))
    engine._cur[:] = 0
    engine._slot_amax[:] = 0.0


if __name__ == "__main__":
    sys.exit(main())
