"""Run one benchmark cell once, on the chip it is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's engine (weights drawn on the device from the seed),
warms the cell's prompt buckets, serves the cell's traffic for the
window, then frees the engine and checks a sample of what was served
against the plain float32 reference (``bench/correct.py``). The last
line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics, read in a profiled span of
the window), ``device`` and, last, ``compared``: each number the
correctness check compared, with its limit. The same numbers are the
last lines of standard error.

It exits non-zero and prints no result when JAX finds no TPU, fewer
chips than the cell asks for or a device kind without peaks in
``bench/peaks.json``, and when anything compiles inside the window.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
#: the compile cache lives at a fixed path inside the checkout: the path is
#: part of each entry's key, and the program takes the directory given here
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def configure_jax():
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


class LayerContext:
    """What a per-layer metric reader may read (see bench/metrics)."""

    def __init__(self, *, config, mix, peak, run, red, window_s):
        from bench.cell import live_slots_mean, round_records
        self.config, self.mix, self.peak, self.trace = config, mix, peak, red
        self.run = run          # the host records (bench.cell.Run)
        self.slots = mix["engine"]["slots"]
        self.window_s = window_s
        self.window_prefills, self.window_decode_rounds = round_records(
            run, run.t_start, run.t_end)
        self.traced_prefills, self.traced_decode_rounds = (
            round_records(run, *run.trace_span) if run.trace_span
            else ([], []))
        #: live slots over the traced span, weighted by time
        self.traced_live_slots = (live_slots_mean(run, *run.trace_span)
                                  if run.trace_span else 0.0)


def per_layer(bench, cell, ctx) -> dict:
    from bench.metrics import module_name
    out = {}
    for m in bench["per_layer"]:
        if cell["name"] not in m.get("workloads", [cell["name"]]):
            continue
        v = importlib.import_module(
            f"bench.metrics.{module_name(m['name'])}").read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def end_to_end(bench, cell, host: dict, setup_s: float) -> dict:
    vals = dict(host, setup_s=setup_s)
    return {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
            for m in bench["end_to_end"]
            if cell["name"] in m.get("workloads", [cell["name"]])}


def run_cell(args, root: str = ROOT, control: bool = False) -> dict:
    from bench import cell as cell_mod
    from bench import correct, traffic
    from bench import trace as trace_mod
    bench, cell, config, mix = cell_mod.load_cell(args.workload, root)
    peaks = cell_mod.load_peaks()
    devs = cell_mod.require_chip(cell["chips"], peaks)
    peak = peaks[devs[0].device_kind]
    counter = cell_mod.CompileCounter()
    cfg = cell_mod.model_config(config)
    wseed = cell_mod.weight_seed(args.seed)
    specs = traffic.generate(mix, args.seed, args.seconds,
                             config["vocab_size"])
    t_build = time.monotonic()
    times = {"start_s": t_build - T_PROCESS}
    engine = cell_mod.build_engine(cfg, mix, wseed, times)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    try:
        run = cell_mod.drive(engine, specs, mix, args.seconds,
                             counter=counter, trace_dir=trace_dir,
                             sample=correct.pre_sample(
                                 specs, args.seed, traffic.lead_s(mix)))
        setup_s = run.t_start - T_PROCESS
        if run.compiles:
            raise cell_mod.BenchError(
                f"{run.compiles} compilation(s) inside the window "
                f"({sorted(set(counter.names))})")
        host = cell_mod.host_metrics(run, mix)
        # set-up split: process start to the build, engine build, bucket
        # warm-up, the traffic's lead-in (all inside setup_s)
        host["setup_split"] = dict(times, lead_s=run.t_start - run.t0)
        stats = devs[0].memory_stats() or {}
        # what serving holds: the most bytes in use at a round boundary of
        # the window (the engine's build peaks higher, with the raw float
        # weights, before the window; that peak is kept beside it)
        host["construction_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs),
                  "memory_peak_bytes": host["bytes_in_use_max"]}
        breakdown = None
        if args.trace:
            red = trace_mod.reduce(trace_mod.load(
                trace_mod.find_xplane(trace_dir)))
            ctx = LayerContext(config=config, mix=mix, peak=peak, run=run,
                               red=red, window_s=run.t_end - run.t_start)
            trace_mod.check(red, prefills=len(ctx.traced_prefills),
                            decode_rounds=len(ctx.traced_decode_rounds))
            metrics = per_layer(bench, cell, ctx)
            device.update(busy_s=red.busy_s,
                          window_s=run.trace_span[1] - run.trace_span[0])
            breakdown = {"device_ops": [list(x) for x in red.device_ops],
                         "idle_gaps": [list(x) for x in red.idle_gaps]}
            host["trace"] = {
                "program_s": red.program_s, "program_n": red.program_n,
                "kernel_s": {"/".join(k): v for k, v in red.kernel_s.items()},
                "prefills": len(ctx.traced_prefills),
                "decode_rounds": len(ctx.traced_decode_rounds),
                "decode_tokens": sum(map(len, ctx.traced_decode_rounds)),
                "live_slots_mean": ctx.traced_live_slots}
        else:
            metrics = end_to_end(bench, cell, host, setup_s)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    cell_mod.free_engine(engine)
    del engine
    comp = correct.compare(config, wseed, run, correct.reference_length(mix),
                           control=control)
    limit = config["correct"]["max_logit_rel_err"]
    value = comp["max_logit_rel_err"]
    ok = limit is not None and value is not None and value <= limit
    result = {"correct": bool(ok), "attempted": host["attempted"],
              "failed": host["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["host"] = host
    result["compared"] = {
        "max_logit_rel_err": {"value": value, "limit": limit},
        "sampled_tokens": comp["sampled_tokens"],
        "sampled_requests": comp["sampled_requests"]}
    if control:
        result["compared"]["control_max_logit_rel_err"] = comp[
            "control_max_logit_rel_err"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    configure_jax()
    from bench.cell import BenchError
    try:
        result = run_cell(args)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    print(f"host {json.dumps(result.pop('host'))}", file=sys.stderr,
          flush=True)
    c = result["compared"]
    print(f"compared max_logit_rel_err {c['max_logit_rel_err']['value']!r} "
          f"limit {c['max_logit_rel_err']['limit']!r} (over {c['sampled_tokens']} "
          f"served tokens of {c['sampled_requests']} requests)",
          file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
