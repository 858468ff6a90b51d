"""Smoke run of the serving path on TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the multi-chip paths, four chips

One chip: deepseek-7b at its published widths (depth cut to ``DEPTH``
layers, random weights from ``SEED``) is served through the slot engine
(``ContinuousBatchingEngine``) on the fused kernel tier
(``FP8_MGS_SERVE_PAGED``), built and driven through the calls
``repro.launch.serve.main`` makes. It checks that every request
completes with finite logits, that a request re-served alone has logits
bitwise equal to its batched rows (the system's contract), that the
compiled decode step holds the Pallas kernels (``tpu_custom_call``), and
that one fused matmul agrees with a float64 product.

``--four-chips`` runs only the multi-chip paths: the slot engine with
pure tensor parallelism over a (1, 4) mesh, and ``ReplicaServeDriver``
over four one-chip sub-meshes. Each one's logits must be bitwise equal
to the one-chip engine's.

The script fails (non-zero exit, no ``ok`` line) when JAX finds no TPU
or any check fails. The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Times printed on the way are smoke timings of one cold run, not
benchmark numbers.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "deepseek-7b"
PUBLISHED = {"d_model": 4096, "n_heads": 32, "n_kv_heads": 32,
             "d_ff": 11008, "vocab": 102400}
# 8 of 30 layers. Raw f32 weights: 8 x 0.81 GB of layers + a 1.68 GB tied
# embedding, with packed codes of 8 x 0.20 GB + a 0.42 GB logits head;
# with the plane-build temporaries construction peaks at 11.9 GB of a
# TPU v5e's 16 GB.
DEPTH = 8
SLOTS, N_REQUESTS, PROMPT_LEN, MAX_NEW, SEED = 4, 8, 128, 16, 0
MAX_LEN = PROMPT_LEN + MAX_NEW + 1      # as repro.launch.serve.main
MATMUL_MKN = (512, 4096, 11008)


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)
    print(f"PASS {what}", flush=True)


def bitwise_equal(a: np.ndarray, b: np.ndarray) -> bool:
    return a.shape == b.shape and np.array_equal(
        a.view(np.uint32), b.view(np.uint32))


def require_tpu(count: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"device platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {d.platform!r}); "
                 "this smoke runs on the chip only")
    if len(devs) != count:
        sys.exit(f"chip_smoke: needs exactly {count} TPU device(s), "
                 f"found {len(devs)}")
    return d, len(devs)


def smoke_config(depth: int = DEPTH):
    from repro.configs import get_config
    from repro.quant.config import FP8_MGS_SERVE_PAGED
    cfg = get_config(ARCH)
    widths = {k: getattr(cfg, k) for k in PUBLISHED}
    check(widths == PUBLISHED, f"{ARCH} at published widths {widths}")
    cfg = dataclasses.replace(cfg, n_layers=depth, quant=FP8_MGS_SERVE_PAGED)
    print(f"config {ARCH}: depth {depth} of {get_config(ARCH).n_layers} "
          f"layers, quant FP8_MGS_SERVE_PAGED (use_kernel="
          f"{cfg.quant.use_kernel}, fused={cfg.quant.fused})", flush=True)
    return cfg


def make_requests(cfg):
    from repro.launch.serve import Request
    rng = np.random.default_rng(SEED)
    return [Request(rid=i, prompt=rng.integers(1, cfg.vocab, PROMPT_LEN)
                    .astype(np.int32), max_new_tokens=MAX_NEW)
            for i in range(N_REQUESTS)]


def serve_checked(engine, reqs, label: str):
    """Serve ``reqs``; check completion and finiteness; return the
    per-request logits rows and the serve wall time."""
    t = time.perf_counter()
    stats = engine.serve(reqs, record_logits=True)
    wall = time.perf_counter() - t
    check(all(len(r.out_tokens) == r.max_new_tokens for r in reqs),
          f"{label}: all {len(reqs)} requests got their "
          f"{reqs[0].max_new_tokens} tokens")
    rows = {rid: np.stack(v) for rid, v in stats["logits"].items()}
    check(all(np.isfinite(r).all() for r in rows.values()),
          f"{label}: every logit finite")
    return rows, wall


def alone_check(engine, reqs, rows, label: str):
    """A batched request re-served alone: logits rows bitwise equal."""
    from repro.launch.serve import Request
    probe = reqs[len(reqs) // 2 + 1]
    alone = Request(rid=probe.rid, prompt=probe.prompt,
                    max_new_tokens=probe.max_new_tokens)
    got = np.stack(engine.serve([alone], record_logits=True)
                   ["logits"][probe.rid])
    check(alone.out_tokens == probe.out_tokens
          and bitwise_equal(got, rows[probe.rid]),
          f"{label}: request {probe.rid} served alone has logits bitwise "
          f"equal to its batched rows")


def decode_step_hlo(engine) -> str:
    """Compiled HLO of the engine's own jitted paged decode step."""
    import jax.numpy as jnp
    return engine._decode_paged.lower(
        engine.params, jnp.asarray(engine._cur), engine.cache,
        engine._cs_decode()).compile().as_text()


def serve_phase(cfg, device):
    import jax
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import make_engine

    t = time.perf_counter()
    mesh = make_mesh((1, 1), ("data", "model"))
    engine = make_engine(cfg, mesh, batch=SLOTS, max_len=MAX_LEN,
                         seed=SEED, continuous=True)
    jax.block_until_ready(engine.params)
    setup_s = time.perf_counter() - t
    print(f"engine {type(engine).__name__}: {SLOTS} slots, pool "
          f"{engine.n_blocks} blocks of {engine.block_size}", flush=True)
    print(f"peak_bytes_in_use after construction: "
          f"{device.memory_stats()['peak_bytes_in_use']}", flush=True)
    t = time.perf_counter()
    engine.warmup([PROMPT_LEN], max_new=2)    # compiles prefill + decode
    compile_s = time.perf_counter() - t

    reqs = make_requests(cfg)
    rows, wall = serve_checked(engine, reqs, "slot engine")
    alone_check(engine, reqs, rows, "slot engine")
    n_calls = decode_step_hlo(engine).count(
        'custom_call_target="tpu_custom_call"')
    check(n_calls > 0, f"compiled decode step holds {n_calls} "
                       f"tpu_custom_call kernels")
    print(f"smoke timing (one cold run, not a benchmark): set-up "
          f"{setup_s:.1f} s, warmup compile {compile_s:.1f} s, serve "
          f"{N_REQUESTS} x ({PROMPT_LEN} in, {MAX_NEW} out) {wall:.2f} s",
          flush=True)
    print(f"peak_bytes_in_use: {device.memory_stats()['peak_bytes_in_use']}",
          flush=True)


def matmul_phase():
    """One fused matmul on random format-exact E4M3 operands against a
    float64 product of the same decoded values."""
    import jax.numpy as jnp
    from repro.core.formats import E4M3, decode_bits
    from repro.kernels.mgs_matmul import (mgs_matmul_exact_fused_pallas,
                                          worst_case_flush_period)
    M, K, N = MATMUL_MKN
    rng = np.random.default_rng(SEED)

    def codes(shape):
        c = rng.integers(0, 256, shape, dtype=np.uint8)
        c[(c & 0x7F) == 0x7F] = 0          # the E4M3 NaN codes
        return c

    xc, wc = codes((M, K)), codes((K, N))
    out = np.asarray(mgs_matmul_exact_fused_pallas(jnp.asarray(xc),
                                                   jnp.asarray(wc)))
    x = np.asarray(decode_bits(jnp.asarray(xc), E4M3), np.float64)
    w = np.asarray(decode_bits(jnp.asarray(wc), E4M3), np.float64)
    # Tolerance. Each E4M3 value is an integer multiple of 2^-9 below
    # 2^9, so every product and every sum of K < 2^16 of them is exact in
    # float64: ``ref`` is the exact result. The kernel's int32 limb-class
    # sums are exact too; it rounds only when a flush folds its 5 class
    # partials into the f32 accumulator (5 int->f32 conversions and 5
    # adds per flush). Balanced 7-bit limbs satisfy
    # sum_a |limb_a| 2^(7a) <= 4 |value| (3.03 at worst), so every
    # partial stays below 16 * sum_k |x_k w_k|, and each rounding costs at
    # most 2^-24 of that.
    nsteps = -(-K // 128)
    n_flush = -(-nsteps // min(worst_case_flush_period(128), nsteps))
    ref = x @ w
    tol = 10 * n_flush * 16 * 2.0 ** -24 * (np.abs(x) @ np.abs(w))
    err = np.abs(out.astype(np.float64) - ref)
    scale = np.abs(ref).max()
    check(np.isfinite(out).all() and bool((err <= tol).all()),
          f"fused matmul {M}x{K}x{N} within its f32-flush bound of the "
          f"float64 product (max |err| {err.max():.3e}, max |ref| "
          f"{scale:.3e}, max err/tol {(err / np.maximum(tol, 1e-300)).max():.3e})")


def plane_bytes_per_device(params) -> dict:
    import jax
    out: dict = {}
    for leaf in jax.tree.leaves(params):
        for s in getattr(leaf, "addressable_shards", ()):
            out[s.device.id] = out.get(s.device.id, 0) + s.data.nbytes
    return dict(sorted(out.items()))


def four_chip_phase(cfg):
    import jax
    from repro.launch.mesh import make_mesh, make_serve_mesh
    from repro.launch.replica import ReplicaServeDriver
    from repro.launch.serve import make_engine
    from repro.models import init_params
    from repro.quant import clear_prepared_cache

    params, dims = init_params(cfg, jax.random.PRNGKey(SEED))
    kw = dict(batch=SLOTS, max_len=MAX_LEN, params=params, dims=dims,
              continuous=True)

    def in_use():
        return {d.id: (d.memory_stats() or {}).get("bytes_in_use")
                for d in jax.devices()}

    def serve_on(mesh, label):
        t = time.perf_counter()
        engine = make_engine(cfg, mesh, **kw)
        engine.warmup([PROMPT_LEN], max_new=2)
        reqs = make_requests(cfg)
        rows, wall = serve_checked(engine, reqs, label)
        print(f"{label}: engine param bytes per device "
              f"{plane_bytes_per_device(engine.params)}; bytes_in_use "
              f"{in_use()}", flush=True)
        print(f"smoke timing (not a benchmark): {label} set-up + compile "
              f"{time.perf_counter() - t - wall:.1f} s, serve {wall:.2f} s",
              flush=True)
        del engine
        clear_prepared_cache()      # the prepared planes live in this cache
        gc.collect()
        return reqs, rows

    ref_reqs, ref = serve_on(make_mesh((1, 1), ("data", "model")),
                             "one chip")
    tp_reqs, tp = serve_on(make_serve_mesh(), "tensor parallel (1, 4)")
    check(all(a.out_tokens == b.out_tokens for a, b in zip(tp_reqs, ref_reqs))
          and all(bitwise_equal(tp[r], ref[r]) for r in ref),
          "tensor parallel (1, 4): logits bitwise equal to one chip")

    with ReplicaServeDriver(cfg, 4, model_parallel=1, **kw) as driver:
        driver.warmup([PROMPT_LEN], max_new=2)
        reqs = make_requests(cfg)
        stats = driver.run(reqs)
        check(all(a.out_tokens == b.out_tokens
                  for a, b in zip(reqs, ref_reqs)),
              f"replica driver: {stats['requests']} requests over "
              f"{stats['groups_per_replica']} per replica, tokens equal to "
              f"one chip")
        print(f"replicas: engine param bytes per device "
              f"{[plane_bytes_per_device(e.params) for e in driver.engines]}"
              f"; bytes_in_use {in_use()}", flush=True)
        for i, engine in enumerate(driver.engines):
            rows, _ = serve_checked(engine, make_requests(cfg),
                                    f"replica {i}")
            check(all(bitwise_equal(rows[r], ref[r]) for r in ref),
                  f"replica {i}: logits bitwise equal to one chip")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip paths, on four chips")
    args = ap.parse_args()
    from repro.launch.compile_cache import configure_compile_cache
    print(f"compile cache: {configure_compile_cache()}", flush=True)
    device, count = require_tpu(4 if args.four_chips else 1)
    try:
        cfg = smoke_config()
        if args.four_chips:
            four_chip_phase(cfg)
        else:
            serve_phase(cfg, device)
            matmul_phase()
    except SmokeFailure as e:
        print(f"FAIL {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
