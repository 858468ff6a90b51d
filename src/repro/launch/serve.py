"""Batched serving driver: prefill + decode loop with continuous batching.

A minimal but real engine: requests enter a queue, get batched (padded to
the compiled batch size), prefilled into a shared KV cache, then decoded
step-by-step with per-slot completion tracking and slot reuse. On this
container it serves reduced configs (examples/serve_lm.py); on TPU the
identical driver serves the full configs under the TP mesh. On a
multi-device mesh the prepared-weight planes are built directly into
their sharded layout (see docs/serving.md) — ``--mesh auto`` serves
pure-TP over every visible device.

  python -m repro.launch.serve --arch deepseek-7b --reduced \
      --batch 4 --prompt-len 32 --max-new 16 --mesh auto
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.configs import get_config, reduced_config
from repro.configs.base import ModelConfig
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.mesh import make_mesh, make_serve_mesh
from repro.models import (adopt_slot, decode_step, decode_step_paged,
                          draft_step_paged, init_cache, init_paged_cache,
                          init_params, param_dims, prefill, release_slot,
                          rewind_slots, verify_step_paged)
from repro.parallel.sharding import make_rules, use_rules
from repro.quant import (BlockAllocator, PreparedWeight, calibrating,
                         prepare_logits_head, prepare_params)
from repro.quant.calibrate import CalibrationTable, applied_calib_state
from repro.quant.streaming import StreamingCalibrator, sample_gate

__all__ = ["ServeEngine", "ContinuousBatchingEngine", "Request",
           "bucket_for", "make_engine", "main"]


def bucket_for(plen: int, buckets=None, *, block: int = 1) -> int:
    """The padded prompt length a request of ``plen`` tokens is served at.

    The smallest warmed bucket that fits, else ``plen`` rounded up to
    ``block``. This is the single bucketing rule shared by
    :meth:`ServeEngine.run` (group padding) and
    :class:`ContinuousBatchingEngine` admission — a pure function of
    ``(plen, buckets, block)``, never of engine state or co-traffic, so
    two engines warmed with the same buckets prefill a given request at
    the same compiled shape (the determinism harness relies on this,
    and it is what keeps admission from recompiling for every distinct
    prompt length between buckets).
    """
    if buckets:
        for b in buckets:
            if b >= plen:
                return int(b)
    return -(-plen // block) * block


def _place_raw_leaves(params, dims, rules):
    """device_put every raw array leaf onto its resolved mesh layout.

    PreparedWeight subtrees are skipped — their planes were already built
    into their sharded layout by ``prepare_params``.
    """

    def walk(node, dnode):
        if isinstance(node, PreparedWeight):
            return node
        if isinstance(node, dict):
            return {k: walk(v, dnode.get(k) if isinstance(dnode, dict)
                            else None)
                    for k, v in node.items()}
        if not (isinstance(dnode, tuple) and hasattr(node, "shape")
                and len(dnode) == getattr(node, "ndim", -1)):
            return node
        spec = rules.resolve(dnode, tuple(node.shape))
        return jax.device_put(node, NamedSharding(rules.mesh, spec))

    return walk(params, dims)


def _stamp_act_sigmas(params, table: CalibrationTable):
    """Stamp each PreparedWeight with its call site's observed act sigma.

    The site name is the ``parent.name`` path convention the model call
    sites use (``"ffn.wg"``, ``"attn.wq"``, ...); the top-level
    unembedding weights (``unembed`` / the tied ``unembed_prepared``
    view) belong to the ``"logits"`` site. Planes are shared; only the
    static aux changes.
    """

    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        if isinstance(node, PreparedWeight):
            if path and path[-1] in ("unembed", "unembed_prepared"):
                sigma = table.sigma("logits")
            elif len(path) >= 2:
                sigma = table.sigma(f"{path[-2]}.{path[-1]}")
            else:
                sigma = None
            if sigma is not None:
                return node.with_act_sigma(sigma)
        return node

    return walk(params, ())


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray             # (prompt_len,) int32
    max_new_tokens: int
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    #: calibration-table version the request was served under (stamped by
    #: the engine: at group start for the group engine, at admission for
    #: the continuous one). ``ServeEngine.replay`` re-installs exactly
    #: this version's runtime state, so the logged output stays
    #: bitwise-reproducible across any number of later hot swaps.
    table_version: int = 0


class ServeEngine:
    """Fixed-batch prefill/decode engine with greedy sampling.

    Static weights are quantized + limb-decomposed exactly **once**, here
    at engine construction (``quant.prepare_params``): every MGS matmul
    in the request loop consumes the cached PreparedWeight planes instead
    of re-quantizing per request. ``quant.PREP_STATS`` counts builds, so
    monitoring (and tests) can assert the per-process-once invariant.

    On a multi-device ``mesh`` the engine prepares each weight *directly
    into its sharded layout*: plane PartitionSpecs are derived from the
    weight's logical dims (``parallel.sharding.prepared_specs`` — codes
    and limb planes inherit the weight's (in, out) layout, per-channel
    scales follow the out dim), and the remaining raw parameters
    (embeddings, norms, conv filters) are placed by the same serve
    rules. Every model matmul — including the attention out-projection,
    decode score/value contractions, MoE expert einsums, and the logits
    head — routes through the unified quantized-einsum dispatch
    (``quant.qeinsum``), so the MGS accumulator discipline covers the
    whole forward pass and distribution cannot reorder those
    contractions: sharded serving is bit-identical to the single-device
    fused path on both pure-TP and data-axis (FSDP) meshes. The
    guarantee also covers the chunked-prefill softmax scan (qeinsum
    contractions + pairwise denominators), the gather-based MoE
    dispatch/combine (exact integer routing), and the packed-FP8 KV
    cache decode step (``quant.kvcache`` + the MGS flash-decode kernel)
    — see docs/serving.md for the full scope.

    ``calibration`` (or a later :meth:`calibrate` call) feeds observed
    per-call-site activation limb sigmas into the Markov flush planner,
    making ``flush_target`` periods per-layer instead of global
    (``quant.calibrate``).
    """

    def __init__(self, cfg: ModelConfig, mesh, batch: int, max_len: int,
                 params=None, dims=None, seed: int = 0,
                 eos_id: Optional[int] = None,
                 calibration: Optional[CalibrationTable] = None,
                 deterministic: bool = True):
        if calibration is not None:
            cfg = dataclasses.replace(
                cfg, quant=cfg.quant.with_calibration(calibration))
        self.cfg = cfg
        self.mesh = mesh
        self.batch = batch
        self.max_len = max_len
        self.eos_id = eos_id
        self._buckets: Optional[List[int]] = None  # set by warmup()
        # deterministic (default) serving layout: weights/planes
        # FSDP-sharded over the data axes, batch-indexed activations
        # replicated — local float-op shapes are then mesh-invariant,
        # which (together with the exact qeinsum matmuls and
        # shape-independent reductions) is what makes logits
        # bit-identical across meshes. Data-parallel throughput comes
        # from running one engine per data-parallel replica group.
        # ``deterministic=False`` restores the batch-over-data layout
        # (in-engine data parallelism, no cross-mesh bit guarantee).
        self.rules = make_rules(mesh, "serve",
                                shard_batch=not deterministic)
        multi = int(np.prod(tuple(mesh.shape.values()))) > 1
        with use_rules(self.rules):
            if params is None:
                params, dims = init_params(cfg, jax.random.PRNGKey(seed))
            elif dims is None:
                # always derive logical dims (abstract trace, no
                # allocation): they make stack/K-axis inference exact for
                # the grouped/expert prepared layouts, mesh or not.
                dims = param_dims(cfg)
            self.dims = dims
            self.params = prepare_params(
                params, cfg.quant, dims=dims,
                rules=self.rules if multi else None)
            # cache a PreparedWeight for the unembedding view too: the
            # logits head otherwise re-quantizes the raw (shared) embed
            # table on every prefill/decode step.
            self.params = prepare_logits_head(
                self.params, cfg.quant, tied=cfg.tie_embeddings,
                rules=self.rules if multi else None)
            if calibration is not None:
                self.params = _stamp_act_sigmas(self.params, calibration)
            if multi and dims is not None:
                self.params = _place_raw_leaves(self.params, dims,
                                                self.rules)
            self._init_calib_runtime(calibration)
            self._build_jits()

    # -- versioned runtime calibration state ---------------------------

    def _init_calib_runtime(self, calibration: Optional[CalibrationTable]):
        """Version bookkeeping + the runtime calib-state pytree.

        ``self._calib_state`` is the small dict the jitted entry points
        take as their last argument: ``{"flush": {site: int32 scalar},
        "q_amax": f32 scalar}`` (keys present only when the config uses
        them). Hot swaps replace the *arrays* — the pytree structure,
        and therefore every trace, is untouched. Versions, tables, and
        the host mirrors live outside any pytree on purpose: a version
        id inside a traced argument would retrace per version.
        """
        self._site_wsigmas = self._collect_limb_sigmas(self.params)
        sites = set(self._site_wsigmas)
        if calibration is not None:
            sites |= {s for s, _ in calibration.to_pairs()
                      if not s.endswith(".amax")}
        self._flush_sites = sorted(sites)
        self._flush_host: Dict[str, int] = {}
        self._amax_value = 0.0
        if calibration is not None:
            v = calibration.version if calibration.version > 0 else 1
            if calibration.version != v:
                calibration = CalibrationTable.from_pairs(
                    calibration.to_pairs(), version=v)
            self._tables = {v: calibration}
            self.table_version = v
        else:
            self._tables: Dict[int, CalibrationTable] = {}
            self.table_version = 0
        self._calib_state = self._build_calib_state(calibration)
        self._streaming: Optional[StreamingCalibrator] = None
        self._stream_seed = 0
        self._stream_index = 0
        self._replaying = False
        # guards the (version, state, host-mirror) swap against readers
        # on other threads: the replica driver pushes refreshed tables
        # from its own thread while worker threads snapshot per group /
        # per admission. RLock: the continuous override re-enters.
        self._calib_lock = threading.RLock()

    @staticmethod
    def _collect_limb_sigmas(params) -> Dict[str, float]:
        """Per-site PreparedWeight limb sigma, keyed like _stamp_act_sigmas."""
        out: Dict[str, float] = {}

        def walk(node, path):
            if isinstance(node, dict):
                for k, v in node.items():
                    walk(v, path + (k,))
            elif isinstance(node, PreparedWeight):
                if path and path[-1] in ("unembed", "unembed_prepared"):
                    out["logits"] = float(node.limb_sigma)
                elif len(path) >= 2:
                    out[f"{path[-2]}.{path[-1]}"] = float(node.limb_sigma)

        walk(params, ())
        return out

    def _build_calib_state(self, table: Optional[CalibrationTable]):
        """Runtime state pytree for ``table`` (None = uncalibrated plan).

        Pure function of ``(cfg.quant, self._site_wsigmas,
        self._flush_sites, table)`` — replay rebuilds any version's
        state from its stored table and gets the very arrays (values,
        not objects) that served it.
        """
        q = self.cfg.quant
        state: Dict[str, Any] = {}
        if q.flush_target is not None:
            host = self._plan_flush_host(table)
            self._flush_host = host
            state["flush"] = {s: jnp.asarray(p, jnp.int32)
                              for s, p in host.items()}
        if q.static_q_scale:
            a = (table.sigma("attn.q.amax") if table is not None else None)
            self._amax_value = float(a) if a is not None and a > 0 else 0.0
            state["q_amax"] = jnp.asarray(self._amax_value, jnp.float32)
        return state if state else None

    def _plan_flush_host(self, table: Optional[CalibrationTable]
                         ) -> Dict[str, int]:
        """Host-side flush plan ``table`` implies — pure, no installation.

        The continuous engine compares this against the installed
        ``self._flush_host`` to decide whether a hot swap is bit-inert
        for in-flight slots or must be fenced behind a drain.
        """
        q = self.cfg.quant
        if q.flush_target is None:
            return {}
        from repro.core.markov import plan_flush_period
        # int32-clamp: huge planned periods (near-uniform sigmas) all
        # mean "flush once at the end" — the kernel clips to its grid
        return {
            s: min(2**31 - 1, plan_flush_period(
                q.block_k, target_overflow=q.flush_target,
                sigma_limb_x=(table.sigma(s) if table is not None
                              else None),
                sigma_limb_w=self._site_wsigmas.get(s)))
            for s in self._flush_sites}

    def _cs(self):
        """The calib-state argument for the jitted entry points."""
        return self._calib_state

    @contextlib.contextmanager
    def _pinned_state(self, version: int):
        """Temporarily re-install ``version``'s runtime state (replay).

        Swaps the state arrays and the stamped version on the *same* jit
        caches — the compiled programs are untouched, which is exactly
        why the replayed bits match the originals. Streaming observation
        is muted for the duration so a replay never perturbs live drift
        statistics.
        """
        if version != 0 and version not in self._tables:
            raise KeyError(f"no calibration table recorded for version "
                           f"{version} (known: {sorted(self._tables)})")
        table = self._tables.get(version)
        prev = (self._calib_state, self._flush_host, self._amax_value,
                self.table_version, self._replaying)
        rec = self._streaming.recorder if self._streaming else None
        prev_mute = rec.muted if rec is not None else None
        try:
            self._calib_state = self._build_calib_state(table)
            self.table_version = version
            self._replaying = True
            if rec is not None:
                rec.muted = True
            yield
        finally:
            (self._calib_state, self._flush_host, self._amax_value,
             self.table_version, self._replaying) = prev
            if rec is not None:
                rec.muted = prev_mute

    def _build_jits(self):
        cfg = self.cfg

        # cs defaults to None (no runtime state -> the static fallback
        # plan, which resolves to the same periods as the engine's
        # default state): tests may drive the jitted entries directly
        # with the pre-versioning 3-arg signature.
        def _pf(p, b, c, cs=None):
            with applied_calib_state(cs):
                return prefill(p, cfg, b, c)

        def _dc(p, t, c, cs=None):
            with applied_calib_state(cs):
                return decode_step(p, cfg, t, c)

        self._prefill = jax.jit(_pf)
        self._decode = jax.jit(_dc, donate_argnums=(2,))

    def _make_batch(self, toks) -> Dict[str, Any]:
        batch = {"tokens": jnp.asarray(toks)}
        if self.cfg.vision_prefix:
            batch["vision_embeds"] = jnp.zeros(
                (self.batch, self.cfg.vision_prefix, self.cfg.d_model),
                jnp.bfloat16)
        if self.cfg.encoder_layers:
            batch["audio_embeds"] = jnp.zeros(
                (self.batch, self.cfg.encoder_len, self.cfg.d_model),
                jnp.bfloat16)
        return batch

    def warmup(self, plen_buckets, *, max_new: int = 1, seed: int = 0):
        """Compile the common padded prompt lengths before traffic.

        Prefill compilation is per padded prompt length: the first
        request group arriving at a new length pays a trace+compile in
        the serving path. Passing the deployment's bucket lengths here
        front-loads those compilations (plus ``max_new`` decode steps,
        which compiles the decode entry point too). Bucket results are
        discarded; served-traffic statistics are untouched.

        Args:
          plen_buckets: iterable of prompt lengths to compile (each must
            leave room for ``max_new`` tokens within ``max_len``).
          max_new: decode steps run per bucket (1 compiles decode).
          seed: RNG seed for the dummy prompt tokens.

        Returns:
          The sorted, de-duplicated bucket list that was compiled.
        """
        buckets = sorted({int(b) for b in plen_buckets})
        bad = [b for b in buckets if b <= 0 or b + max_new > self.max_len]
        if bad:
            raise ValueError(f"warmup buckets {bad} out of range for "
                             f"max_len={self.max_len}, max_new={max_new}")
        rng = np.random.default_rng(seed)
        for plen in buckets:
            toks = rng.integers(1, self.cfg.vocab,
                                (self.batch, plen)).astype(np.int32)
            batch = self._make_batch(toks)
            cache, _ = init_cache(self.cfg, self.batch, self.max_len)
            with use_rules(self.rules):
                logits, cache = self._prefill(self.params, batch, cache,
                                              self._cs())
                cur = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
                for _ in range(max_new):
                    logits, cache = self._decode(self.params, cur, cache,
                                                 self._cs())
                    cur = jnp.argmax(logits, axis=-1)[:, None].astype(
                        jnp.int32)
            jax.block_until_ready(logits)
        self._buckets = buckets
        return buckets

    def apply_calibration(self, table: CalibrationTable) -> int:
        """Install a calibration table on this engine; returns its version.

        Two paths, split on whether a table is already installed:

        **First install** (legacy full rebuild): the table is stored on
        the QuantConfig, stamped onto every
        :class:`~repro.quant.PreparedWeight` (``act_sigma`` — planes are
        shared, only the static aux changes), and the jitted entry
        points rebuilt. This is how replica engines share one
        calibration pass (:class:`repro.launch.replica.
        ReplicaServeDriver` calibrates replica 0 and applies the table
        to the rest). Do it before traffic — the rebuild retraces.

        **Hot swap** (every later call): only the runtime state arrays
        are replaced — flush periods and the static decode-query amax
        flow to the kernels as runtime scalars, so the swap costs zero
        recompiles and is safe *between decode steps* under live
        traffic. The config and the PreparedWeight aux are deliberately
        left at their first-install values (restamping the static aux
        would retrace); they only feed the static fallback plan, which
        the runtime state overrides. In-flight work is protected by
        snapshotting: the group engine pins state per group, the
        continuous engine pins per-slot amax at admission and fences
        flush-state changes until resident requests drain (no
        mid-request plan tearing).

        The assigned version is monotone per engine: ``table.version``
        when it advances the engine's counter, else ``current + 1``.
        Every version's table is retained for :meth:`replay`.
        """
        with self._calib_lock:
            v = (table.version if table.version > self.table_version
                 else self.table_version + 1)
            if table.version != v:
                table = CalibrationTable.from_pairs(table.to_pairs(),
                                                    version=v)
            first = not self._tables
            self._tables[v] = table
            new_sites = {s for s, _ in table.to_pairs()
                         if not s.endswith(".amax")} - set(self._flush_sites)
            if new_sites:
                # site universe grew (e.g. first table adds attention
                # score sites): the state pytree structure changes,
                # costing one retrace on the next call. refreshed()
                # tables keep the universe stable, so streaming swaps
                # never hit this.
                self._flush_sites = sorted(set(self._flush_sites)
                                           | new_sites)
            self.table_version = v
            if first:
                self.cfg = dataclasses.replace(
                    self.cfg, quant=self.cfg.quant.with_calibration(table))
                self.params = _stamp_act_sigmas(self.params, table)
                self._calib_state = self._build_calib_state(table)
                self._build_jits()
            else:
                self._calib_state = self._build_calib_state(table)
            if self._streaming is not None:
                self._streaming.table = table
            return v

    def calibrate(self, prompts: Optional[List[np.ndarray]] = None, *,
                  update: bool = True, seed: int = 0) -> CalibrationTable:
        """One-pass activation-statistics trace (``quant.calibrate``).

        Runs a single *eager* prefill over ``prompts`` (default: a
        random token batch) under a recording context: every site-tagged
        matmul logs its quantized activation's limb PMF, aggregated
        across the scanned layer stack. Returns the resulting
        :class:`CalibrationTable`; with ``update=True`` the table is also
        installed on the engine (:meth:`apply_calibration` — the full
        first-install path when no table is installed yet, a runtime
        hot swap otherwise) so subsequent requests plan their
        exact-kernel flush periods from observed per-site sigmas.
        Calibration never changes *accuracy* — it lengthens flush
        periods within the Markov overflow budget — but a changed
        period does move the wide-accumulator rounding by ulps, which
        is why requests record their table version and :meth:`replay`
        restores it exactly.
        """
        if prompts is None:
            rng = np.random.default_rng(seed)
            prompts = [rng.integers(1, self.cfg.vocab,
                                    min(self.max_len - 1, 16)).astype(
                                        np.int32)
                       for _ in range(self.batch)]
        plen = max(len(p) for p in prompts)
        toks = np.zeros((self.batch, plen), np.int32)
        for j, p in enumerate(prompts[:self.batch]):
            toks[j, plen - len(p):] = p
        cache, _ = init_cache(self.cfg, self.batch, self.max_len)
        with use_rules(self.rules), calibrating() as rec:
            # eager (non-jitted) prefill + one decode step: the scan
            # bodies still trace, and the per-site recording rides
            # jax.debug.callback, so it fires once per scanned layer.
            # The decode step covers the decode-only sites
            # (attn.scores / attn.values).
            logits, cache = prefill(self.params, self.cfg,
                                    self._make_batch(toks), cache)
            cur = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            decode_step(self.params, self.cfg, cur, cache)
        table = rec.table()
        if update:
            self.apply_calibration(table)
        return table

    # -- streaming calibration (quant.streaming) -----------------------

    def enable_streaming(self, calibrator: Optional[StreamingCalibrator]
                         = None, *, seed: Optional[int] = None,
                         sample_period: int = 4,
                         **thresholds) -> StreamingCalibrator:
        """Attach a streaming calibrator; gated traffic feeds its recorder.

        Once enabled, every ``sample_gate``-admitted unit of traffic
        (request group here; admission on the continuous engine) also
        runs a *shadow pass*: an eager re-execution of the same tokens
        under ``calibrating(recorder)``. The shadow pass is completely
        off the compiled serve path — the production jit caches never
        contain a recording callback, so enabling streaming cannot move
        a single served bit; it costs roughly ``1/sample_period`` extra
        prefills. Pass a shared ``calibrator`` to pool statistics
        across replicas (per-engine ``seed`` staggers their gates);
        ``thresholds`` forward to :class:`StreamingCalibrator`.
        """
        if calibrator is None:
            calibrator = StreamingCalibrator(
                self._tables.get(self.table_version,
                                 CalibrationTable({})),
                seed=seed if seed is not None else 0,
                sample_period=sample_period, **thresholds)
        self._streaming = calibrator
        self._stream_seed = seed if seed is not None else calibrator.seed
        return calibrator

    def maybe_refresh_calibration(self):
        """Drift-check the streaming statistics; hot-swap on drift.

        Returns the justifying :class:`~repro.quant.streaming.
        DriftReport` when a refresh happened, else ``None``. The
        refreshed table goes through :meth:`apply_calibration`'s hot
        path (runtime state swap, zero recompiles).
        """
        if self._streaming is None:
            return None
        return self._streaming.maybe_refresh(self.apply_calibration)

    def _shadow_pass(self, toks: np.ndarray):
        """Eager recording pass over sampled traffic tokens.

        The streaming twin of :meth:`calibrate`'s trace: one eager
        prefill + one decode step over the *actual* gated tokens, under
        the shared streaming recorder. Results are discarded; only the
        per-site statistics (and the decode-query amax) survive.
        """
        rec = self._streaming.recorder
        cache, _ = init_cache(self.cfg, toks.shape[0], self.max_len)
        with use_rules(self.rules), calibrating(rec):
            logits, cache = prefill(self.params, self.cfg,
                                    self._make_batch(toks), cache)
            cur = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            decode_step(self.params, self.cfg, cur, cache)

    def replay(self, request: Request, version: Optional[int] = None, *,
               group: Optional[List[Request]] = None):
        """Re-serve a logged request under its recorded table version.

        Returns ``(replayed_request, stats)`` where ``stats["logits"]``
        carries the f32 logits row behind every emitted token — the
        observable the determinism suite compares bitwise against the
        original run. ``version`` defaults to
        ``request.table_version``; the engine re-installs exactly that
        version's runtime state (same jit caches, same arrays), so the
        replay is bit-identical *forever*, however many hot swaps
        happened since.

        ``group``: the request's original co-members, in their original
        order. Required whenever the quant config uses per-tensor
        activation scales (``per_row_act=False``) — a group member's
        quantization then depends on the whole group's absmax, so the
        single request is not a closed bit-reproducible unit; replaying
        the full group is. With ``per_row_act=True`` (the continuous
        engine's contract) the default lone replay is exact.
        """
        version = request.table_version if version is None else version
        members = list(group) if group is not None else [request]
        idx = next((i for i, r in enumerate(members) if r is request), None)
        if idx is None:
            raise ValueError("request must be a member of its group")
        if group is None and not self.cfg.quant.per_row_act and \
                self.batch > 1:
            raise ValueError(
                "per-tensor activation scales couple group members: pass "
                "group=<the request's original co-members> to replay "
                "(per_row_act=False quant)")
        copies = [dataclasses.replace(r, out_tokens=[], done=False)
                  for r in members]
        with self._pinned_state(version):
            stats = self._replay_run(copies)
        return copies[idx], stats

    def _replay_run(self, copies: List[Request]) -> Dict[str, Any]:
        return self.run(copies, record_logits=True)

    def run(self, requests: List[Request], *, injector=None,
            deadline_s: Optional[float] = None,
            should_abort=None, record_logits: bool = False
            ) -> Dict[str, Any]:
        """Serve a list of requests in fixed-size batches.

        The keyword-only arguments are the fault-tolerance seam the
        replica fleet threads through (``repro.runtime.fault_tolerance``,
        docs/replica_serving.md):

        * ``injector`` — a bound :class:`~repro.runtime.fault_tolerance.
          FaultInjector` view; its ``before_group()`` hook runs as each
          request group starts and ``on_decode(step)`` before each decode
          step, so chaos tests can raise / hang / poison at a
          deterministic point in the stream.
        * ``deadline_s`` — per-group watchdog: if a group (prefill +
          decode) exceeds this wall-clock budget, the engine raises
          :class:`~repro.runtime.fault_tolerance.DeadlineExceeded` at the
          next step boundary (cooperative — it catches hangs that
          surface between device calls, e.g. an injected straggler).
        * ``should_abort`` — callable polled at the same boundaries; a
          True return raises ``DeadlineExceeded`` (the supervisor's
          abort path for draining a replica that is being retired).

        On any raise the engine itself stays serviceable (per-group
        state — batch, cache — is rebuilt from scratch each group), but
        the current group's requests may hold partial ``out_tokens``;
        the caller owns resetting them before a re-run.
        """
        from repro.runtime.fault_tolerance import DeadlineExceeded
        t_start = time.time()
        n_prefill_tokens = 0
        n_decode_tokens = 0
        logits_log: Dict[int, List[np.ndarray]] = {}
        for i in range(0, len(requests), self.batch):
            group = requests[i:i + self.batch]
            t_group = time.time()
            # snapshot the runtime calib state for the whole group: a hot
            # swap landing mid-group must not tear a request across two
            # flush plans (the swap takes effect at the next group).
            with self._calib_lock:
                cs = self._calib_state
                ver = self.table_version
            for r in group:
                r.table_version = ver

            def _watchdog():
                if should_abort is not None and should_abort():
                    raise DeadlineExceeded("aborted by supervisor")
                if (deadline_s is not None
                        and time.time() - t_group > deadline_s):
                    raise DeadlineExceeded(
                        f"group exceeded deadline_s={deadline_s}")

            if injector is not None:
                injector.before_group()
            _watchdog()
            # pad the group to the shared bucketing rule: after warmup,
            # every in-range prompt length reuses a compiled shape
            # (bucket_for falls back to the raw group max when no warmed
            # bucket fits — the pre-warmup behavior)
            plen = bucket_for(max(len(r.prompt) for r in group),
                              self._buckets)
            toks = np.zeros((self.batch, plen), np.int32)
            for j, r in enumerate(group):
                toks[j, plen - len(r.prompt):] = r.prompt  # left-pad
            if (self._streaming is not None and not self._replaying):
                idx = self._stream_index
                self._stream_index += 1
                if sample_gate(self._stream_seed, idx,
                               self._streaming.sample_period):
                    self._shadow_pass(toks)
            batch = self._make_batch(toks)
            cache, _ = init_cache(self.cfg, self.batch, self.max_len)
            with use_rules(self.rules):
                logits, cache = self._prefill(self.params, batch, cache, cs)
                n_prefill_tokens += plen * len(group)
                _watchdog()
                cur = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
                max_new = max(r.max_new_tokens for r in group)
                for step in range(max_new):
                    if injector is not None:
                        injector.on_decode(step + 1)
                    _watchdog()
                    rows = np.asarray(logits) if record_logits else None
                    for j, r in enumerate(group):
                        if not r.done and len(r.out_tokens) < r.max_new_tokens:
                            tok = int(cur[j, 0])
                            r.out_tokens.append(tok)
                            n_decode_tokens += 1
                            if record_logits:
                                logits_log.setdefault(r.rid, []).append(
                                    rows[j].copy())
                            if self.eos_id is not None and tok == self.eos_id:
                                r.done = True
                    if all(r.done or len(r.out_tokens) >= r.max_new_tokens
                           for r in group):
                        break
                    logits, cache = self._decode(self.params, cur, cache, cs)
                    cur = jnp.argmax(logits, axis=-1)[:, None].astype(
                        jnp.int32)
            for r in group:
                r.done = True
        dt = time.time() - t_start
        stats = {"prefill_tokens": n_prefill_tokens,
                 "decode_tokens": n_decode_tokens,
                 "wall_s": dt,
                 "decode_tok_per_s": n_decode_tokens / max(dt, 1e-9)}
        if record_logits:
            stats["logits"] = logits_log
        return stats


@dataclasses.dataclass
class _Slot:
    """Book-keeping for one occupied decode slot (host-side only)."""
    req: Request
    blocks: List[int]
    arrival: float
    admit_s: float
    cur: int                       # token to feed at the next decode step


class ContinuousBatchingEngine(ServeEngine):
    """Slot-level continuous batching over the paged KV pool.

    Where :class:`ServeEngine` serves fixed groups (a whole batch
    prefills together, decodes together, and the group's slowest request
    gates every other member), this engine schedules **slots**: each of
    the ``slots`` decode lanes holds one request, new requests are
    admitted into free lanes *between decode steps of the in-flight
    ones*, and finished requests release their lane (and their KV
    blocks) immediately. Every compiled shape is fixed — prefill is
    batch-1 at warmed bucket lengths, admission is one traced
    ``adopt_slot`` scatter (slot id, physical block ids and the prefill
    planes are all runtime values), and the decode step is always
    ``(slots, 1)`` over the shared block pool
    (``models.decode_step_paged``) — so steady-state traffic never
    recompiles, whatever the arrival pattern.

    Determinism contract: a request's logits and tokens are **bitwise
    identical** to an isolated run of that request alone on the same
    engine — independent of admission order, assigned slot, co-resident
    requests, or pool block assignment — and its greedy tokens match an
    isolated batch-1 :class:`ServeEngine` run warmed with the same
    buckets. (Bit-level f32 reproducibility is scoped to the compiled
    geometry — slot count and mesh — the same way the group engine's
    guarantee is scoped to its mesh: XLA may reassociate unquantized f32
    ops across *different* compiled batch shapes.) This needs
    ``quant.per_row_act`` (row-independent linear quantization; the
    constructor enforces it) on top of the packed cache: attention is
    already per-slice, the paged kernel walks only the slot's own live
    blocks, and free lanes decode into the trash block. See
    docs/serving.md and tests/test_continuous.py.

    With ``spec_k >= 1`` the engine decodes **speculatively**: each
    round runs ``spec_k - 1`` cheap truncated-layer self-draft steps
    (``cfg.quant.draft_layers`` of the model propose the next tokens),
    then scores current-token + drafts in one multi-query verify step
    (``models.verify_step_paged``) and accepts the longest prefix whose
    draft tokens match the verify argmaxes **exactly** (integer ``==``).
    Because every verify position is its own kernel slice with its own
    quantization rows, accepted tokens — and their logits rows — are
    *bitwise identical* to plain sequential decode; the rejected tail is
    physically zeroed back out of the pool (``models.rewind_slots``), so
    a request's bits never depend on ``spec_k``, the draft depth, or
    co-resident acceptance patterns. Draft quality only moves the
    acceptance *rate* (surfaced in ``stats["spec"]``), never a token.

    Restricted to plain dense decoder-only architectures (the
    ``models.init_paged_cache`` guard); the replica fleet's fault
    injection seam is group-mode only and not threaded through here.
    """

    def __init__(self, cfg: ModelConfig, mesh, *, slots: int, max_len: int,
                 n_blocks: Optional[int] = None, params=None, dims=None,
                 seed: int = 0, eos_id: Optional[int] = None,
                 calibration: Optional[CalibrationTable] = None,
                 spec_k: Optional[int] = None):
        if not cfg.quant.per_row_act:
            raise ValueError(
                "ContinuousBatchingEngine requires quant.per_row_act=True: "
                "per-tensor activation scales couple co-scheduled slots "
                "through a shared absmax, breaking the traffic-invariance "
                "contract (use e.g. quant.config.FP8_MGS_SERVE_PAGED)")
        if spec_k is not None and spec_k < 1:
            raise ValueError(f"spec_k must be >= 1 (got {spec_k}); use "
                             f"spec_k=None for plain sequential decode")
        # must precede super().__init__: _build_jits (called there) is
        # virtual and compiles the verify/draft/rewind entry points with
        # spec_k as a static shape
        self.spec_k = spec_k
        super().__init__(cfg, mesh, batch=1, max_len=max_len,
                         params=params, dims=dims, seed=seed, eos_id=eos_id,
                         calibration=calibration, deterministic=True)
        self.slots = slots
        self.block_size = cfg.quant.block_k
        self.n_table = -(-max_len // self.block_size)
        # default pool: every slot can hold a full table of live blocks
        # (+ the reserved trash block 0)
        self.n_blocks = (slots * self.n_table + 1 if n_blocks is None
                         else n_blocks)
        with use_rules(self.rules):
            self.cache, self.cache_dims = init_paged_cache(
                cfg, slots, max_len, self.n_blocks)
        self.alloc = BlockAllocator(self.n_blocks)
        self._free_slots = deque(range(slots))
        self._cur = np.zeros((slots, 1), np.int32)
        self._logits_log: Optional[Dict[int, List[np.ndarray]]] = None
        # per-slot pinned decode-query amax: set at admission from the
        # then-current table, so a later hot swap never moves an
        # in-flight request's static q scale (0 = slot free -> dynamic
        # path, never hit: free slots decode into the trash block)
        self._slot_amax = np.zeros(slots, np.float32)
        # fenced hot swap: a flush-plan-changing table waits here until
        # the active slots drain (admissions pause meanwhile)
        self._pending: Optional[CalibrationTable] = None
        self._serving = False

    def _build_jits(self):
        super()._build_jits()
        cfg = self.cfg

        def _dp(p, t, c, cs=None):
            with applied_calib_state(cs):
                return decode_step_paged(p, cfg, t, c)

        self._decode_paged = jax.jit(_dp, donate_argnums=(2,))
        self._adopt = jax.jit(adopt_slot, donate_argnums=(0,))
        self._release = jax.jit(release_slot, donate_argnums=(0,))
        if self.spec_k:
            k = self.spec_k

            def _round_body(p, cur, c):
                # the whole round — k - 1 chained truncated-layer
                # drafts plus the multi-query verify — is one jitted
                # program, so a round costs a single dispatch. On
                # launch-overhead-bound tiers (CPU emulation) this is
                # what makes speculation a win at all: k separate
                # launches can never beat k sequential steps there.
                toks = [cur]
                for j in range(k - 1):
                    dlog, c = draft_step_paged(
                        p, cfg, toks[-1], c, jnp.asarray(j, jnp.int32))
                    toks.append(jnp.argmax(dlog, axis=-1)[:, None]
                                .astype(jnp.int32))
                tokens = (toks[0] if k == 1
                          else jnp.concatenate(toks, axis=1))
                logits, c = verify_step_paged(p, cfg, tokens, c)
                return tokens, logits, c

            def _round(p, cur, c, cs=None):
                with applied_calib_state(cs):
                    return _round_body(p, cur, c)

            self._spec_round = jax.jit(_round, donate_argnums=(2,))
            self._rewind = jax.jit(
                lambda c, keep: rewind_slots(c, keep, k),
                donate_argnums=(0,))

    def warmup(self, plen_buckets, *, max_new: int = 1, seed: int = 0):
        """Compile the admission + decode path at the bucket lengths.

        Serves one dummy request per bucket through the *real*
        admit/decode/release cycle, which compiles batch-1 prefill and
        the ``adopt_slot`` scatter per bucket plus the (bucket-
        independent) paged decode step and release — afterwards,
        admitting any prompt that ``bucket_for`` maps into a warmed
        bucket costs zero compilations. The pool is empty again on
        return.
        """
        buckets = sorted({int(b) for b in plen_buckets})
        pad = self.spec_k - 1 if self.spec_k else 0
        bad = [b for b in buckets
               if b <= 0
               or -(-(b + max_new + pad) // self.block_size) > self.n_table]
        if bad:
            raise ValueError(f"warmup buckets {bad} out of range for "
                             f"max_len={self.max_len}, max_new={max_new}")
        self._buckets = buckets
        rng = np.random.default_rng(seed)
        for plen in buckets:
            req = Request(rid=-1,
                          prompt=rng.integers(1, self.cfg.vocab, plen)
                          .astype(np.int32),
                          max_new_tokens=max_new)
            self.serve([req])
        return buckets

    def _cs_decode(self):
        """Decode-step calib state: per-slot pinned q amaxes.

        Same pytree structure as the admission-time state except
        ``q_amax`` is the ``(slots,)`` vector of amaxes pinned at each
        slot's admission — a hot swap between decode steps changes what
        *new* admissions pin, never what a resident slot quantizes with.
        """
        cs = self._calib_state
        if cs is None or "q_amax" not in cs:
            return cs
        cs = dict(cs)
        cs["q_amax"] = jnp.asarray(self._slot_amax)
        return cs

    def _admit(self, req: Request, arrival: float, t0: float,
               active: Dict[int, _Slot]) -> Optional[_Slot]:
        """Try to admit one request; None if no slot/blocks right now."""
        plen = len(req.prompt)
        bucket = bucket_for(plen, self._buckets, block=self.block_size)
        # reserve spec_k - 1 extra rows: a verify round starting at the
        # last sequential position appends that far past it before the
        # rejected tail is rewound
        pad = self.spec_k - 1 if self.spec_k else 0
        n_alloc = -(-(bucket + req.max_new_tokens + pad)
                    // self.block_size)
        if n_alloc > self.n_table:
            raise ValueError(
                f"request {req.rid}: bucket {bucket} + "
                f"max_new {req.max_new_tokens} (+ {pad} speculative "
                f"headroom) needs {n_alloc} blocks > "
                f"table width {self.n_table} (raise max_len)")
        if not self._free_slots or self.alloc.n_free < n_alloc:
            return None
        slot = self._free_slots.popleft()
        blocks = self.alloc.alloc(n_alloc)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, bucket - plen:] = req.prompt          # left-pad
        if self._streaming is not None and not self._replaying:
            idx = self._stream_index
            self._stream_index += 1
            if sample_gate(self._stream_seed, idx,
                           self._streaming.sample_period):
                self._shadow_pass(toks)
        with self._calib_lock:
            # admission-time pin: version stamp, per-slot q amax, and
            # the state the prefill runs under are one consistent read
            req.table_version = self.table_version
            self._slot_amax[slot] = self._amax_value
            cs = self._cs()
        pcache, _ = init_cache(self.cfg, 1, bucket)
        logits, pcache = self._prefill(self.params, self._make_batch(toks),
                                       pcache, cs)
        phys = np.zeros(self.n_table, np.int32)       # tail -> trash block
        phys[:n_alloc] = blocks
        self.cache = self._adopt(self.cache, pcache,
                                 jnp.asarray(slot, jnp.int32),
                                 jnp.asarray(phys))
        tok = int(jnp.argmax(logits[0]))
        st = _Slot(req=req, blocks=blocks, arrival=arrival,
                   admit_s=time.monotonic() - t0, cur=tok)
        active[slot] = st
        self._harvest(slot, st, active, np.asarray(logits[0]))
        return st

    def _harvest(self, slot: int, st: _Slot, active: Dict[int, _Slot],
                 logits_row: np.ndarray):
        """Record one generated token; release the slot when done."""
        st.req.out_tokens.append(st.cur)
        if self._logits_log is not None:
            self._logits_log.setdefault(st.req.rid, []).append(
                logits_row.copy())
        if (self.eos_id is not None and st.cur == self.eos_id) \
                or len(st.req.out_tokens) >= st.req.max_new_tokens:
            st.req.done = True
            self.cache = self._release(self.cache,
                                       jnp.asarray(slot, jnp.int32))
            self.alloc.free(st.blocks)
            self._free_slots.append(slot)
            self._cur[slot, 0] = 0
            self._slot_amax[slot] = 0.0
            del active[slot]

    def serve(self, requests: List[Request], *, arrivals=None,
              record_logits: bool = False, feed=None,
              on_done=None) -> Dict[str, Any]:
        """Serve requests with continuous (slot-level) admission.

        ``arrivals``: optional per-request arrival offsets in seconds
        (same order as ``requests``); a request becomes admissible once
        that much wall-clock has elapsed. Default: everything is
        admissible immediately (admission order = list order —
        deterministic, which the invariance tests permute on purpose).

        ``feed``: optional zero-arg callable polled once per scheduling
        round; any :class:`Request` list it returns joins the waiting
        queue *mid-flight* — new traffic is admitted between decode
        steps of the in-flight requests (the replica driver's continuous
        dispatch rides this hook). ``on_done``: optional per-request
        completion callback, invoked the moment a request finishes
        (its slot is already released).

        With ``record_logits`` the returned stats carry
        ``stats["logits"][rid]``: the f32 logits row behind each emitted
        token — the observable the determinism harness compares bitwise.

        Returns the :meth:`ServeEngine.run`-style stats dict plus
        ``steps`` (decode steps run — speculative *rounds* when
        ``spec_k`` is set, each emitting 1..k tokens), per-request
        ``timing[rid] = (arrival_s, admit_s, done_s)``, and — under
        speculation — ``stats["spec"]`` with the round's drafted /
        accepted counts and acceptance rate.
        """
        if arrivals is None:
            arrivals = [0.0] * len(requests)
        if len(arrivals) != len(requests):
            raise ValueError("arrivals must parallel requests")
        self._logits_log: Optional[Dict[int, List[np.ndarray]]] = (
            {} if record_logits else None)
        t0 = time.monotonic()
        waiting = deque(zip(arrivals, requests))
        active: Dict[int, _Slot] = {}
        timing: Dict[int, Any] = {}
        n_prefill = n_decode = n_steps = 0
        n_drafted = n_accepted = 0
        self._serving = True

        def finish(req: Request, arrival: float, admit_s: float):
            nonlocal n_decode
            n_decode += len(req.out_tokens)
            timing[req.rid] = (arrival, admit_s, time.monotonic() - t0)
            if on_done is not None:
                on_done(req)

        try:
            with use_rules(self.rules):
                while True:
                    now = time.monotonic() - t0
                    if feed is not None:
                        for req in feed():
                            waiting.append((now, req))
                    if (self._pending is not None and not active
                            and not self._replaying):
                        # fenced hot swap: the active slots drained, install
                        # the deferred table and resume admissions under it
                        ServeEngine.apply_calibration(self, self._pending)
                        self._pending = None
                    while (waiting and waiting[0][0] <= now
                           and (self._pending is None or self._replaying)):
                        arr, req = waiting[0]
                        st = self._admit(req, arr, t0, active)
                        if st is None:
                            break
                        waiting.popleft()
                        n_prefill += bucket_for(len(req.prompt), self._buckets,
                                                block=self.block_size)
                        if req.done:                      # done at first token
                            finish(req, arr, st.admit_s)
                    if not active:
                        if waiting:
                            time.sleep(min(1e-3, max(0.0,
                                                     waiting[0][0] - now)))
                            continue
                        break
                    for slot, st in active.items():
                        self._cur[slot, 0] = st.cur
                    if self.spec_k:
                        k = self.spec_k
                        # one fused launch drafts and verifies the whole
                        # round; a single host sync covers all k positions
                        tokens, logits, self.cache = self._spec_round(
                            self.params, jnp.asarray(self._cur), self.cache,
                            self._cs_decode())
                        n_steps += 1
                        targets = np.asarray(
                            jnp.argmax(logits, axis=-1).astype(jnp.int32))
                        tokens_np = np.asarray(tokens)
                        rows = np.asarray(logits)      # (slots, k, vocab)
                        keep = np.zeros(self.slots, np.int32)
                        for slot in list(active):
                            st = active[slot]
                            # exact acceptance: drafts survive while they
                            # equal the verify argmax at their position
                            a = 0
                            while (a + 1 < k and tokens_np[slot, a + 1]
                                    == targets[slot, a]):
                                a += 1
                            n_drafted += k - 1
                            n_accepted += a
                            keep[slot] = a + 1
                            for j in range(a + 1):
                                st.cur = int(targets[slot, j])
                                self._harvest(slot, st, active, rows[slot, j])
                                if st.req.done:
                                    finish(st.req, st.arrival, st.admit_s)
                                    break
                        # released slots have pos == 0 and are skipped; live
                        # ones advance by their accepted count and shed the
                        # rejected rows
                        self.cache = self._rewind(self.cache,
                                                  jnp.asarray(keep))
                    else:
                        logits, self.cache = self._decode_paged(
                            self.params, jnp.asarray(self._cur), self.cache,
                            self._cs_decode())
                        n_steps += 1
                        rows = np.asarray(logits)
                        for slot in list(active):
                            st = active[slot]
                            st.cur = int(rows[slot].argmax())
                            self._harvest(slot, st, active, rows[slot])
                            if st.req.done:
                                finish(st.req, st.arrival, st.admit_s)
        finally:
            self._serving = False
        dt = time.monotonic() - t0
        stats: Dict[str, Any] = {
            "prefill_tokens": n_prefill, "decode_tokens": n_decode,
            "steps": n_steps, "wall_s": dt,
            "decode_tok_per_s": n_decode / max(dt, 1e-9),
            "timing": timing}
        if self.spec_k:
            stats["spec"] = {
                "k": self.spec_k,
                "draft_layers": self.cfg.quant.draft_layers,
                "drafted": n_drafted, "accepted": n_accepted,
                "acceptance_rate": n_accepted / max(n_drafted, 1),
                "tokens_per_round": n_decode / max(n_steps, 1)}
        if record_logits:
            stats["logits"] = self._logits_log
        self._logits_log = None
        return stats

    def apply_calibration(self, table: CalibrationTable) -> int:
        """Hot-swap with a drain fence for flush-plan changes.

        Flush periods are *global* kernel scalars (one SMEM operand per
        step, shared by every slot), so a swap that changes any site's
        planned period cannot be applied while requests are resident —
        it would tear them across two plans mid-request. Such swaps are
        **fenced**: the table is parked, admissions pause, the active
        slots drain at their own pace, and the swap installs at the next
        empty scheduling round (zero dropped requests, zero recompiles —
        the fence is pure host bookkeeping).

        Bit-inert swaps — same flush plan, e.g. an amax-only refresh —
        install immediately even under traffic: resident slots are
        protected by their admission-pinned per-slot amax, so only new
        admissions see the new table.

        Returns the installed version, or the *current* version when the
        swap was fenced (the pending table's version is assigned when it
        installs).
        """
        with self._calib_lock:
            if (self._serving and self._tables
                    and self._plan_flush_host(table) != self._flush_host):
                self._pending = table
                return self.table_version
            return super().apply_calibration(table)

    def _replay_run(self, copies: List[Request]) -> Dict[str, Any]:
        return self.serve(copies, record_logits=True)

    def run(self, requests: List[Request], **kw) -> Dict[str, Any]:
        """Group-mode entry point is replaced by :meth:`serve`."""
        if kw:
            raise NotImplementedError(
                "fault-injection/deadline seams are group-mode only "
                "(ServeEngine.run); the continuous engine serves via "
                ".serve()")
        return self.serve(requests)


def make_engine(cfg: ModelConfig, mesh, *, batch: int, max_len: int,
                params=None, dims=None, seed: int = 0,
                eos_id: Optional[int] = None,
                calibration: Optional[CalibrationTable] = None,
                deterministic: bool = True,
                continuous: bool = False,
                spec_k: Optional[int] = None) -> ServeEngine:
    """Engine factory — one construction point for every driver.

    A thin, keyword-only wrapper over :class:`ServeEngine` so the CLI
    below, the replica-group driver
    (:class:`repro.launch.replica.ReplicaServeDriver`), and tests all
    build engines through one signature: pass ``params`` (prepared trees
    included — preparation is idempotent) to share weights across
    engines, and ``calibration`` to start pre-calibrated. With
    ``continuous=True`` the returned engine is a
    :class:`ContinuousBatchingEngine` with ``batch`` decode slots
    (always deterministic — that layout is its contract); ``spec_k``
    additionally turns on draft/verify speculative decoding there
    (bitwise-exact acceptance — tokens never change, only throughput).
    """
    if continuous:
        if not deterministic:
            raise ValueError("continuous engines are deterministic by "
                             "construction (per-request bit-identity is "
                             "their contract)")
        return ContinuousBatchingEngine(
            cfg, mesh, slots=batch, max_len=max_len, params=params,
            dims=dims, seed=seed, eos_id=eos_id, calibration=calibration,
            spec_k=spec_k)
    if spec_k is not None:
        raise ValueError("spec_k requires continuous=True: speculative "
                         "decoding runs on the paged continuous engine")
    return ServeEngine(cfg, mesh, batch=batch, max_len=max_len,
                       params=params, dims=dims, seed=seed, eos_id=eos_id,
                       calibration=calibration, deterministic=deterministic)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--n-requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--mesh", default="1x1",
                    help='"DATAxMODEL" (e.g. 2x4) or "auto" (pure TP '
                         "over every visible device); ignored with "
                         "--replicas > 1 (the driver carves sub-meshes)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="run R data-parallel replica engines on disjoint "
                         "sub-meshes (repro.launch.replica) — aggregate "
                         "throughput scales with R while every request "
                         "stays bit-identical to a single-engine run")
    ap.add_argument("--scheduler", default="round_robin",
                    choices=("round_robin", "least_loaded"),
                    help="replica dispatch policy (--replicas > 1)")
    ap.add_argument("--continuous", action="store_true",
                    help="slot-level continuous batching over the paged "
                         "KV pool (ContinuousBatchingEngine): per-request "
                         "admission/release instead of fixed groups, "
                         "bit-identical per-request outputs under any "
                         "traffic; forces the FP8_MGS_SERVE_PAGED quant "
                         "preset; incompatible with --replicas > 1 here "
                         "(use ReplicaServeDriver(continuous=True))")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding depth for --continuous: "
                         "each round drafts k-1 tokens with the first "
                         "--draft-layers layers and verifies all k in "
                         "one multi-query step; accepted tokens are "
                         "bitwise identical to sequential decode "
                         "(0 = off)")
    ap.add_argument("--draft-layers", type=int, default=0,
                    help="layers the self-draft pass runs (default 0 = "
                         "half the stack); fewer layers draft faster "
                         "but accept less")
    ap.add_argument("--no-deterministic", action="store_true",
                    help="batch-over-data throughput layout instead of "
                         "the deterministic (cross-mesh bit-identical) "
                         "default — see docs/serving.md; incompatible "
                         "with --replicas > 1 (replica engines are "
                         "deterministic by construction)")
    args = ap.parse_args()
    configure_compile_cache()
    if args.replicas > 1 and args.no_deterministic:
        ap.error("--no-deterministic is incompatible with --replicas > 1: "
                 "the replica driver exists to provide data-parallel "
                 "throughput *with* the deterministic layout "
                 "(docs/replica_serving.md)")

    cfg = (reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    if args.continuous:
        if args.replicas > 1 or args.no_deterministic:
            ap.error("--continuous is a single-engine mode here and is "
                     "always deterministic")
        from repro.quant.config import FP8_MGS_SERVE_PAGED
        q = FP8_MGS_SERVE_PAGED
        if args.reduced:    # CPU-friendly tiles + jnp reference path
            q = q.replace(use_kernel=False, fused=False,
                          block_m=32, block_n=32, block_k=32)
        if args.spec_k:
            q = q.replace(draft_layers=args.draft_layers
                          or max(1, cfg.n_layers // 2))
        cfg = dataclasses.replace(cfg, quant=q)
    elif args.spec_k:
        ap.error("--spec-k requires --continuous (speculation runs on "
                 "the paged continuous engine)")
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab,
                                        args.prompt_len).astype(np.int32),
                    max_new_tokens=args.max_new)
            for i in range(args.n_requests)]
    max_len = (args.prompt_len + args.max_new + 1
               + (args.spec_k - 1 if args.spec_k else 0))

    if args.replicas > 1:
        from repro.launch.replica import ReplicaServeDriver
        with ReplicaServeDriver(cfg, args.replicas, batch=args.batch,
                                max_len=max_len,
                                scheduler=args.scheduler) as driver:
            driver.warmup(prompt_len=args.prompt_len,
                          max_new=args.max_new)
            stats = driver.run(reqs)
    else:
        if args.mesh == "auto":
            mesh = make_serve_mesh()   # every visible device, pure TP
        else:
            data_p, model_p = (int(x) for x in args.mesh.split("x"))
            mesh = make_mesh((data_p, model_p), ("data", "model"))
        engine = make_engine(cfg, mesh, batch=args.batch, max_len=max_len,
                             deterministic=not args.no_deterministic,
                             continuous=args.continuous,
                             spec_k=args.spec_k or None)
        if args.continuous:
            engine.warmup([args.prompt_len], max_new=1)
            stats = engine.serve(reqs)
        else:
            stats = engine.run(reqs)
    print(stats)
    for r in reqs[:2]:
        print(f"req {r.rid}: {r.out_tokens[:10]}")


if __name__ == "__main__":
    main()
