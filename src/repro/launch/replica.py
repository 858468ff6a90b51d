"""Replica-group serving driver: data-parallel throughput, bit-identical
logits.

The deterministic ``ServeEngine`` layout (``shard_batch=False``) makes
logits bit-identical across meshes by *replicating* batch-indexed
activations over the data axes — which deliberately gives up in-engine
data parallelism. This module restores the throughput without touching
the numerics: partition the device set into R disjoint sub-meshes
(:func:`repro.launch.mesh.carve_submeshes`), run one deterministic
:class:`~repro.launch.serve.ServeEngine` per sub-mesh, and dispatch
request batches across the replicas. Every replica computes exactly the
single-engine deterministic program on its own devices, so every
request's logits are bit-identical to a single-device run — while
aggregate requests/sec scales with R
(``benchmarks/replica_throughput.py``).

Weight state is built **once** and shared: replica 0 prepares the
quantized planes (``quant.prepare_params`` — packed codes, scales,
the cached unembedding view), and the remaining replicas receive
``device_put`` transfers of the same planes onto their sub-meshes — zero
re-quantization, counted by ``quant.PREP_STATS`` staying flat in R
(``tests/test_replica.py``). Calibration is likewise one pass:
:meth:`ReplicaServeDriver.calibrate` traces replica 0 and installs the
resulting table on every engine
(:meth:`~repro.launch.serve.ServeEngine.apply_calibration`).

Scheduling model
----------------
Requests are batched in **arrival order** into groups of the engine batch
size; the *group* is the scheduling unit. Only the group -> replica
assignment is policy-driven (``"round_robin"`` or ``"least_loaded"``) —
group composition never is. Since a deterministic engine's outputs depend
only on the group's contents (never on which devices ran it), the
driver's outputs are invariant to the scheduler policy and to R, and
equal to a single engine serving the same requests in the same order.

Fault tolerance (the self-healing fleet)
----------------------------------------
Every worker serves its groups under a retry-with-backoff loop and a
per-group watchdog deadline; repeated failure escalates to the
supervisor path (:meth:`ReplicaServeDriver._fail_replica`): the replica
is marked unhealthy, its queued **and** in-flight requests are reset and
requeued onto surviving replicas (group composition preserved, so
outputs stay invariant), and a replacement engine is rebuilt on the
replica's healthy device set
(:func:`repro.runtime.elastic.replacement_mesh` + :func:`transfer_tree`
— zero re-quantization, ``quant.PREP_STATS`` stays flat across
recovery). Because every engine is deterministic, a requeued request's
logits are **bitwise identical** on whichever replica re-runs it — the
exactness guarantee that turns failover testing from a tolerance
argument into an equality assert (``tests/test_failover.py``,
``benchmarks/failover.py``). Deterministic fault injection for those
tests threads through ``injector=``
(:class:`repro.runtime.fault_tolerance.FaultInjector`).

Lifecycle::

    driver = ReplicaServeDriver(cfg, replicas=4, batch=8, max_len=128)
    driver.warmup(prompt_len=32)        # compile prefill/decode per replica
    futs = driver.submit_many(reqs)     # async: Future -> completed Request
    driver.drain()                      # flush partial group, wait for all
    print(driver.stats())               # incl. per-replica health states
    driver.close()                      # or use it as a context manager

See docs/replica_serving.md for the architecture walkthrough, the
fault-tolerance states, and the throughput-vs-determinism trade-off
against ``shard_batch=True``.
"""

from __future__ import annotations

import dataclasses
import queue
import sys
import threading
import time
from concurrent.futures import Future, InvalidStateError
from typing import Any, Dict, List, Optional, Sequence

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.launch.mesh import carve_submeshes
from repro.launch.serve import Request, make_engine
from repro.quant.calibrate import CalibrationTable
from repro.runtime.elastic import replacement_mesh
from repro.runtime.fault_tolerance import (FaultInjector, PoisonedDeviceError,
                                           ReplicaHealth, backoff_delay)

__all__ = ["ReplicaServeDriver", "transfer_tree"]

SCHEDULERS = ("round_robin", "least_loaded")


def transfer_tree(tree, mesh):
    """device_put every array leaf of ``tree`` onto ``mesh``, keeping specs.

    The replica sub-meshes all share the ``("data", "model")`` axis names
    and shape, so a leaf's existing PartitionSpec (derived once, on
    replica 0, from the weight's logical dims) is re-resolved verbatim on
    the target mesh: sharded planes stay sharded the same way, just on
    the new device set. Leaves without a named sharding (single-device
    sub-meshes) transfer fully replicated. PreparedWeight leaves are
    registered pytrees, so their codes/limbs/scale planes transfer
    transparently — this is a pure placement operation, with **no**
    re-quantization (``quant.PREP_STATS`` is untouched).
    """

    def move(leaf):
        if not hasattr(leaf, "sharding"):
            return leaf
        sh = leaf.sharding
        spec = sh.spec if isinstance(sh, NamedSharding) else P()
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree.map(move, tree)


@dataclasses.dataclass
class _Job:
    """One dispatched batch group (the scheduling unit)."""
    requests: List[Request]
    futures: List[Future]
    counted: bool = True    # warmup jobs don't enter the served stats
    # (buckets, max_new, seed): run ServeEngine.warmup instead of a
    # group — the bucketed prefill-length compilation, one job per
    # replica so the R compilations proceed concurrently.
    warmup: Optional[tuple] = None


class ReplicaServeDriver:
    """R deterministic ServeEngines on disjoint sub-meshes, one queue each.

    Construction carves ``jax.devices()`` (or ``devices``) into R
    disjoint ``("data", "model")`` sub-meshes, builds one deterministic
    engine per sub-mesh — replica 0 prepares the weight planes, replicas
    1..R-1 receive device_put transfers of the same planes
    (:func:`transfer_tree`) — and starts one worker thread per replica.

    Args:
      cfg: model config (the quant config selects the kernel tier, as for
        a single engine).
      replicas: number of replica groups R; must divide the device count.
      batch / max_len / seed / eos_id: per-engine serving parameters (see
        :class:`~repro.launch.serve.ServeEngine`).
      params / dims: optional shared parameter tree (+ logical dims);
        prepared once on replica 0 regardless of R.
      calibration: optional pre-built table installed on every engine.
      scheduler: group -> replica assignment policy. ``"round_robin"``
        cycles replicas in dispatch order; ``"least_loaded"`` picks the
        replica with the fewest queued + in-flight groups, preferring
        fully healthy replicas over suspect ones. Both skip unhealthy /
        rebuilding / dead replicas. Outputs are identical under either
        (see module docstring).
      model_parallel: model-axis size of each sub-mesh (default: all of
        the replica's devices — pure TP).
      devices: explicit device list to carve (default all visible).
      injector: optional
        :class:`repro.runtime.fault_tolerance.FaultInjector` — bound
        per replica and threaded into every ``engine.run`` (chaos
        tests / the ``failover`` benchmark). Warmup jobs are never
        injected.
      max_retries: in-place retries per group before the supervisor
        declares the replica failed (poisoned-device faults skip
        straight to failover — the device set itself is bad).
      deadline_s: per-group watchdog budget handed to ``engine.run``;
        a group exceeding it raises ``DeadlineExceeded`` and enters the
        same retry/failover path.
      backoff_base_s / backoff_cap_s: retry backoff shape
        (:func:`repro.runtime.fault_tolerance.backoff_delay`; jitter is
        deterministic, seeded per replica).
      continuous: run one
        :class:`~repro.launch.serve.ContinuousBatchingEngine` per
        replica (``batch`` decode slots each) instead of group engines.
        The scheduling unit becomes the *request*: ``submit`` dispatches
        immediately and the replica's serve loop admits it between
        decode steps of its in-flight work
        (:meth:`_worker_continuous`). Requires the row-independent quant
        preset (``per_row_act``); per-request outputs stay bit-identical
        to an isolated run under any traffic. The fault-injection /
        deadline / failover seam stays group-mode-only — passing
        ``injector`` or ``deadline_s`` with ``continuous=True`` raises.

    Every engine keeps ``shard_batch=False`` (the deterministic layout),
    so per-request logits are bit-identical to a single-device run; the
    driver is the data-parallel axis.
    """

    def __init__(self, cfg: ModelConfig, replicas: int, *, batch: int,
                 max_len: int, params=None, dims=None, seed: int = 0,
                 eos_id: Optional[int] = None,
                 calibration: Optional[CalibrationTable] = None,
                 scheduler: str = "round_robin",
                 model_parallel: Optional[int] = None, devices=None,
                 injector: Optional[FaultInjector] = None,
                 max_retries: int = 2,
                 deadline_s: Optional[float] = None,
                 backoff_base_s: float = 0.02,
                 backoff_cap_s: float = 0.5,
                 continuous: bool = False):
        if scheduler not in SCHEDULERS:
            raise ValueError(f"scheduler {scheduler!r} not in {SCHEDULERS}")
        if continuous and (injector is not None or deadline_s is not None):
            # the chaos/watchdog seam is threaded through ServeEngine.run
            # (group mode); the slot engine serves via .serve() and has
            # no injection points — keep the failure story honest.
            raise ValueError("fault injection / deadline_s are group-mode "
                             "features; continuous=True does not support "
                             "them (docs/serving.md)")
        self.batch = batch
        self.scheduler = scheduler
        self.cfg = cfg
        self.continuous = continuous
        self._engine_kwargs = dict(batch=batch, max_len=max_len, seed=seed,
                                   eos_id=eos_id, continuous=continuous)
        self._calibration = calibration
        self._injector = injector
        self._max_retries = max_retries
        self._deadline_s = deadline_s
        self._backoff = dict(base_s=backoff_base_s, cap_s=backoff_cap_s)
        self._seed = seed
        self._warmup_plan: Optional[tuple] = None
        self.meshes = carve_submeshes(replicas, model_parallel=model_parallel,
                                      devices=devices)
        first = make_engine(cfg, self.meshes[0], batch=batch,
                            max_len=max_len, params=params, dims=dims,
                            seed=seed, eos_id=eos_id,
                            calibration=calibration, continuous=continuous)
        self.engines = [first]
        for mesh in self.meshes[1:]:
            # shared prepared planes: transfer, never re-prepare.
            # make_engine passes the PreparedWeight leaves through
            # (preparation is idempotent) and re-places raw leaves onto
            # the already-correct layout.
            self.engines.append(make_engine(
                cfg, mesh, batch=batch, max_len=max_len,
                params=transfer_tree(first.params, mesh), dims=first.dims,
                seed=seed, eos_id=eos_id, calibration=calibration,
                continuous=continuous))

        self._lock = threading.Lock()
        self._pending: List = []        # [(Request, Future)] awaiting a group
        self._inflight = [0] * replicas  # queued + running groups per replica
        self._rr = 0
        self._t0: Optional[float] = None
        self._stats: Dict[str, Any] = {
            "prefill_tokens": 0, "decode_tokens": 0, "requests": 0,
            "groups": 0, "busy_s": 0.0, "retries": 0, "failovers": 0,
            "requeued_requests": 0, "rebuilds": 0,
            "groups_per_replica": [0] * replicas}
        self.health = [ReplicaHealth() for _ in range(replicas)]
        self._events: List[Dict[str, Any]] = []
        self._streaming = None          # set by enable_streaming
        self._closed = False
        self._queues: List["queue.Queue"] = [queue.Queue()
                                             for _ in range(replicas)]
        worker = self._worker_continuous if continuous else self._worker
        self._workers = [
            threading.Thread(target=worker, args=(i,), daemon=True,
                             name=f"replica-serve-{i}")
            for i in range(replicas)]
        for t in self._workers:
            t.start()

    # -- worker ------------------------------------------------------------

    def _worker(self, idx: int):
        q = self._queues[idx]
        while True:
            job = q.get()
            if job is None:
                q.task_done()
                return
            try:
                self._run_job(idx, job)
            except BaseException as e:
                # defensive: _run_job owns failure handling; anything
                # escaping it (a bug in the failover path itself) must
                # not strand the futures.
                delivered = False
                for fut in job.futures:
                    if not fut.done():
                        fut.set_exception(e)
                        delivered = True
                if not delivered:
                    import traceback
                    print(f"replica-serve-{idx}: failure with no live "
                          f"futures to notify:", file=sys.stderr)
                    traceback.print_exception(type(e), e, e.__traceback__)
            finally:
                with self._lock:
                    self._inflight[idx] -= 1
                q.task_done()

    def _worker_continuous(self, idx: int):
        """Continuous-mode worker: one ``serve()`` absorbs queued traffic.

        Jobs carry single requests (``submit`` dispatches immediately,
        no group formation). The first blocking ``get`` starts an
        ``engine.serve()`` whose ``feed`` hook drains everything that
        queues up afterwards — new requests are admitted into free slots
        *between decode steps* of the in-flight ones, which is the
        continuous-batching scheduling the group worker cannot do. Each
        request's future resolves from serve's ``on_done`` callback, the
        moment that request finishes (not when its batch drains).
        """
        q = self._queues[idx]
        while True:
            job = q.get()
            if job is None:
                q.task_done()
                return
            if job.warmup is not None:
                try:
                    self._run_job(idx, job)
                except BaseException as e:
                    for fut in job.futures:
                        if not fut.done():
                            fut.set_exception(e)
                finally:
                    with self._lock:
                        self._inflight[idx] -= 1
                    q.task_done()
                continue
            jobs = [job]
            deferred: List[_Job] = []
            sentinel: List[Any] = []
            futmap = {id(r): f
                      for r, f in zip(job.requests, job.futures)}

            def feed():
                got: List[Request] = []
                while True:
                    try:
                        j = q.get_nowait()
                    except queue.Empty:
                        return got
                    if j is None:             # close() sentinel
                        sentinel.append(j)
                        return got
                    if j.warmup is not None:  # run after this serve pass
                        deferred.append(j)
                        continue
                    jobs.append(j)
                    for r, f in zip(j.requests, j.futures):
                        futmap[id(r)] = f
                    got.extend(j.requests)

            def on_done(req: Request):
                fut = futmap.pop(id(req), None)
                if fut is not None:
                    try:
                        fut.set_result(req)
                    except InvalidStateError:
                        pass

            try:
                stats = self.engines[idx].serve(
                    list(job.requests), feed=feed, on_done=on_done)
                with self._lock:
                    self.health[idx].record_success(stats["wall_s"])
                    self._stats["prefill_tokens"] += stats["prefill_tokens"]
                    self._stats["decode_tokens"] += stats["decode_tokens"]
                    n_req = sum(len(j.requests) for j in jobs)
                    self._stats["requests"] += n_req
                    self._stats["groups"] += len(jobs)
                    self._stats["groups_per_replica"][idx] += len(jobs)
                    self._stats["busy_s"] += stats["wall_s"]
            except BaseException as e:
                for j in jobs:
                    for fut in j.futures:
                        if not fut.done():
                            fut.set_exception(e)
            finally:
                with self._lock:
                    self._inflight[idx] -= len(jobs)
                for _ in jobs:
                    q.task_done()
            for j in deferred:
                try:
                    self._run_job(idx, j)
                except BaseException as e:
                    for fut in j.futures:
                        if not fut.done():
                            fut.set_exception(e)
                finally:
                    with self._lock:
                        self._inflight[idx] -= 1
                    q.task_done()
            if sentinel:
                q.task_done()   # the consumed None
                q.put(None)     # re-post: the next get() exits cleanly

    @staticmethod
    def _deliver(job: _Job, results):
        for r, fut in zip(results, job.futures):
            # a caller may have cancelled one future of the group while
            # it was queued; the batch still ran, so deliver the others
            # instead of poisoning them with the cancelled one's
            # InvalidStateError.
            try:
                fut.set_result(r)
            except InvalidStateError:
                pass

    @staticmethod
    def _reset_requests(requests: List[Request]):
        """Roll a group back to its as-submitted state before a re-run.

        A fault can land mid-decode, leaving partial ``out_tokens``;
        since engines are deterministic, a clean re-run of the *same*
        group reproduces every token bitwise — which is only true if the
        re-run starts from the same blank state the first run saw.
        """
        for r in requests:
            r.out_tokens.clear()
            r.done = False

    def _log_event(self, event: str, idx: int, **fields):
        rec = {"event": event, "replica": idx, "t": time.time(), **fields}
        with self._lock:
            self._events.append(rec)

    def _run_job(self, idx: int, job: _Job):
        engine = self.engines[idx]
        if job.warmup is not None:
            buckets, max_new, seed = job.warmup
            engine.warmup(buckets, max_new=max_new, seed=seed)
            self._deliver(job, [None] * len(job.futures))
            return
        attempts = 0
        while True:
            bound = (self._injector.bind(idx)
                     if self._injector is not None else None)
            try:
                stats = engine.run(job.requests, injector=bound,
                                   deadline_s=self._deadline_s)
            except BaseException as err:
                attempts += 1
                self._reset_requests(job.requests)
                poisoned = (err.device_ids
                            if isinstance(err, PoisonedDeviceError) else ())
                retryable = attempts <= self._max_retries and not poisoned
                with self._lock:
                    self.health[idx].record_failure(err)
                    if retryable:
                        self._stats["retries"] += 1
                self._log_event(
                    "fault", idx, attempt=attempts, retrying=retryable,
                    error=f"{type(err).__name__}: {err}")
                if retryable:
                    time.sleep(backoff_delay(attempts,
                                             seed=self._seed + idx,
                                             **self._backoff))
                    continue
                self._fail_replica(idx, job, err, poisoned)
                return
            with self._lock:
                self.health[idx].record_success(stats["wall_s"])
                if job.counted:
                    self._stats["prefill_tokens"] += stats["prefill_tokens"]
                    self._stats["decode_tokens"] += stats["decode_tokens"]
                    self._stats["requests"] += len(job.requests)
                    self._stats["groups"] += 1
                    self._stats["groups_per_replica"][idx] += 1
                    self._stats["busy_s"] += stats["wall_s"]
            self._deliver(job, job.requests)
            return

    # -- supervisor: drain, requeue, rebuild -------------------------------

    def _fail_replica(self, idx: int, job: _Job, err: BaseException,
                      poisoned=()):
        """Retries exhausted (or the device set is poisoned): fail over.

        Runs on the failing replica's own worker thread. Marks the
        replica ``rebuilding`` (the schedulers stop routing to it),
        drains its queue, requeues the queued + in-flight requests onto
        surviving replicas — whole groups, composition untouched, so the
        deterministic engines reproduce their logits bitwise — and then
        rebuilds a replacement engine on the healthy device subset. With
        no survivors, requests are held and dispatched to the rebuilt
        replica itself; only if the rebuild also fails do their futures
        carry the error.
        """
        t_detect = time.time()
        with self._lock:
            self.health[idx].force("rebuilding")
            self._stats["failovers"] += 1
        # drain: everything still queued behind the failed in-flight job
        q = self._queues[idx]
        drained, saw_sentinel = [job], False
        n_popped = 0
        while True:
            try:
                j = q.get_nowait()
            except queue.Empty:
                break
            n_popped += 1
            if j is None:        # close() sentinel: re-posted after rebuild
                saw_sentinel = True
                continue
            drained.append(j)
        requeue: List[_Job] = []
        for j in drained:
            if j.warmup is not None:   # warmup is best-effort; don't requeue
                self._deliver(j, [None] * len(j.futures))
                continue
            self._reset_requests(j.requests)
            requeue.append(j)
        n_requests = sum(len(j.requests) for j in requeue)
        with self._lock:
            self._inflight[idx] -= n_popped
            self._stats["requeued_requests"] += n_requests
            survivors = [i for i in range(len(self.engines))
                         if i != idx and self.health[i].schedulable()]
            if survivors:
                for j in requeue:
                    self._dispatch_locked(j)
                held = []
            else:
                held = requeue
        # the popped jobs were counted by their original put(); balance
        # the queue's join() accounting now that they live elsewhere.
        for _ in range(n_popped):
            q.task_done()
        self._log_event("drain_requeue", idx, requests=n_requests,
                        queued_jobs=len(drained) - 1,
                        survivors=len(survivors),
                        error=f"{type(err).__name__}: {err}")
        ok = self._rebuild_replica(idx, exclude=poisoned, t_detect=t_detect)
        if held:
            if ok:
                with self._lock:
                    for j in held:
                        self._dispatch_locked(j, idx=idx)
            else:
                for j in held:
                    for fut in j.futures:
                        if not fut.done():
                            fut.set_exception(err)
        if saw_sentinel:
            q.put(None)

    def _rebuild_replica(self, idx: int, exclude=(), *,
                         t_detect: float) -> bool:
        """Build a replacement engine on the replica's healthy devices.

        Re-meshes around the exclusion set
        (:func:`repro.runtime.elastic.replacement_mesh` keeps the model
        axis width) and constructs the engine from a *transfer* of a
        surviving engine's prepared planes (:func:`transfer_tree`) — a
        pure ``device_put``, no re-quantization, so
        ``quant.PREP_STATS`` stays flat across recovery and the
        replacement serves bit-identical logits by construction. Replays
        the driver's last warmup plan so the replica rejoins at full
        speed. Returns False (replica ``dead``) when fewer than
        model-axis-width healthy devices remain.
        """
        try:
            mesh = replacement_mesh(self.meshes[idx], exclude=exclude)
            with self._lock:
                donors = [i for i in range(len(self.engines))
                          if i != idx and self.health[i].schedulable()]
            donor = self.engines[donors[0]] if donors else self.engines[idx]
            # replay the donor's calibration history in version order:
            # the replacement retains every version (replay-serviceable)
            # and ends on the fleet's current runtime state, so it
            # serves — and replays — bit-identically to the survivors.
            # (Built bare when the donor holds tables — the donor's v1
            # is the authoritative first install, not the ctor table.)
            donor_tables = dict(donor._tables)
            engine = make_engine(
                self.cfg, mesh, params=transfer_tree(donor.params, mesh),
                dims=donor.dims,
                calibration=None if donor_tables else self._calibration,
                **self._engine_kwargs)
            for v in sorted(donor_tables):
                engine.apply_calibration(donor_tables[v])
            if donor._streaming is not None:
                engine.enable_streaming(
                    donor._streaming,
                    seed=donor._streaming.seed + idx)
            if self._warmup_plan is not None:
                buckets, max_new, seed = self._warmup_plan
                engine.warmup(buckets, max_new=max_new, seed=seed)
        except Exception as e:
            with self._lock:
                self.health[idx].force("dead")
            self._log_event("replica_dead", idx,
                            reason=f"{type(e).__name__}: {e}")
            return False
        self.engines[idx] = engine
        self.meshes[idx] = mesh
        with self._lock:
            self.health[idx].reset()
            self._stats["rebuilds"] += 1
        self._log_event("rebuilt", idx, excluded=list(exclude),
                        devices=len(list(mesh.devices.flat)),
                        recovery_s=time.time() - t_detect)
        return True

    # -- dispatch ----------------------------------------------------------

    def _schedulable_locked(self) -> List[int]:
        return [i for i in range(len(self._queues))
                if self.health[i].schedulable()]

    def _pick_replica_locked(self) -> int:
        live = self._schedulable_locked()
        if not live:
            raise RuntimeError("no schedulable replicas (all unhealthy or "
                               "rebuilding; see driver.stats()['health'])")
        if self.scheduler == "least_loaded":
            return min(live, key=lambda i: (
                self._inflight[i], self.health[i].state != "healthy", i))
        for _ in range(len(self._queues)):
            idx = self._rr
            self._rr = (self._rr + 1) % len(self._queues)
            if idx in live:
                return idx
        return live[0]

    def _dispatch_locked(self, job: _Job, idx: Optional[int] = None):
        if self._closed:
            raise RuntimeError("driver is closed")
        if idx is None:
            idx = self._pick_replica_locked()
        self._inflight[idx] += 1
        if job.counted and self._t0 is None:
            self._t0 = time.time()
        self._queues[idx].put(job)

    def _flush_locked(self):
        while self._pending:
            group = self._pending[:self.batch]
            del self._pending[:self.batch]
            self._dispatch_locked(_Job([r for r, _ in group],
                                       [f for _, f in group]))

    # -- public API --------------------------------------------------------

    @property
    def replicas(self) -> int:
        return len(self.engines)

    def submit(self, request: Request) -> Future:
        """Enqueue one request; returns a Future of the completed Request.

        Requests accumulate in arrival order until a full group of
        ``batch`` exists, which is then dispatched to a replica by the
        scheduler policy. A partial trailing group is dispatched by
        :meth:`flush` / :meth:`drain` (the engine pads it).
        """
        fut: Future = Future()
        with self._lock:
            if self._closed:
                raise RuntimeError("driver is closed")
            if self.continuous:
                # the request is the scheduling unit: dispatch now, the
                # replica's serve loop admits it at the next step
                # boundary (no group formation latency)
                self._dispatch_locked(_Job([request], [fut]))
            else:
                self._pending.append((request, fut))
                if len(self._pending) >= self.batch:
                    self._flush_locked()
        return fut

    def submit_many(self, requests: Sequence[Request]) -> List[Future]:
        """Submit a sequence of requests, preserving their order."""
        return [self.submit(r) for r in requests]

    def flush(self):
        """Dispatch any partial pending group immediately."""
        with self._lock:
            self._flush_locked()

    def drain(self):
        """Flush and block until every dispatched request has completed.

        Failover can move work between queues mid-drain (a failed
        replica's jobs requeue onto survivors — possibly onto a queue
        already joined this pass), so the join loops until a full pass
        finds every queue empty and nothing in flight.
        """
        self.flush()
        while True:
            for q in self._queues:
                q.join()
            with self._lock:
                busy = any(self._inflight) or bool(self._pending)
            if not busy:
                return

    def warmup(self, prompt_len: Optional[int] = None, max_new: int = 1, *,
               plen_buckets: Optional[Sequence[int]] = None, seed: int = 0):
        """Compile each replica's prefill/decode before traffic arrives.

        Pushes one uncounted warmup job to **every** replica — each runs
        :meth:`~repro.launch.serve.ServeEngine.warmup` over the prompt-
        length buckets on its own sub-mesh, so the R compilations proceed
        concurrently — then waits for all of them. Pass either a single
        ``prompt_len`` (the padded length real groups will compile for)
        or ``plen_buckets`` with every common padded length of the
        deployment (the bucketed-plen warmup; first-request latency then
        only hits lengths outside the buckets). Warmup traffic never
        enters :meth:`stats`.
        """
        if hasattr(prompt_len, "__iter__"):
            # a bucket list passed positionally — the natural call shape
            # after ServeEngine.warmup([...]); accept it rather than
            # failing on int(list) below
            if plen_buckets is not None:
                raise ValueError("pass exactly one of prompt_len / "
                                 "plen_buckets")
            prompt_len, plen_buckets = None, prompt_len
        if (prompt_len is None) == (plen_buckets is None):
            raise ValueError("pass exactly one of prompt_len / "
                             "plen_buckets")
        buckets = tuple(sorted({int(b) for b in (
            plen_buckets if plen_buckets is not None else [prompt_len])}))
        # remember the plan: a rebuilt replacement engine replays it
        # before rejoining the fleet (docs/replica_serving.md).
        self._warmup_plan = (buckets, max_new, seed)
        futs: List[Future] = []
        with self._lock:
            for idx in range(self.replicas):
                if self.health[idx].state == "dead":
                    continue      # nobody will ever consume its queue
                fut: Future = Future()
                futs.append(fut)
                self._dispatch_locked(
                    _Job([], [fut], counted=False,
                         warmup=(buckets, max_new, seed)), idx=idx)
        for fut in futs:
            fut.result()

    def calibrate(self, prompts=None, *, seed: int = 0) -> CalibrationTable:
        """One calibration pass, shared by every replica.

        Traces replica 0 (:meth:`ServeEngine.calibrate` — one eager
        prefill + decode step recording per-site activation limb PMFs)
        and installs the resulting table on all engines via
        :meth:`~repro.launch.serve.ServeEngine.apply_calibration`. Call
        while idle (before traffic, or after :meth:`drain`): installing a
        table rebuilds the jitted entry points.
        """
        self.drain()
        table = self.engines[0].calibrate(prompts, update=True, seed=seed)
        for engine in self.engines[1:]:
            engine.apply_calibration(table)
        return table

    # -- streaming calibration: fleet-wide versioned hot swap --------------

    def apply_calibration(self, table: CalibrationTable) -> int:
        """Push ``table`` to every schedulable replica — **without** drain.

        The fleet twin of :meth:`ServeEngine.apply_calibration`'s hot
        path: each engine swaps its runtime state between decode steps
        (the group engine at the next group boundary, the continuous
        engine behind its drain fence), so live traffic keeps flowing —
        zero recompiles, zero dropped requests. Call :meth:`calibrate`
        for the *first* install instead (that path rebuilds jits and
        must run idle). Returns the version the table was installed at
        (identical on every replica: versions advance in lockstep
        because every install goes through the driver).
        """
        with self._lock:
            live = [i for i in range(len(self.engines))
                    if self.health[i].state != "dead"]
        versions = [self.engines[i].apply_calibration(table) for i in live]
        self._log_event("calib_swap", -1, version=max(versions),
                        replicas=live)
        return max(versions)

    def enable_streaming(self, *, seed: int = 0, sample_period: int = 4,
                         **thresholds):
        """Attach one shared streaming calibrator to the whole fleet.

        Every replica feeds the same
        :class:`~repro.quant.streaming.StreamingRecorder` (it is
        thread-safe) through its own deterministic sampling gate —
        ``seed + replica`` staggers the gates so the replicas sample
        different traffic instead of all shadowing the same indices.
        Returns the shared calibrator; drive refreshes with
        :meth:`maybe_refresh_calibration`.
        """
        calibrator = self.engines[0].enable_streaming(
            seed=seed, sample_period=sample_period, **thresholds)
        for i, engine in enumerate(self.engines[1:], start=1):
            engine.enable_streaming(calibrator, seed=seed + i)
        self._streaming = calibrator
        return calibrator

    def maybe_refresh_calibration(self):
        """Drift-check the shared statistics; fleet hot-swap on drift.

        Returns the justifying
        :class:`~repro.quant.streaming.DriftReport` when a refresh
        happened, else ``None``. The refreshed table reaches every
        replica through :meth:`apply_calibration` (the no-drain push).
        """
        if getattr(self, "_streaming", None) is None:
            return None
        report = self._streaming.maybe_refresh(self.apply_calibration)
        if report is not None:
            self._log_event("calib_refresh", -1,
                            drifted_sites=list(report.drifted_sites))
        return report

    def replay(self, request: Request, version=None, *,
               group: Optional[List[Request]] = None):
        """Re-serve a logged request under its recorded table version.

        Routes to a schedulable replica that has the version's table
        retained (they all do when every install went through the
        driver) — since replicas are bit-identical by construction, any
        of them reproduces the original bits. Run while idle (after
        :meth:`drain`): replay borrows the engine's compiled entry
        points. See :meth:`ServeEngine.replay`.
        """
        want = request.table_version if version is None else version
        with self._lock:
            live = self._schedulable_locked()
        for i in live:
            if want == 0 or want in self.engines[i]._tables:
                return self.engines[i].replay(request, version,
                                              group=group)
        raise KeyError(f"no schedulable replica retains calibration "
                       f"version {want}")

    _COUNTERS = ("prefill_tokens", "decode_tokens", "requests", "groups",
                 "busy_s", "retries", "failovers", "requeued_requests",
                 "rebuilds")

    def events(self) -> List[Dict[str, Any]]:
        """Structured fault/recovery event log (chronological).

        Each entry carries ``event`` (``"fault"``, ``"drain_requeue"``,
        ``"rebuilt"``, ``"replica_dead"``), ``replica``, a ``t``
        timestamp, and event-specific fields (``recovery_s`` on
        ``"rebuilt"`` — the detect-to-serving latency the ``failover``
        benchmark reports).
        """
        with self._lock:
            return [dict(e) for e in self._events]

    def run(self, requests: Sequence[Request]) -> Dict[str, Any]:
        """Synchronous convenience mirroring ``ServeEngine.run``: submit
        everything, drain, return stats for **this call** (counter deltas
        over a wall clock spanning exactly this submit-to-drain window —
        :meth:`stats` stays cumulative since construction).

        The per-call numbers assume no *concurrent* submitters: traffic
        another thread pushes via :meth:`submit` during the window lands
        in the deltas (and :meth:`drain` waits for it). Mixing the sync
        and async APIs is safe for correctness, but read :meth:`stats`
        for the aggregate instead of trusting this return value."""
        with self._lock:
            base = {k: self._stats[k] for k in self._COUNTERS}
            base_groups = list(self._stats["groups_per_replica"])
        t0 = time.time()
        futs = self.submit_many(requests)
        self.drain()
        for fut in futs:
            fut.result()    # surface worker exceptions
        wall = max(time.time() - t0, 1e-9)
        with self._lock:
            out = {k: self._stats[k] - base[k] for k in self._COUNTERS}
            out["groups_per_replica"] = [
                g - b for g, b in zip(self._stats["groups_per_replica"],
                                      base_groups)]
        out["replicas"] = self.replicas
        out["scheduler"] = self.scheduler
        out["wall_s"] = wall
        out["requests_per_s"] = out["requests"] / wall
        out["decode_tok_per_s"] = out["decode_tokens"] / wall
        return out

    def stats(self) -> Dict[str, Any]:
        """Cumulative served-traffic statistics since construction.

        ``busy_s`` sums per-replica engine wall time (it exceeds
        ``wall_s`` when replicas overlap — that overlap *is* the
        data-parallel speedup); ``wall_s`` spans first counted dispatch
        to now, idle gaps included (use :meth:`run`'s return value for
        per-call rates). Warmup traffic is excluded.
        """
        with self._lock:
            out = dict(self._stats,
                       groups_per_replica=list(
                           self._stats["groups_per_replica"]))
            out["health"] = [h.snapshot() for h in self.health]
            t0 = self._t0
        out["replicas"] = self.replicas
        out["scheduler"] = self.scheduler
        out["wall_s"] = (time.time() - t0) if t0 is not None else 0.0
        wall = max(out["wall_s"], 1e-9)
        out["requests_per_s"] = out["requests"] / wall
        out["decode_tok_per_s"] = out["decode_tokens"] / wall
        return out

    def close(self):
        """Drain outstanding work and stop the worker threads."""
        with self._lock:
            if self._closed:
                return
            self._flush_locked()
            self._closed = True
        for q in self._queues:
            q.put(None)
        for t in self._workers:
            t.join()

    def __enter__(self) -> "ReplicaServeDriver":
        return self

    def __exit__(self, *exc):
        self.close()
        return False
