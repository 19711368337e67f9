"""CNNService: continuous-batching image inference over a BinArrayProgram.

The serving loop the ROADMAP names ("a real inference *service* over
compiled programs"): a bounded request queue with per-request deadlines
feeding dynamic batch assembly into ``deploy.execute``, governed by the
§IV-D degradation ladder (:mod:`repro.serve_cnn.slo`).  The robustness
contract, enforced fault class by fault class in tests/test_serve_cnn.py:

  every fault is **retried, shed, or degraded — never a silent wrong
  answer, never a stuck queue.**

Dispositions:

  * **transient executor failures** (raised exceptions, NaN/Inf outputs
    caught by the finite screen) — bounded retry with exponential backoff;
    a batch that exhausts retries fails *loudly*: its requests return
    ``status="failed"`` with the error attached, counted in
    ``stats["exec_failed_batches"]``, and the queue keeps draining.
  * **latency pressure** — the SLO controller degrades the ``m_active``
    schedule down the ladder (cheaper batches) before anything is dropped,
    and recovers to full-M when the windowed p99 clears.
  * **overload** — explicit admission control: a full queue, an
    already-expired deadline, or controller-commanded shedding rejects the
    request *at submit* with a named reason (``stats["shed"]``), instead of
    letting the queue grow without bound.  Requests whose deadline expires
    while queued are shed at dispatch, not executed past their deadline.
  * **in-memory program corruption** — a budgeted watchdog
    (``selftest_every``) replays the compile-time golden probe
    (``deploy.self_test``) on the active rung every N batches and on every
    rung change; a digest mismatch quarantines the live program and
    hot-reloads the last-known-good checkpoint
    (``deploy.load_latest_good``), re-runs the self-test, and resumes —
    counted in ``stats`` (``selftest_runs`` / ``selftest_failures`` /
    ``reloads`` / ``quarantined_steps``) and *loud* (the original
    ``SelfTestFailure`` propagates) when no checkpoint manager was wired
    or the recovery walk exhausts.

Batches are always zero-padded to the configured ``batch_size``, so the
executor sees one input shape and compiles exactly one variant per ladder
rung — and every response is bit-exact against ``deploy.execute`` on the
same padded batch at the same schedule (``last_batch``/``last_schedule``
expose the pair for exactly that check).

Determinism hooks: ``clock``/``sleep`` are injectable (tests pass
``testing.faults.ManualClock``) and ``execute_fn`` defaults to looking up
``repro.deploy.executor.execute`` *at call time*, so the fault injector's
module patch (``testing.faults.inject_faults``) is visible without the
service opting in — while ``repro.deploy.execute`` stays the clean
reference for bit-exactness checks.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import time

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.deploy.program import BinArrayProgram
from repro.serve_cnn.slo import SLOConfig, SLOController, default_ladder

SHED_REASONS = ("queue_full", "deadline_expired", "slo_shed")
# the stages of one step(), in order; each is a ``binarray.serve.<stage>``
# span inside the step's ``binarray.serve.step`` span, and a sum of seconds
# in ``stats["stage_s"]``
STAGES = ("watchdog", "assemble", "execute", "screen", "fetch", "respond")


class NonFiniteOutput(RuntimeError):
    """The executor returned NaN/Inf logits — a wrong answer that must never
    reach a client silently.  Raised by the service's finite screen and
    handled exactly like a transient executor fault (retried, then failed
    loudly)."""


@dataclasses.dataclass
class ImageRequest:
    """One inference request and its full lifecycle record.

    ``deadline_s`` is an *absolute* time on the service clock (None = no
    deadline).  ``status`` walks pending -> queued -> done | shed | failed;
    shed requests carry ``shed_reason`` (one of :data:`SHED_REASONS`),
    failed ones carry ``error``.  Completed requests carry the served
    ``logits``, the resolved ``m_schedule``/``rung`` they were computed at,
    their ``batch_index`` into the padded batch, and ``latency_s``.
    Requests taken into a batch carry ``dispatch_t`` (service clock),
    ``queue_wait_s`` (``dispatch_t - submit_t``) and ``step``, the id of the
    ``step()`` that served them (the ``step`` of its trace span).
    """

    image: np.ndarray
    deadline_s: float | None = None
    id: int = -1
    status: str = "pending"
    shed_reason: str | None = None
    error: str | None = None
    logits: np.ndarray | None = None
    m_schedule: tuple[int, ...] | None = None
    rung: int | None = None
    batch_index: int | None = None
    submit_t: float = 0.0
    latency_s: float | None = None
    dispatch_t: float | None = None
    queue_wait_s: float | None = None
    step: int | None = None


class CNNService:
    """SLO-governed continuous-batching inference over one compiled program.

    Parameters
    ----------
    program:      the compiled :class:`BinArrayProgram` to serve.
    slo:          :class:`SLOConfig`; ``target_ms=None`` (default) pins the
                  ladder at ``initial_rung`` and never sheds on pressure.
    ladder:       degradation schedules; default :func:`default_ladder`.
    batch_size:   padded device batch (one compiled variant per rung).
    max_queue:    admission bound; beyond it requests shed ``queue_full``.
    max_retries:  executor re-attempts per batch before failing loudly.
    backoff_s:    base of the exponential retry backoff.
    clock/sleep:  time sources (injectable for deterministic tests).
    execute_fn:   ``fn(program, x, m_active, *, interpret)``; default
                  late-binds ``repro.deploy.executor.execute`` so
                  fault-injection patches apply.
    interpret:    Pallas interpret override passed through to the executor.
    mesh_plan:    optional :class:`repro.distributed.MeshPlan` — batches are
                  served through ``distributed.execute_sharded`` (bit-exact
                  vs the single-device path, so every SLO/degradation
                  contract carries over unchanged).  ``batch_size`` must
                  divide evenly over the plan's data axis: the service
                  always pads to ``batch_size``, and an uneven split would
                  silently waste a device column every step.
    selftest_every: run the golden self-test (``deploy.self_test``, always
                  the *clean* execute path — the BIST diagnoses the program,
                  not the fault harness) on the active rung every this-many
                  served batches, plus once at startup and on every rung
                  change.  Requires the program to carry a GoldenRecord.
                  None (default) disables the watchdog.
    checkpoint_manager / restore_like: recovery source for the watchdog —
                  on a self-test failure the live program is quarantined
                  and ``deploy.load_latest_good(checkpoint_manager,
                  restore_like)`` hot-reloads the newest checkpoint that
                  passes digests + verification + self-test.  Without them
                  a self-test failure raises (loud, by design).
    """

    def __init__(self, program: BinArrayProgram, *,
                 slo: SLOConfig | None = None,
                 ladder=None,
                 batch_size: int = 4,
                 max_queue: int = 16,
                 max_retries: int = 2,
                 backoff_s: float = 0.01,
                 clock=time.monotonic,
                 sleep=time.sleep,
                 execute_fn=None,
                 interpret: bool | None = None,
                 mesh_plan=None,
                 initial_rung: int = 0,
                 selftest_every: int | None = None,
                 checkpoint_manager=None,
                 restore_like: BinArrayProgram | None = None):
        if batch_size < 1 or max_queue < 1:
            raise ValueError(
                f"batch_size ({batch_size}) and max_queue ({max_queue}) "
                "must be >= 1")
        if selftest_every is not None:
            if selftest_every < 1:
                raise ValueError(
                    f"selftest_every must be >= 1, got {selftest_every}")
            if program.golden is None:
                raise ValueError(
                    "selftest_every requires a program with a GoldenRecord "
                    "(deploy.compile(..., golden=True), the default)")
        self.program = program
        self.batch_size = int(batch_size)
        self.max_queue = int(max_queue)
        self.max_retries = int(max_retries)
        self.backoff_s = float(backoff_s)
        self.clock = clock
        self.sleep = sleep
        self.interpret = interpret
        if mesh_plan is not None:
            if len(mesh_plan.shards) != len(program.instrs):
                raise ValueError(
                    f"mesh_plan carries {len(mesh_plan.shards)} shard(s) "
                    f"for a {len(program.instrs)}-instruction program")
            if batch_size % mesh_plan.n_data:
                raise ValueError(
                    f"batch_size={batch_size} must divide over the mesh "
                    f"data axis (n_data={mesh_plan.n_data}): the service "
                    f"pads every batch to batch_size, so an uneven split "
                    f"wastes a device column every step")
        self.mesh_plan = mesh_plan
        self._execute_fn = execute_fn
        self.selftest_every = selftest_every
        self.checkpoint_manager = checkpoint_manager
        self.restore_like = restore_like
        self._last_selftest_batch: int | None = None
        self.last_reload_step: int | None = None
        self.quarantined_program: BinArrayProgram | None = None
        self.controller = SLOController(
            tuple(ladder) if ladder is not None else default_ladder(program),
            slo, initial_rung=initial_rung)
        self.queue: collections.deque[ImageRequest] = collections.deque()
        self._ids = itertools.count()
        self._step_ids = itertools.count()
        self._latencies = collections.deque(maxlen=512)
        self._schedules_seen: set[tuple[int, ...]] = set()
        self.last_batch: np.ndarray | None = None
        self.last_schedule: tuple[int, ...] | None = None
        self._stats = {
            "admitted": 0, "completed": 0, "failed": 0, "batches": 0,
            "retries": 0, "exec_exceptions": 0, "nonfinite_detected": 0,
            "exec_failed_batches": 0, "shed_count": 0,
            "shed": {r: 0 for r in SHED_REASONS},
            "fault_types": {}, "rung_hist": {},
            "selftest_runs": 0, "selftest_failures": 0, "reloads": 0,
            "quarantined_steps": 0,
            "batch_slots": 0, "images_batched": 0,
            "stage_s": {name: 0.0 for name in STAGES},
        }
        self._last_rung = self.controller.rung

    # ------------------------------------------------------------ admit ---
    def submit(self, image, deadline_s: float | None = None) -> ImageRequest:
        """Admit one image; returns the request (check ``status``).

        Malformed inputs raise ``ValueError`` (caller bug, not load).
        Admission sheds — full queue, dead-on-arrival deadline, controller
        shedding — set ``status="shed"`` + ``shed_reason`` and count in
        ``stats``; they are the explicit backpressure signal.
        """
        image = np.asarray(image, np.float32)
        want = tuple(self.program.input_shape[1:])
        if image.shape != want:
            raise ValueError(
                f"request image has shape {image.shape}; program "
                f"{self.program.arch!r} serves {want} "
                f"(input_shape={self.program.input_shape})")
        req = ImageRequest(image=image, deadline_s=deadline_s,
                           id=next(self._ids), submit_t=self.clock())
        if deadline_s is not None and deadline_s <= req.submit_t:
            return self._shed(req, "deadline_expired")
        if self.controller.shedding and len(self.queue) >= self.batch_size:
            # controller-commanded shedding is *backpressure*, not an
            # outage: up to one batch's worth stays admitted so the system
            # keeps serving (and keeps measuring — without fresh latency
            # samples the controller could never observe recovery and
            # shedding would latch forever); everything that would queue
            # beyond that is shed
            return self._shed(req, "slo_shed")
        if len(self.queue) >= self.max_queue:
            return self._shed(req, "queue_full")
        req.status = "queued"
        self.queue.append(req)
        self._stats["admitted"] += 1
        return req

    def _shed(self, req: ImageRequest, reason: str) -> ImageRequest:
        req.status = "shed"
        req.shed_reason = reason
        self._stats["shed"][reason] += 1
        self._stats["shed_count"] += 1
        return req

    # ------------------------------------------------------------- step ---
    def step(self) -> list[ImageRequest]:
        """Serve one batch: assemble, execute at the controller's rung with
        bounded retry, screen for non-finite outputs, record latencies, run
        one SLO update.  Returns every request that left the system this
        step (done, failed, or shed-at-dispatch).  The integrity watchdog
        (when configured) runs *before* batch assembly, so a corrupt program
        is replaced before it can answer this step's requests.

        Each call is one ``binarray.serve.step`` profiler span (``step``,
        ``rung``, ``filled``) around one child span per stage of
        :data:`STAGES`; each stage's seconds on ``clock`` add up in
        ``stats["stage_s"]``."""
        step_id = next(self._step_ids)
        with TraceAnnotation("binarray.serve.step", step=step_id,
                             rung=self.controller.rung) as span:
            if self.selftest_every is not None:
                with self._stage("watchdog"):
                    self._watchdog()
            with self._stage("assemble"):
                finished, batch, x_np, x = self._assemble(step_id)
            span.set_metadata(filled=len(batch))
            if not batch:
                return finished
            rung = self.controller.rung
            sched = self.controller.schedule
            out, err = self._run_batch(x, rung, sched)
            with self._stage("respond"):
                self._respond(batch, out, err, rung, sched, x_np, finished)
            return finished

    @contextlib.contextmanager
    def _stage(self, name: str):
        """One stage of :meth:`step`: a profiler span, and its seconds on
        ``clock`` added to ``stats["stage_s"][name]``."""
        t0 = self.clock()
        try:
            with TraceAnnotation(f"binarray.serve.{name}"):
                yield
        finally:
            self._stats["stage_s"][name] += self.clock() - t0

    def _assemble(self, step_id: int):
        """Dequeue up to ``batch_size`` live requests (shedding those whose
        deadline passed in the queue) and zero-pad them into the device
        batch.  Returns (shed requests, batch, host batch, device batch)."""
        finished: list[ImageRequest] = []
        batch: list[ImageRequest] = []
        while self.queue and len(batch) < self.batch_size:
            req = self.queue.popleft()
            now = self.clock()
            if req.deadline_s is not None and req.deadline_s <= now:
                finished.append(self._shed(req, "deadline_expired"))
                continue
            req.dispatch_t = now
            req.queue_wait_s = now - req.submit_t
            req.step = step_id
            batch.append(req)
        if not batch:
            return finished, batch, None, None
        shape = (self.batch_size,) + tuple(self.program.input_shape[1:])
        x_np = np.zeros(shape, np.float32)
        for i, req in enumerate(batch):
            x_np[i] = req.image
        self._stats["batch_slots"] += self.batch_size
        self._stats["images_batched"] += len(batch)
        return finished, batch, x_np, jnp.asarray(x_np)

    def _run_batch(self, x, rung: int, sched):
        """Execute with bounded retry and screen the logits.  Returns (host
        logits or None, the last error)."""
        out, err = None, None
        for attempt in range(self.max_retries + 1):
            try:
                with self._stage("execute"):
                    y = self._execute(x, sched)
                with self._stage("screen"):
                    finite = bool(jnp.all(jnp.isfinite(y)))
                if not finite:
                    self._stats["nonfinite_detected"] += 1
                    raise NonFiniteOutput(
                        f"non-finite logits at rung {rung} "
                        f"(schedule {sched})")
                with self._stage("fetch"):
                    out = np.asarray(y)
                break
            except Exception as e:  # noqa: BLE001 — disposition by contract
                err = e
                name = type(e).__name__
                self._stats["fault_types"][name] = (
                    self._stats["fault_types"].get(name, 0) + 1)
                if not isinstance(e, NonFiniteOutput):
                    self._stats["exec_exceptions"] += 1
                if attempt < self.max_retries:
                    self._stats["retries"] += 1
                    with self._stage("execute"):    # the retry's backoff
                        self.sleep(self.backoff_s * (2 ** attempt))
        return out, err

    def _respond(self, batch, out, err, rung: int, sched, x_np,
                 finished: list[ImageRequest]) -> None:
        """Record the batch's outcome on each request and in ``stats``, and
        run one SLO update."""
        self._stats["batches"] += 1
        self._stats["rung_hist"][rung] = (
            self._stats["rung_hist"].get(rung, 0) + 1)
        self._schedules_seen.add(sched)
        self.last_batch = x_np
        self.last_schedule = sched

        now = self.clock()
        if out is None:
            # loud failure: requests carry the error, queue keeps draining
            self._stats["exec_failed_batches"] += 1
            for req in batch:
                req.status = "failed"
                req.error = repr(err)
                req.rung = rung
                finished.append(req)
        else:
            for i, req in enumerate(batch):
                req.status = "done"
                req.logits = out[i]
                req.m_schedule = sched
                req.rung = rung
                req.batch_index = i
                req.latency_s = now - req.submit_t
                self.controller.observe(req.latency_s)
                self._latencies.append(req.latency_s)
                self._stats["completed"] += 1
                finished.append(req)
        self.controller.update()

    # --------------------------------------------------------- watchdog ---
    def _watchdog(self) -> None:
        """Budgeted integrity check: golden self-test on the active rung
        every ``selftest_every`` served batches and on every rung change
        (each compiled rung variant gets re-attested when it comes live)."""
        rung = self.controller.rung
        due = (rung != self._last_rung
               or self._last_selftest_batch is None
               or (self._stats["batches"] - self._last_selftest_batch
                   >= self.selftest_every))
        self._last_rung = rung
        if not due:
            return
        self._last_selftest_batch = self._stats["batches"]
        self._selftest_rungs(self._watch_rungs(self.program))

    def _watch_rungs(self, program):
        """The active rung when the golden record covers it, else full-M
        (rung 0 of golden_rungs — always recorded)."""
        sched = program.resolve_schedule(self.controller.schedule)
        if program.golden.digest_for(sched) is not None:
            return (sched,)
        return (program.resolve_schedule(None),)

    def _selftest_rungs(self, rungs) -> None:
        from repro.deploy.selftest import SelfTestFailure, self_test

        self._stats["selftest_runs"] += 1
        try:
            self_test(self.program, rungs=rungs)
        except SelfTestFailure as e:
            self._stats["selftest_failures"] += 1
            self._recover(e)

    def _recover(self, cause) -> None:
        """Quarantine the live program and hot-reload the last-known-good
        checkpoint.  Loud when recovery is impossible: without a wired
        checkpoint manager the original failure propagates, and an
        exhausted walk raises ``NoGoodCheckpoint`` — a service that cannot
        prove its answers right anymore must not keep serving."""
        self.quarantined_program = self.program
        if self.checkpoint_manager is None or self.restore_like is None:
            raise cause
        from repro.deploy.compiler import load_latest_good
        from repro.deploy.selftest import self_test

        before = len(self.checkpoint_manager.quarantined)
        step, fresh = load_latest_good(
            self.checkpoint_manager, self.restore_like)
        self._stats["quarantined_steps"] += (
            len(self.checkpoint_manager.quarantined) - before)
        # the walk already self-tested every recorded rung; re-run on the
        # rung this service is actually serving as the explicit resume gate
        self._stats["selftest_runs"] += 1
        self_test(fresh, rungs=self._watch_rungs(fresh))
        self.program = fresh
        self._stats["reloads"] += 1
        self.last_reload_step = step

    def _execute(self, x, sched):
        if self._execute_fn is not None:
            return self._execute_fn(self.program, x, sched,
                                    interpret=self.interpret)
        if self.mesh_plan is not None:
            from repro.distributed import executor as dist_executor

            return dist_executor.execute_sharded(
                self.program, self.mesh_plan, x, m_active=sched,
                interpret=self.interpret)
        # late binding: resolve the module attribute at call time so a
        # testing.faults.inject_faults patch is seen (deploy.execute — the
        # import-time binding — stays clean for reference outputs)
        from repro.deploy import executor

        return executor.execute(self.program, x, sched,
                                interpret=self.interpret)

    def drain(self, max_steps: int = 10_000) -> list[ImageRequest]:
        """Step until the queue is empty; returns everything that finished.
        Bounded (a stuck queue raises instead of spinning forever)."""
        done: list[ImageRequest] = []
        for _ in range(max_steps):
            if not self.queue:
                return done
            done.extend(self.step())
        raise RuntimeError(
            f"queue failed to drain within {max_steps} steps "
            f"({len(self.queue)} requests left)")

    # ------------------------------------------------------------ stats ---
    @property
    def stats(self) -> dict:
        """Counters + derived latency quantiles (p50/p99 over a bounded
        window) + controller state.  ``shed`` is by-reason; ``fault_types``
        is by-exception-class; ``rung_hist`` is batches served per rung —
        the degradation histogram the acceptance criteria name."""
        out = {k: (dict(v) if isinstance(v, dict) else v)
               for k, v in self._stats.items()}
        out["queue_depth"] = len(self.queue)
        out["rung"] = self.controller.rung
        out["shedding"] = self.controller.shedding
        lat = sorted(self._latencies)
        if lat:
            out["p50_latency_s"] = lat[len(lat) // 2]
            out["p99_latency_s"] = lat[min(len(lat) - 1,
                                           int(0.99 * len(lat)))]
        return out

    def cache_gauges(self) -> dict:
        """Flat-by-contract gauges for ``repro.testing.soak``: the executor's
        compiled-variant counters plus the service's own distinct-schedule
        count (bounded by the ladder length — a growing value means the
        controller is inventing schedules)."""
        from repro.deploy import executor

        gauges = dict(executor.cache_gauges())
        gauges["svc_schedules_seen"] = (
            lambda: float(len(self._schedules_seen)))
        return gauges
