"""Continuous correctness audit plane (ISSUE 19).

Wrong answers are the failure mode the self-healing ladder (PR 3) can
NEVER catch: a device tier that returns a plausible-but-incorrect
payload raises nothing, so retry/failover/poison all stay silent and the
error ships to the client.  This module closes that gap with two
background samplers that re-derive ground truth and compare:

- ``ShadowAuditor`` (server-side): re-executes a seeded 1-in-N sample of
  completed production queries against the always-correct host oracle
  (``QueryExecutor.execute_host_oracle``) over the EXACT views the
  production reply served (``query_view()`` snapshots pin mutable
  segments at their row watermark, so the re-execution sees the same
  staged generation; the result cache is bypassed by construction).
  Payloads are compared after stripping accounting (the PR 3
  differential contract — ``numDocsScanned`` etc. legitimately differ
  per tier) with a bounded numeric tolerance (``payloads_equivalent``):
  a float32 device sum and the float64 host oracle honestly wobble with
  accumulation order, and rank groups whose values tie within that
  tolerance differently.  A divergence increments ``audit.divergences``,
  dumps a
  flight-recorder bundle carrying both payloads + tier/residency state,
  and quarantines the (plan digest, tier) via the executor's poison map
  so the lying tier stops serving that shape.

  The oracle streams: ``QueryExecutor.host_oracle_steps`` is the pass
  as a generator, one step a block of ``config.HOST_BLOCK_ROWS`` rows
  (``engine/host_fallback.py``: float64 partial states carried from
  block to block, every row of every view still read), and the worker
  drives the steps on its own thread: no numpy call holds the
  interpreter lock for longer than a block takes, so a pass over 134M
  rows hands the interpreter over whenever a serving thread has waited
  a switch interval, instead of holding it for a segment's whole call.
  Series: meter ``audit.offered`` (every N-th eligible answer, before
  budget and queue) beside
  ``audit.samples`` (passes finished), ``audit.dropped``,
  ``audit.errors`` and ``audit.divergences``; timer ``audit.stepMs``
  (one update a step, the worker's processor time) with gauge ``audit.stepMaxMs`` (the longest of
  the timer's retained steps); timer ``audit.shadowMs``, the wall time
  of a whole pass, which is also the ``pinot:auditPass`` annotation on
  the profiler's clock, tagged with the plan digest and shape.

- ``ReplicaAuditor`` (broker-side): occasionally re-issues a sampled
  query's first batch to BOTH the original server and an alternate
  covering replica and compares the (accounting-stripped) reduced
  payloads — the replica-divergence detector.  Restricted to
  non-realtime physical tables: realtime replicas consume independently,
  so an offset-drift "divergence" would be noise, not corruption.

Both samplers draw from ONE process-wide token budget
(``PINOT_TPU_AUDIT_BUDGET_PER_S``), so the audit plane's total overhead
is bounded regardless of how many tables/brokers sample.  The work
itself runs on background worker threads modeled on
``server/prewarm.py`` — bounded queue, drop-don't-block, never on the
serving path.

Knobs:

- ``PINOT_TPU_AUDIT_SAMPLE_N``    shadow sample rate (1-in-N completed
                                  queries), default 64; 0 disables.
- ``PINOT_TPU_AUDIT_REPLICA_N``   replica sample rate, default 256;
                                  0 disables.
- ``PINOT_TPU_AUDIT_BUDGET_PER_S``shared token budget, default 8/s.
"""
from __future__ import annotations

import logging
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

logger = logging.getLogger(__name__)

# every auditor that ever started a thread, for the test-suite leak
# guard (same contract as prewarm._workers: only a STOPPED auditor
# whose thread survives is a leak)
_workers: List[Any] = []
_workers_lock = threading.Lock()


def leaked_audit_threads(grace_s: float = 2.0) -> List[str]:
    """Names of audit threads of STOPPED auditors still alive after
    ``grace_s`` of joining (conftest guard)."""
    deadline = time.monotonic() + grace_s
    leaked: List[str] = []
    with _workers_lock:
        workers = list(_workers)
    for w in workers:
        t = w._thread
        if t is None or not w._stop.is_set():
            continue
        t.join(timeout=max(0.0, deadline - time.monotonic()))
        if t.is_alive():
            leaked.append(t.name)
    return leaked


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


class SamplerBudget:
    """Token bucket shared by EVERY sampler in the process: the "one
    sampler budget" that bounds total audit overhead.  ``take()`` is a
    non-blocking permit check — a sample denied a token is simply not
    audited (counted by the caller as dropped), never queued."""

    def __init__(self, per_s: Optional[float] = None, burst: float = 4.0) -> None:
        self.per_s = (
            per_s
            if per_s is not None
            else _env_float("PINOT_TPU_AUDIT_BUDGET_PER_S", 8.0)
        )
        self.burst = max(1.0, burst)
        self._tokens = self.burst
        self._last = time.monotonic()
        self._lock = threading.Lock()

    def take(self, n: float = 1.0) -> bool:
        if self.per_s <= 0:
            return False
        now = time.monotonic()
        with self._lock:
            self._tokens = min(
                self.burst, self._tokens + (now - self._last) * self.per_s
            )
            self._last = now
            if self._tokens >= n:
                self._tokens -= n
                return True
            return False


# THE shared budget (both auditors in a process draw from it; tests may
# swap in private instances)
BUDGET = SamplerBudget()


# accounting fields every byte-identity differential strips (the PR 3
# contract, extended with freshnessMs): wall-clock, per-tier work
# counters, and scatter topology legitimately differ between a
# production tier and the host oracle / an alternate replica — the DATA
# fields (selection rows, aggregation values, totalDocs, exceptions)
# must not.
ACCOUNTING_FIELDS = (
    "timeUsedMs",
    "requestId",
    "cost",
    "numDocsScanned",
    "numEntriesScannedInFilter",
    "numEntriesScannedPostFilter",
    "numSegmentsQueried",
    "numServersQueried",
    "numServersResponded",
    "numSegmentsUnserved",
    "partialResponse",
    "numRetries",
    "numHedges",
    "freshnessMs",
    "planDigest",
    "traceInfo",
    "explain",
)


def strip_accounting(payload: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(payload)
    for key in ACCOUNTING_FIELDS:
        out.pop(key, None)
    return out


def canonical_payload(request, result) -> Dict[str, Any]:
    """One IntermediateResult -> the comparable client payload: run the
    REAL broker reduce over it (so formatting, trimming, and ordering
    are exactly what a client would see), then strip accounting."""
    from pinot_tpu.engine.reduce import reduce_to_response

    return strip_accounting(reduce_to_response(request, [result], []).to_json())


def _as_number(x: Any) -> Optional[float]:
    if isinstance(x, bool):
        return None
    if isinstance(x, (int, float)):
        return float(x)
    if isinstance(x, str):
        try:
            return float(x)
        except ValueError:
            return None
    return None


def _ranked_groups_equivalent(a, b, rel_tol: float, abs_tol: float) -> bool:
    """Two ``groupByResult`` lists (groups ranked by value) that differ
    only in the order of groups whose values tie within the tolerance.

    The rank of two groups whose values lie closer than the band that
    forgives each value says nothing more than the values do: at 134M
    rows two of the six groups of a TPC-H Q1 shape held sums 3e-7 apart
    on one seed of some fifty, the device's float32 sum and the oracle's
    float64 one rank them differently, and a byte-exact order
    quarantined the healthy device tier (PERF.md, PR 29).  So: the same
    set of group labels, byte-exact; every group's value close to the
    same group's value on the other side; and the value at every rank
    close to the value at that rank on the other side, which leaves a
    group no place but among its ties.  A group missing, a label
    altered, a value off or a rank taken from a group outside the band
    still reads as divergence."""
    entries = [g for side in (a, b) for g in side]
    if not entries or not all(
        isinstance(g, dict) and g.keys() == {"group", "value"} for g in entries
    ):
        return False
    theirs = {tuple(g["group"]): g["value"] for g in b}
    if len(theirs) != len(b) or {tuple(g["group"]) for g in a} != theirs.keys():
        return False
    return all(
        payloads_equivalent(x["value"], theirs[tuple(x["group"])], rel_tol, abs_tol)
        and payloads_equivalent(x["value"], y["value"], rel_tol, abs_tol)
        for x, y in zip(a, b)
    )


def payloads_equivalent(
    a: Any, b: Any, rel_tol: float = 5e-4, abs_tol: float = 1e-3
) -> bool:
    """Structural payload equality with a numeric tolerance on leaves,
    exact everywhere else.

    Why not byte identity: a float32 device sum and the float64 host
    oracle legitimately disagree (accumulation order + precision), and
    byte-comparing the formatted values would quarantine healthy tiers.
    The tolerance is sized for float32 tree-reduction noise at real scan
    sizes — relative error grows ~sqrt(n)·eps, so a 10M-row sum honestly
    wobbles ~2e-4; 5e-4 covers that with margin (an earlier 1e-5 draft
    false-positived on a clean 1M-row Q1 sum and quarantined the healthy
    device tier).  Genuine wrong answers — a corrupted tier, a dropped
    segment, a stale replica — shift aggregates by whole values, orders
    of magnitude above the band, and the exact-aggregate contract (ints,
    min/max, counts) still compares exactly: identical values are always
    close.  Structure, keys, ordering, group labels, and non-numeric
    strings remain byte-exact.  One order is not: groups of a
    ``groupByResult`` whose values tie within the same band may stand in
    either order (``_ranked_groups_equivalent``)."""
    if isinstance(a, dict) and isinstance(b, dict):
        if a.keys() != b.keys():
            return False
        return all(
            payloads_equivalent(a[k], b[k], rel_tol, abs_tol) for k in a
        )
    if isinstance(a, (list, tuple)) and isinstance(b, (list, tuple)):
        if len(a) != len(b):
            return False
        return all(
            payloads_equivalent(x, y, rel_tol, abs_tol)
            for x, y in zip(a, b)
        ) or _ranked_groups_equivalent(a, b, rel_tol, abs_tol)
    if a == b:
        return True
    na, nb = _as_number(a), _as_number(b)
    if na is None or nb is None:
        return False
    import math

    return math.isclose(na, nb, rel_tol=rel_tol, abs_tol=abs_tol)


def selection_equivalent(request, produced, oracle) -> bool:
    """A sorted selection's reply held to the host oracle's rows by the
    rule SQL states, where ties leave more than one right answer:
    ``produced`` and ``oracle`` are the two ``IntermediateResult``s, the
    oracle's holding EVERY matching row with its sort values
    (``host_fallback``).  The reply's window (offset, size) has the
    oracle's window's length and its key at every rank (so every row
    under the cut's key is there as often as the table holds it), and the
    rows it shows at a key are drawn from the table's rows at that key,
    none oftener than the table holds it.  A different choice among rows
    tied at the cut is then no divergence; a row from above the cut, a row
    the table lacks and a row repeated are."""
    from collections import Counter

    from pinot_tpu.engine.reduce import ordered_selection_rows

    sel = request.selection
    start, end = sel.offset, sel.offset + sel.size
    have = ordered_selection_rows(sel, produced.selection_rows or [])[start:end]
    every = ordered_selection_rows(sel, oracle.selection_rows or [])
    if [keys for keys, _ in have] != [keys for keys, _ in every[start:end]]:
        return False
    if not have:
        return True
    # the table's rows at the window's keys: the window, widened over the ties at both its ends
    end = start + len(have)
    while start > 0 and every[start - 1][0] == have[0][0]:
        start -= 1
    while end < len(every) and every[end][0] == have[-1][0]:
        end += 1
    held = Counter((repr(keys), repr(row)) for keys, row in every[start:end])
    shown = Counter((repr(keys), repr(row)) for keys, row in have)
    return all(n <= held[entry] for entry, n in shown.items())


def results_equivalent(request, produced, oracle) -> bool:
    """What the shadow auditor holds a production result to: the
    canonical payloads equivalent (``payloads_equivalent``), or, for a
    selection under an ORDER BY whose rows differ, everything but the
    rows equivalent and the rows another right answer
    (``selection_equivalent``)."""
    a, b = canonical_payload(request, produced), canonical_payload(request, oracle)
    if payloads_equivalent(a, b):
        return True
    if not (request.is_selection and request.selection.sorts):
        return False
    rest = lambda payload: dict(payload, selectionResults=dict(payload.get("selectionResults") or {}, results=None))
    return payloads_equivalent(rest(a), rest(b)) and selection_equivalent(request, produced, oracle)


# ---------------------------------------------------------------------------
# Server-side shadow differential auditor
# ---------------------------------------------------------------------------


class ShadowAuditor:
    """Background differential checker for one ``ServerInstance``.

    ``offer()`` is the serving-path hook (``_process_traced``, after a
    successful execution): a deterministic 1-in-N counter plus the
    shared token budget decide whether the completed query is queued
    for shadow re-execution.  Holding the offered ``views`` pins the
    exact snapshot production served; the worker replays the request on
    the host oracle and compares canonical payloads."""

    _QUEUE_MAX = 16
    _DIVERGENCE_RING = 16
    def __init__(
        self,
        instance,
        sample_n: Optional[int] = None,
        budget: Optional[SamplerBudget] = None,
    ) -> None:
        self.instance = instance
        self.sample_n = (
            sample_n
            if sample_n is not None
            else _env_int("PINOT_TPU_AUDIT_SAMPLE_N", 64)
        )
        self.budget = budget if budget is not None else BUDGET
        self.metrics = instance.metrics
        for m in (
            "audit.offered", "audit.samples", "audit.divergences",
            "audit.dropped", "audit.errors", "audit.quarantines",
        ):
            self.metrics.meter(m)
        step_ms = self.metrics.timer("audit.stepMs")
        self.metrics.gauge("audit.stepMaxMs").set_fn(lambda: step_ms.percentile(100))
        self._count = 0
        self._queue: deque = deque()
        self._divergences: deque = deque(maxlen=self._DIVERGENCE_RING)
        self._trigger = threading.Event()
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self.metrics.gauge("audit.queueDepth").set_fn(lambda: len(self._queue))

    @property
    def enabled(self) -> bool:
        return self.sample_n > 0

    # -- serving-path hook (must stay cheap) ---------------------------
    def offer(self, req: dict, request, views, result) -> bool:
        """Called inline after a successful non-explain, non-join
        execution.  The fast path is one counter increment; only the
        1-in-N winners pay the budget check and enqueue."""
        if not self.enabled or self._stop.is_set():
            return False
        self._count += 1
        if self._count % self.sample_n:
            return False
        if (
            result.exceptions
            or request.explain
            or request.join is not None
            or getattr(result, "_served_tier", None) in (None, "host")
        ):
            # host-served replies ARE the oracle — re-checking them
            # could only burn budget agreeing with itself
            return False
        self.metrics.meter("audit.offered").mark()
        if not self.budget.take():
            self.metrics.meter("audit.dropped").mark()
            return False
        job = {
            "requestId": str(req.get("requestId") or ""),
            "table": req.get("table", ""),
            "request": request,
            "views": list(views),
            "result": result,
            "enqueuedAt": time.monotonic(),
        }
        with self._lock:
            if len(self._queue) >= self._QUEUE_MAX:
                self.metrics.meter("audit.dropped").mark()
                return False
            self._queue.append(job)
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run,
                    name=f"audit-{self.instance.name}",
                    daemon=True,
                )
                with _workers_lock:
                    _workers.append(self)
                self._thread.start()
        self._trigger.set()
        return True

    def stop(self) -> None:
        self._stop.set()
        self._trigger.set()
        t = self._thread
        if t is not None:
            t.join(timeout=5)
        with self._lock:
            self._queue.clear()

    # -- worker --------------------------------------------------------
    def _run(self) -> None:
        while not self._stop.is_set():
            if not self._trigger.wait(timeout=0.5):
                continue
            self._trigger.clear()
            while not self._stop.is_set():
                with self._lock:
                    job = self._queue.popleft() if self._queue else None
                if job is None:
                    break
                try:
                    self._audit_one(job)
                except Exception:
                    # a sick audit must never kill the worker — one
                    # sample is lost, the next drains normally
                    logger.exception("shadow audit failed")
                    self.metrics.meter("audit.errors").mark()

    def _between_steps(self) -> bool:
        """On the worker, after every step of a pass: True to go on.
        The steps are what gives the interpreter up: no numpy call of a
        step holds the lock for longer than a block takes (most release
        it outright), and where a serving thread has waited a switch
        interval the interpreter hands it over at the next bytecode,
        which a block is never far from.  No sleep is taken here: on
        the chip's host a release of the worker's own between blocks
        (``time.sleep(0)``) made a pass a fifth longer, a sleep of 1 ms
        tripled a step, and a thread that sleeps 1 ms at a time woke no
        later beside a pass without either (PERF.md, PR 29)."""
        return not self._stop.is_set()

    def _oracle_pass(self, job: dict, digest: str):
        """One pass of the streamed host oracle over the job's views:
        step by step on this thread, each step timed on the thread's own
        processor clock (``audit.stepMs``: what the worker can have kept
        the interpreter for; a stall of the whole machine, which a wall
        clock would read into the step it falls in, is no part of it).
        ``audit.shadowMs`` and the ``pinot:auditPass`` annotation are
        the pass's wall time.  None if the auditor was stopped before
        the pass ended."""
        from pinot_tpu.utils.trace import boundary

        request = job["request"]
        shape = (
            "groupBy" if request.is_group_by
            else "aggregation" if request.is_aggregation
            else "selection"
        )
        step_ms = self.metrics.timer("audit.stepMs")
        steps = self.instance.executor.host_oracle_steps(job["views"], request)
        with boundary(
            "auditPass", timer=self.metrics.timer("audit.shadowMs"),
            requestId=job["requestId"], digest=digest, shape=shape,
        ):
            while True:
                t0 = time.thread_time()
                try:
                    next(steps)
                except StopIteration as done:
                    return done.value
                step_ms.update((time.thread_time() - t0) * 1000.0)
                if not self._between_steps():
                    steps.close()
                    return None  # the server is going down: the pass is abandoned

    def _audit_one(self, job: dict) -> None:
        from pinot_tpu.engine.plandigest import plan_shape_digest

        request = job["request"]
        digest = plan_shape_digest(request)
        oracle = self._oracle_pass(job, digest)
        if oracle is None:
            return
        self.metrics.meter("audit.samples").mark()
        if results_equivalent(request, job["result"], oracle):
            return
        produced = canonical_payload(request, job["result"])
        expected = canonical_payload(request, oracle)
        # -- divergence: the device (or an optimization tier) lied -----
        tier = getattr(job["result"], "_served_tier", "unknown")
        detect_ms = (time.monotonic() - job["enqueuedAt"]) * 1000.0
        self.metrics.meter("audit.divergences").mark()
        self.metrics.meter("audit.quarantines").mark()
        self.metrics.timer("audit.detectMs").update(detect_ms)
        self.instance.executor.audit_quarantine(
            digest, tier, f"shadow differential mismatch ({job['requestId']})"
        )
        record = {
            "requestId": job["requestId"],
            "table": job["table"],
            "planDigest": digest,
            "tier": tier,
            "detectMs": round(detect_ms, 3),
            "ts": round(time.time(), 3),
        }
        self._divergences.append(record)
        logger.warning(
            "AUDIT DIVERGENCE: tier %s served a wrong answer for shape %s "
            "(request %s) — quarantined", tier, digest, job["requestId"],
        )
        self.instance.flightrec.maybe_dump(
            "auditDivergence",
            {
                **record,
                "producedPayload": produced,
                "expectedPayload": expected,
            },
        )

    # -- observability -------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        return {
            "enabled": self.enabled,
            "sampleN": self.sample_n,
            "budgetPerS": self.budget.per_s,
            "completed": self._count,
            "offered": self.metrics.meter("audit.offered").count,
            "samples": self.metrics.meter("audit.samples").count,
            "divergences": self.metrics.meter("audit.divergences").count,
            "dropped": self.metrics.meter("audit.dropped").count,
            "errors": self.metrics.meter("audit.errors").count,
            "queueDepth": len(self._queue),
            "recentDivergences": list(self._divergences),
            "quarantined": self.instance.executor.audit_quarantined_snapshot(),
        }


# ---------------------------------------------------------------------------
# Broker-side replica divergence auditor
# ---------------------------------------------------------------------------


class ReplicaAuditor:
    """Background replica cross-checker for one broker.

    ``offer()`` samples completed, successful, non-join, non-explain,
    non-partial queries; the worker re-issues the query's FIRST batch
    to both the original server and an alternate covering replica and
    compares the reduced, accounting-stripped payloads.  Realtime
    physical tables are excluded — their replicas consume the stream
    independently, so honest offset drift would read as divergence."""

    _QUEUE_MAX = 8
    _DIVERGENCE_RING = 16

    def __init__(
        self,
        broker,
        sample_n: Optional[int] = None,
        budget: Optional[SamplerBudget] = None,
    ) -> None:
        self.broker = broker
        self.sample_n = (
            sample_n
            if sample_n is not None
            else _env_int("PINOT_TPU_AUDIT_REPLICA_N", 256)
        )
        self.budget = budget if budget is not None else BUDGET
        self.metrics = broker.metrics
        for m in (
            "audit.replicaChecks", "audit.replicaDivergences",
            "audit.replicaDropped", "audit.replicaErrors",
        ):
            self.metrics.meter(m)
        self._count = 0
        self._queue: deque = deque()
        self._divergences: deque = deque(maxlen=self._DIVERGENCE_RING)
        self._trigger = threading.Event()
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None

    @property
    def enabled(self) -> bool:
        return self.sample_n > 0

    def offer(
        self,
        request,
        batches,
        request_id: str,
        timeout_ms: float,
        resp,
    ) -> bool:
        """Serving-path hook (end of ``_handle_admitted``): cheap
        counter first, then eligibility, then the shared budget."""
        if not self.enabled or self._stop.is_set() or not batches:
            return False
        self._count += 1
        if self._count % self.sample_n:
            return False
        if (
            request.explain
            or request.join is not None
            or resp.exceptions
            or resp.partial_response
        ):
            return False
        batch = batches[0]
        if batch.table.endswith("_REALTIME"):
            return False
        if not self.broker.routing.has_alternate(
            batch.table, list(batch.segments), {batch.server}
        ):
            return False  # replication factor 1: nothing to cross-check
        if not self.budget.take():
            self.metrics.meter("audit.replicaDropped").mark()
            return False
        job = {
            "requestId": request_id,
            "table": batch.table,
            "pql": batch.pql,
            "segments": list(batch.segments),
            "server": batch.server,
            "timeoutMs": float(timeout_ms),
            "enqueuedAt": time.monotonic(),
        }
        with self._lock:
            if len(self._queue) >= self._QUEUE_MAX:
                self.metrics.meter("audit.replicaDropped").mark()
                return False
            self._queue.append(job)
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run,
                    name=f"replica-audit-{self.broker.name}",
                    daemon=True,
                )
                with _workers_lock:
                    _workers.append(self)
                self._thread.start()
        self._trigger.set()
        return True

    def stop(self) -> None:
        self._stop.set()
        self._trigger.set()
        t = self._thread
        if t is not None:
            t.join(timeout=5)
        with self._lock:
            self._queue.clear()

    # -- worker --------------------------------------------------------
    def _run(self) -> None:
        while not self._stop.is_set():
            if not self._trigger.wait(timeout=0.5):
                continue
            self._trigger.clear()
            while not self._stop.is_set():
                with self._lock:
                    job = self._queue.popleft() if self._queue else None
                if job is None:
                    break
                try:
                    self._audit_one(job)
                except Exception:
                    logger.exception("replica audit failed")
                    self.metrics.meter("audit.replicaErrors").mark()

    def _reduced(self, request, parts) -> Dict[str, Any]:
        from pinot_tpu.engine.reduce import reduce_to_response

        return strip_accounting(reduce_to_response(request, parts, []).to_json())

    def _audit_one(self, job: dict) -> None:
        from pinot_tpu.pql import optimize_request, parse_pql

        request = optimize_request(parse_pql(job["pql"]))
        assignment, leftover = self.broker.routing.alternates(
            job["table"], job["segments"], {job["server"]}
        )
        if leftover or not assignment:
            return  # the alternate cover evaporated since the offer
        aid = f"{job['requestId']}-raudit"
        primary = self.broker._send_one(
            job["server"], job["table"], job["pql"], job["segments"],
            trace=False, debug_options=None, timeout_ms=job["timeoutMs"],
            attempt_timeout_ms=None, request_id=f"{aid}-p",
        )
        alternates = [
            self.broker._send_one(
                server, job["table"], job["pql"], list(segments),
                trace=False, debug_options=None, timeout_ms=job["timeoutMs"],
                attempt_timeout_ms=None, request_id=f"{aid}-a",
            )
            for server, segments in sorted(assignment.items())
        ]
        if primary.exceptions or any(a.exceptions for a in alternates):
            return  # an errored re-issue proves nothing about data
        self.metrics.meter("audit.replicaChecks").mark()
        lhs = self._reduced(request, [primary])
        rhs = self._reduced(request, alternates)
        divergent = not payloads_equivalent(lhs, rhs)
        record = {
            "requestId": job["requestId"],
            "table": job["table"],
            "server": job["server"],
            "alternates": sorted(assignment),
            "divergent": divergent,
            "detectMs": round(
                (time.monotonic() - job["enqueuedAt"]) * 1000.0, 3
            ),
            "ts": round(time.time(), 3),
        }
        # cross-link: the slow-query log entry (when recorded) gains the
        # audit verdict, so /debug/queries answers "was this checked?"
        self.broker.querylog.annotate(
            job["requestId"], auditRef={"type": "replica", "divergent": divergent}
        )
        if not divergent:
            return
        self.metrics.meter("audit.replicaDivergences").mark()
        self._divergences.append(record)
        logger.warning(
            "REPLICA DIVERGENCE: %s vs %s disagree on table %s (request %s)",
            job["server"], sorted(assignment), job["table"], job["requestId"],
        )
        self.broker.flightrec.maybe_dump(
            "replicaDivergence",
            {**record, "primaryPayload": lhs, "alternatePayload": rhs},
        )

    def snapshot(self) -> Dict[str, Any]:
        return {
            "enabled": self.enabled,
            "sampleN": self.sample_n,
            "budgetPerS": self.budget.per_s,
            "offered": self._count,
            "checks": self.metrics.meter("audit.replicaChecks").count,
            "divergences": self.metrics.meter("audit.replicaDivergences").count,
            "dropped": self.metrics.meter("audit.replicaDropped").count,
            "errors": self.metrics.meter("audit.replicaErrors").count,
            "queueDepth": len(self._queue),
            "recentDivergences": list(self._divergences),
        }
