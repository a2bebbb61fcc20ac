"""Fault-injection transport wrapper for deterministic chaos tests.

Wraps any transport exposing ``request(address, payload, timeout)`` and
injects per-address faults *at the call site*, so the same scenarios run
against ``LocalTransport`` (in-process, deterministic) and
``TcpTransport`` (real sockets) without touching server code — the
ChaosMonkey analog, but seedable and replayable instead of killing OS
processes with signals.

Fault modes per address (composable):

- ``down``        — every request raises ``TransportError`` immediately
                    (dead server / connection refused).
- ``fail_next=n`` — the next ``n`` requests raise ``TransportError``,
                    then the address heals (transient blip).
- ``error_rate``  — each request fails with probability p, drawn from a
                    seeded RNG (flaky link; deterministic per seed).
- ``delay_s``     — sleep before forwarding (slow server / stragglers;
                    the hedged-request trigger).
- ``blackhole``   — sleep out the caller's full timeout budget, then
                    raise (packets dropped: no RST, just silence).

Every call is appended to ``calls`` (address, mode-applied) so tests can
assert exactly which replicas absorbed retries and hedges.

``DeviceFaultInjector`` is the same idea one layer down: it hooks the
server's DeviceLane (``engine/dispatch.py``) and injects *device-side*
faults — failed launches (retryable or poison), stalls that wedge the
lane thread (the watchdog trigger), and per-plan-digest poisoning — so
the self-healing path (device retry, watchdog restart, host failover,
poison quarantine) runs deterministically on a CPU test rig.

``NetworkFaultInjector`` is the partition layer: it models the NETWORK
between roles as directed links keyed by instance NAME (``src -> dst``)
rather than one server's address, so a single injector shared by every
role-pair transport (broker<->server scatter, server<->controller
heartbeat/commit/fetch, broker<->controller clusterstate poll) can cut,
delay, duplicate, or one-way-partition any link in the cluster.  The
physical model is per-DIRECTION packet loss: cutting ``a -> b`` loses
a's requests before they reach b (and b's replies to a ride ``b -> a``,
so cutting only that direction delivers a's request — side effects
happen at b! — and then loses the reply, which is exactly the
asymmetric-partition shape that makes lease fencing necessary).
"""
from __future__ import annotations

import random
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from pinot_tpu.transport.tcp import TransportError

Address = Tuple[str, int]


@dataclass
class FaultSpec:
    down: bool = False
    fail_next: int = 0
    error_rate: float = 0.0
    delay_s: float = 0.0
    blackhole: bool = False


@dataclass
class CallRecord:
    address: Address
    outcome: str  # "ok" | "down" | "fail_next" | "error_rate" | "blackhole" | "error"
    latency_s: float = 0.0


class FaultInjectingTransport:
    """Decorator transport: same ``request`` interface as the inner one."""

    def __init__(self, inner, seed: int = 0) -> None:
        self.inner = inner
        self._rng = random.Random(seed)
        self._faults: Dict[Address, FaultSpec] = {}
        self._lock = threading.Lock()
        self.calls: List[CallRecord] = []

    # -- fault programming --------------------------------------------
    def set_fault(self, address: Address, **kwargs: Any) -> FaultSpec:
        """Program faults for one address, e.g. ``set_fault(a, down=True)``
        or ``set_fault(a, delay_s=0.5)``.  Unspecified modes reset."""
        spec = FaultSpec(**kwargs)
        with self._lock:
            self._faults[address] = spec
        return spec

    def clear_fault(self, address: Address) -> None:
        with self._lock:
            self._faults.pop(address, None)

    def clear_all(self) -> None:
        with self._lock:
            self._faults.clear()

    def calls_to(self, address: Address) -> List[CallRecord]:
        with self._lock:
            return [c for c in self.calls if c.address == address]

    # -- transport interface ------------------------------------------
    def request(self, address: Address, payload: bytes, timeout: float = 15.0) -> bytes:
        with self._lock:
            spec = self._faults.get(address)
            if spec is not None:
                if spec.down:
                    self.calls.append(CallRecord(address, "down"))
                    raise TransportError(f"injected: server {address} down")
                if spec.fail_next > 0:
                    spec.fail_next -= 1
                    self.calls.append(CallRecord(address, "fail_next"))
                    raise TransportError(f"injected: transient failure at {address}")
                if spec.error_rate > 0.0 and self._rng.random() < spec.error_rate:
                    self.calls.append(CallRecord(address, "error_rate"))
                    raise TransportError(f"injected: flaky link to {address}")
            delay = spec.delay_s if spec is not None else 0.0
            blackhole = spec.blackhole if spec is not None else False
        if blackhole:
            time.sleep(timeout)
            with self._lock:
                self.calls.append(CallRecord(address, "blackhole", timeout))
            raise TransportError(f"injected: request to {address} blackholed")
        if delay > 0.0:
            time.sleep(delay)
        t0 = time.perf_counter()
        try:
            reply = self.inner.request(address, payload, timeout=timeout)
        except Exception:
            with self._lock:
                self.calls.append(
                    CallRecord(address, "error", time.perf_counter() - t0 + delay)
                )
            raise
        with self._lock:
            self.calls.append(CallRecord(address, "ok", time.perf_counter() - t0 + delay))
        return reply


# ---------------------------------------------------------------------------
# Device-side fault injection (the lane-supervision chaos hook)
# ---------------------------------------------------------------------------


@dataclass
class LaunchRecord:
    """One lane launch as seen by the injector (digest is the StaticPlan
    digest the executor handed the lane; None for raw key-only
    submits)."""

    digest: Optional[str]
    # "ok" | "fail_next" | "alloc_fail" | "error_rate" | "alloc_rate"
    # | "poison" | "stall" | "corrupt"
    outcome: str


class DeviceFaultInjector:
    """Seedable device-fault programming for the DeviceLane.

    Modes (composable, mirroring the transport injector):

    - ``fail_next(n, retryable=True)`` — the next ``n`` launches raise a
      typed ``DeviceExecutionError`` (transient blip or hard fault).
    - ``alloc_fail_next(n)``          — the next ``n`` launches raise a
      RAW RuntimeError with PJRT's RESOURCE_EXHAUSTED wording, so the
      executor's real ``classify_device_error`` path produces the
      ``resource_exhausted`` heal class (demote-then-retry, never
      poison) exactly as a full HBM would — deterministically testable
      without a real device.
    - ``stall_next(n, stall_s)``      — the next ``n`` launches sleep
      ``stall_s`` inside the lane thread before proceeding (the
      watchdog-restart trigger when ``stall_s`` exceeds the lane's
      stall timeout).
    - ``poison_plan(digest)``         — every launch whose StaticPlan
      digest matches raises a non-retryable poison error until
      ``heal()``; the executor's quarantine is expected to stop sending
      the plan to the device at all.
    - ``error_rate``                  — each launch fails (retryable)
      with probability p from a seeded RNG.
    - ``corrupt_results(n, tier=..., digest_substring=..., delta=...)``
      — WRONG-ANSWER injection for the audit plane (utils/audit.py):
      unlike every mode above, a corrupted execution SUCCEEDS — the
      executor consults ``check_corrupt`` after the tier produced its
      result and perturbs one numeric aggregation partial by ``delta``.
      No error is raised, so the self-healing ladder (retry, failover,
      poison) can NEVER catch it; only the shadow differential audit
      can.  The host tier is never corrupted (it is the oracle).
    - ``alloc_error_rate``            — each launch raises the raw
      RESOURCE_EXHAUSTED error with probability p from the same seeded
      RNG (sustained memory pressure, not a one-shot).

    Every launch decision is recorded in ``launches`` so tests can
    assert which plans were poisoned/stalled and read back digests.
    """

    def __init__(self, seed: int = 0) -> None:
        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        self.launches: List[LaunchRecord] = []
        self._fail_next = 0
        self._fail_retryable = True
        self._alloc_fail_next = 0
        self._stall_next = 0
        self._stall_s = 0.0
        self._poisoned: set = set()
        self.error_rate = 0.0
        self.alloc_error_rate = 0.0
        self._corrupt_next = 0
        self._corrupt_tier = ""
        self._corrupt_digest = ""
        self._corrupt_delta = 1.0

    # -- fault programming --------------------------------------------
    def fail_next(self, n: int, retryable: bool = True) -> None:
        with self._lock:
            self._fail_next = n
            self._fail_retryable = retryable

    def alloc_fail_next(self, n: int) -> None:
        with self._lock:
            self._alloc_fail_next = n

    def stall_next(self, n: int, stall_s: float) -> None:
        with self._lock:
            self._stall_next = n
            self._stall_s = stall_s

    def poison_plan(self, digest: str) -> None:
        with self._lock:
            self._poisoned.add(digest)

    def corrupt_results(
        self,
        n: int = 1,
        tier: str = "",
        digest_substring: str = "",
        delta: float = 1.0,
    ) -> None:
        """Arm wrong-answer injection: the next ``n`` executions whose
        serving tier matches ``tier`` (empty = any non-host tier) and
        whose plan-shape digest contains ``digest_substring`` get one
        numeric aggregation partial perturbed by ``delta``."""
        with self._lock:
            self._corrupt_next = n
            self._corrupt_tier = tier
            self._corrupt_digest = digest_substring
            self._corrupt_delta = delta

    @property
    def corruption_armed(self) -> bool:
        """Cheap pre-check so the executor only derives a plan digest
        for the consult when a corruption budget is actually armed."""
        return self._corrupt_next > 0

    def check_corrupt(self, plan_digest: Optional[str], tier: str) -> Optional[float]:
        """Executor consult after a tier produced a result: the delta to
        apply, or None.  Decrements the armed budget on a match."""
        with self._lock:
            if self._corrupt_next <= 0:
                return None
            if tier == "host":
                return None  # the oracle stays correct, always
            if self._corrupt_tier and tier != self._corrupt_tier:
                return None
            if self._corrupt_digest and self._corrupt_digest not in (
                plan_digest or ""
            ):
                return None
            self._corrupt_next -= 1
            self.launches.append(LaunchRecord(plan_digest, "corrupt"))
            return self._corrupt_delta

    def heal(self) -> None:
        with self._lock:
            self._fail_next = 0
            self._alloc_fail_next = 0
            self._stall_next = 0
            self._stall_s = 0.0
            self._poisoned.clear()
            self.error_rate = 0.0
            self.alloc_error_rate = 0.0
            self._corrupt_next = 0
            self._corrupt_tier = ""
            self._corrupt_digest = ""
            self._corrupt_delta = 1.0

    def records_for(self, outcome: str) -> List[LaunchRecord]:
        with self._lock:
            return [r for r in self.launches if r.outcome == outcome]

    # -- lane hook -----------------------------------------------------
    def on_launch(self, digest: Optional[str], key: Any) -> None:
        """Called by the lane thread immediately before a launch; may
        sleep (stall) or raise ``DeviceExecutionError``."""
        from pinot_tpu.engine.dispatch import DeviceExecutionError

        with self._lock:
            # a launch over L of a table's segments comes as
            # ``<planDigest>.L<L>`` (ladder.launch_digest): poisoned by
            # its plan, at every size
            if digest is not None and digest.partition(".L")[0] in self._poisoned:
                self.launches.append(LaunchRecord(digest, "poison"))
                raise DeviceExecutionError(
                    f"injected: poisoned plan {digest}", retryable=False
                )
            if self._alloc_fail_next > 0:
                self._alloc_fail_next -= 1
                self.launches.append(LaunchRecord(digest, "alloc_fail"))
                # a RAW error, not a pre-typed DeviceExecutionError: the
                # executor must exercise its real classification path
                # (dispatch.classify_device_error -> resource_exhausted)
                raise RuntimeError(
                    "injected: RESOURCE_EXHAUSTED: out of memory while "
                    "allocating device buffer"
                )
            if self._fail_next > 0:
                self._fail_next -= 1
                retryable = self._fail_retryable
                self.launches.append(LaunchRecord(digest, "fail_next"))
                raise DeviceExecutionError(
                    "injected: device launch failure", retryable=retryable
                )
            if (
                self.alloc_error_rate > 0.0
                and self._rng.random() < self.alloc_error_rate
            ):
                self.launches.append(LaunchRecord(digest, "alloc_rate"))
                raise RuntimeError(
                    "injected: RESOURCE_EXHAUSTED: out of memory while "
                    "allocating device buffer"
                )
            if self.error_rate > 0.0 and self._rng.random() < self.error_rate:
                self.launches.append(LaunchRecord(digest, "error_rate"))
                raise DeviceExecutionError(
                    "injected: flaky device launch", retryable=True
                )
            stall = 0.0
            if self._stall_next > 0:
                self._stall_next -= 1
                stall = self._stall_s
                self.launches.append(LaunchRecord(digest, "stall"))
            else:
                self.launches.append(LaunchRecord(digest, "ok"))
        if stall > 0.0:
            # sleep OUTSIDE the injector lock, inside the lane thread:
            # this is the wedge the watchdog must detect
            time.sleep(stall)


def apply_result_corruption(result, delta: float) -> bool:
    """Perturb one numeric field of ``result``'s first aggregation
    partial (scalar list or first group) in place — the wrong-answer the
    armed ``corrupt_results`` mode injects.  Returns True when a field
    was actually perturbed (selection-only results have no numeric
    partial to corrupt and stay untouched)."""
    partials = None
    aggs = getattr(result, "aggregations", None)
    if aggs:
        partials = aggs
    else:
        groups = getattr(result, "groups", None)
        if groups:
            partials = groups[next(iter(groups))]
    if not partials:
        return False
    p = partials[0]
    for attr in ("count", "total", "value", "mn", "mx"):
        v = getattr(p, attr, None)
        if isinstance(v, float):
            setattr(p, attr, v + float(delta))
            return True
    return False


# ---------------------------------------------------------------------------
# Link-level network fault injection (the partition-tolerance chaos hook)
# ---------------------------------------------------------------------------

# the controller's link name: every role-pair link has instance names at
# both ends, and the controller is a singleton role
CONTROLLER_LINK = "controller"


class PartitionedLinkError(TransportError):
    """Injected: the packet (request or reply) died on a cut link."""


@dataclass
class LinkSpec:
    """Quality degradation for one directed link (``src -> dst``).
    A cut link is tracked separately (``NetworkFaultInjector.cut``)."""

    delay_s: float = 0.0
    duplicate: bool = False  # deliver the request twice (at-least-once wire)
    error_rate: float = 0.0  # flaky link: seeded per-call loss probability


@dataclass
class LinkEvent:
    src: str
    dst: str
    # "ok" | "dropped" | "replyDropped" | "delayed" | "duplicated" | "flaky"
    outcome: str


class NetworkFaultInjector:
    """Seedable, name-keyed link-fault programming for EVERY role pair.

    One injector instance is shared by all the transports/HTTP clients
    of a cluster under test; each call site identifies itself with
    ``(src, dst)`` instance names and routes its RPC through ``call``:

    - ``cut(a, b)``                — packets a->b are dropped: a's
      requests to b raise ``PartitionedLinkError`` WITHOUT reaching b.
    - ``cut(b, a)`` (reply path)   — a's requests reach b (side effects
      happen!), but the reply is lost: a still sees a transport error.
      This is the one-way partition that distinguishes a live-but-
      unreachable server from a dead one.
    - ``partition(a, b)``          — both directions (symmetric cut).
    - ``set_link(a, b, ...)``      — delay / duplicate / seeded flaky
      loss on a live link.
    - ``heal(...)``                — clear one link, every link touching
      a node, or everything.

    Every decision is recorded in ``events`` (and optionally marked on a
    per-role metrics registry as ``netfaults.*``) so chaos tests can
    assert exactly which links absorbed the injected weather.
    """

    _EVENT_RING = 4096  # bounded: long harness runs must not grow RAM

    def __init__(self, seed: int = 0, metrics=None) -> None:
        from collections import deque

        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        self._cuts: set = set()  # directed (src, dst) pairs
        self._links: Dict[Tuple[str, str], LinkSpec] = {}
        self.events = deque(maxlen=self._EVENT_RING)
        # fallback registry; call sites pass their ROLE's registry per
        # call so netfaults.* lands on the role that saw the weather
        self.metrics = metrics

    # -- fault programming --------------------------------------------
    def cut(self, src: str, dst: str) -> None:
        """Drop packets flowing ``src -> dst`` (one direction only)."""
        with self._lock:
            self._cuts.add((src, dst))

    def partition(self, a: str, b: str) -> None:
        """Symmetric partition: no packets flow between ``a`` and ``b``."""
        with self._lock:
            self._cuts.add((a, b))
            self._cuts.add((b, a))

    def set_link(self, src: str, dst: str, **kwargs: Any) -> LinkSpec:
        spec = LinkSpec(**kwargs)
        with self._lock:
            self._links[(src, dst)] = spec
        return spec

    def heal(self, src: Optional[str] = None, dst: Optional[str] = None) -> None:
        """``heal()`` clears everything; ``heal(node)`` clears every cut
        and spec touching ``node``; ``heal(src, dst)`` clears that one
        directed link."""
        with self._lock:
            if src is None:
                self._cuts.clear()
                self._links.clear()
            elif dst is None:
                self._cuts = {c for c in self._cuts if src not in c}
                self._links = {
                    k: v for k, v in self._links.items() if src not in k
                }
            else:
                self._cuts.discard((src, dst))
                self._links.pop((src, dst), None)

    def is_cut(self, src: str, dst: str) -> bool:
        with self._lock:
            return (src, dst) in self._cuts

    def events_for(self, src: str, dst: str) -> List[LinkEvent]:
        with self._lock:
            return [e for e in self.events if e.src == src and e.dst == dst]

    def _record(self, src: str, dst: str, outcome: str, metrics=None) -> None:
        with self._lock:
            self.events.append(LinkEvent(src, dst, outcome))
        m = metrics if metrics is not None else self.metrics
        if m is not None and outcome != "ok":
            m.meter(f"netfaults.{outcome}").mark()

    # -- the one call-site hook ----------------------------------------
    def call(self, src: str, dst: str, fn, metrics=None):
        """Run one RPC (``fn``) over the ``src -> dst`` link.

        May raise ``PartitionedLinkError`` WITHOUT invoking ``fn``
        (request lost), may invoke ``fn`` and then raise (reply lost on
        the cut ``dst -> src`` direction — the asymmetric case), may
        sleep first (delay), may invoke ``fn`` twice and return the
        SECOND reply (duplicate delivery: upstream handlers must be
        idempotent — exactly what the at-least-once message board and
        the epoch/lease commit fences are for).  ``metrics`` is the
        CALLING role's registry for the ``netfaults.*`` attribution."""
        with self._lock:
            request_cut = (src, dst) in self._cuts
            reply_cut = (dst, src) in self._cuts
            spec = self._links.get((src, dst))
            flaky = (
                spec is not None
                and spec.error_rate > 0.0
                and self._rng.random() < spec.error_rate
            )
        if request_cut:
            self._record(src, dst, "dropped", metrics)
            raise PartitionedLinkError(f"injected: link {src}->{dst} is cut")
        if flaky:
            self._record(src, dst, "flaky", metrics)
            raise PartitionedLinkError(f"injected: flaky link {src}->{dst}")
        if spec is not None and spec.delay_s > 0.0:
            self._record(src, dst, "delayed", metrics)
            time.sleep(spec.delay_s)
        if spec is not None and spec.duplicate:
            # duplicate delivery: the first invocation's reply is
            # discarded, as a retransmitted request's would be
            self._record(src, dst, "duplicated", metrics)
            fn()
        reply = fn()
        if reply_cut:
            # the request executed at dst; the caller never learns
            self._record(src, dst, "replyDropped", metrics)
            raise PartitionedLinkError(
                f"injected: reply lost on cut link {dst}->{src}"
            )
        self._record(src, dst, "ok", metrics)
        return reply


def call_on_controller_link(injector, src: str, fn, metrics=None):
    """Shared call-site helper: run one controller-bound RPC through
    ``injector`` as link ``src -> controller`` (plain call when no
    injector is wired).  Used by both networked starters and the
    gateway edge so the link contract lives in one place."""
    if injector is None:
        return fn()
    return injector.call(src, CONTROLLER_LINK, fn, metrics=metrics)


class LinkFaultTransport:
    """Transport decorator consulting a ``NetworkFaultInjector`` per
    request — the broker<->server scatter hook.  ``resolve`` maps a
    transport address to the destination's instance name; the default
    takes ``address[0]``, which IS the name for ``LocalTransport``
    addresses (networked brokers pass a reverse lookup over their
    server-address map)."""

    def __init__(
        self, inner, injector: NetworkFaultInjector, src: str, resolve=None,
        metrics=None,
    ) -> None:
        self.inner = inner
        self.injector = injector
        self.src = src
        self.metrics = metrics  # the owning role's registry (netfaults.*)
        self._resolve = resolve or (lambda address: str(address[0]))

    def request(self, address: Address, payload: bytes, timeout: float = 15.0) -> bytes:
        dst = self._resolve(address)
        return self.injector.call(
            self.src,
            dst,
            lambda: self.inner.request(address, payload, timeout=timeout),
            metrics=self.metrics,
        )
