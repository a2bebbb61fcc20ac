"""Multi-host serving topology: broker PQL answered by a (hosts, chips)
mesh (the single-program ICI+DCN path wired into the
serving stack, not just the SPMD harness).

The reference scales serving across machines only by scatter-gather
over TCP (``ScatterGatherImpl.java:80``): every server computes its own
partial and the broker merges.  A TPU pod slice offers a second,
stronger topology: all hosts of the slice run ONE sharded program, XLA
merges partials over ICI within a host and DCN across hosts, and the
broker talks to a single endpoint.  This module is that server mode:

- every host process builds the global (hosts, chips) mesh via
  ``jax.distributed`` (``parallel/multihost.py``) and owns the SAME
  table/segment view (each device holds its shard of the stacked
  segment axis — XLA partitions the arrays, so per-host HBM holds only
  its slice);
- the LEAD host (process 0) serves the framework's length-framed
  query protocol to brokers, so it drops into ``BrokerRequestHandler``
  routing like any scatter-gather server;
- because the program is SPMD, every process must enter the kernel for
  its collectives to complete: the lead forwards each InstanceRequest
  to the followers over the data-plane TCP transport *before* running
  it locally, and a per-process FIFO (one in-flight query, matching
  arrival order) keeps collective ordering identical everywhere —
  jax.distributed requires identical program order across processes.

The lead's reply alone carries the answer (psum leaves the reduced
value on every process; the followers' copies are dropped), so the
broker sees an ordinary single-server response with the whole mesh's
throughput behind it.
"""
from __future__ import annotations

import concurrent.futures
import logging
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Sequence, Tuple

from pinot_tpu.segment.immutable import ImmutableSegment
from pinot_tpu.server.instance import ServerInstance
from pinot_tpu.transport.tcp import TcpServer, TcpTransport

logger = logging.getLogger(__name__)


class MultihostQueryServer:
    """One host process of a mesh-serving group.

    Call :meth:`connect_followers` on the lead (process 0) once every
    follower's TCP address is known; then point a broker at
    ``lead.address``.
    """

    def __init__(
        self,
        table: str,
        segments: Sequence[ImmutableSegment],
        coordinator_address: Optional[str],
        num_processes: int,
        process_id: int,
        name: Optional[str] = None,
        host: str = "127.0.0.1",
        port: int = 0,
    ) -> None:
        from pinot_tpu.parallel.multihost import (
            flatten_to_segment_mesh,
            initialize_distributed,
            make_multihost_mesh,
        )

        initialize_distributed(coordinator_address, num_processes, process_id)
        mesh = flatten_to_segment_mesh(make_multihost_mesh())
        self.process_id = process_id
        self.is_lead = process_id == 0
        self.name = name or f"meshhost{process_id}"
        # num_workers=1: queries execute strictly in arrival order —
        # the SPMD contract (identical collective order on every
        # process) forbids concurrent kernels
        self.server = ServerInstance(self.name, mesh=mesh, num_workers=1)
        for seg in segments:
            self.server.add_segment(table, seg)
        self._followers: List[Tuple[str, int]] = []
        self._transport = TcpTransport()
        self._fanout = ThreadPoolExecutor(max_workers=8)
        self._order_lock = threading.Lock()
        # set when a follower failed AFTER the query was forwarded: the
        # collective program order across processes is no longer
        # trustworthy (survivors may be wedged in a psum barrier) and
        # jax.distributed cannot re-admit a restarted process — the
        # recovery contract is an immediate typed error on every
        # subsequent query until the serving group is restarted
        self.degraded: Optional[str] = None
        self.tcp = TcpServer(self._handle, host=host, port=port)
        self.tcp.start()

    @property
    def address(self) -> Tuple[str, int]:
        return self.tcp.address

    def connect_followers(self, addresses: Sequence[Tuple[str, int]]) -> None:
        self._followers = [tuple(a) for a in addresses]

    PING = b"\x00MESHPING"
    PONG = b"\x00MESHPONG"

    def _error_reply(self, msg: str) -> bytes:
        from pinot_tpu.common.datatable import serialize_result
        from pinot_tpu.common.response import ErrorCode
        from pinot_tpu.engine.results import IntermediateResult

        logger.error("%s", msg)
        return serialize_result(
            IntermediateResult(exceptions=[(ErrorCode.QUERY_EXECUTION, msg)])
        )

    # -- query path ----------------------------------------------------
    def _handle(self, payload: bytes) -> bytes:
        if payload == self.PING:
            return self.PONG
        if self.degraded is not None:
            return self._error_reply(
                f"mesh serving group degraded ({self.degraded}); "
                "restart the group to re-form the jax.distributed mesh"
            )
        with self._order_lock:
            if self.degraded is not None:
                # a query blocked on the lock while the one ahead of it
                # degraded the group must NOT proceed into the dead
                # collective
                return self._error_reply(
                    f"mesh serving group degraded ({self.degraded}); "
                    "restart the group to re-form the jax.distributed mesh"
                )
            # Liveness preflight BEFORE forwarding anything: once any
            # follower holds the query it will enter the collective, so
            # discovering a dead peer after forwarding would wedge the
            # survivors in the psum barrier.  The short ping timeout
            # also catches network-partitioned hosts whose connects
            # hang rather than refuse.  A follower dying between ping
            # and kernel entry is left to jax.distributed's own
            # failure detection.
            ping_futs = [
                self._fanout.submit(self._transport.request, addr, self.PING, 5.0)
                for addr in self._followers
            ]
            down = []
            for addr, f in zip(self._followers, ping_futs):
                try:
                    if f.result(timeout=6.0) != self.PONG:
                        down.append((addr, "bad ping reply"))
                except Exception as e:
                    down.append((addr, e))
            if down:
                msg = "; ".join(f"{a}: {e}" for a, e in down)
                return self._error_reply(f"mesh followers unreachable: {msg}")
            # forward, then run locally (awaiting follower replies
            # before running would deadlock the collective)
            futures = [
                self._fanout.submit(self._transport.request, addr, payload, 600.0)
                for addr in self._followers
            ]
            # The hard failure window: a follower dying
            # BETWEEN the preflight ping and collective entry.  Its
            # request future fails fast (connection reset / refused),
            # while a healthy follower's future stays pending until it
            # finishes executing — so a short grace watch that reacts
            # only to EXCEPTIONS distinguishes the two.  Aborting
            # before the lead enters the kernel keeps this process out
            # of the doomed psum barrier; the group is still marked
            # degraded because other followers may already be in it.
            # FIRST_EXCEPTION returns the moment a forward fails; the
            # healthy path always pays the full grace (followers cannot
            # reply before the lead runs its kernel), so the default is
            # a small fixed latency tax chosen against localhost/ICI
            # connect-failure times — tune per deployment via env.
            try:
                grace = float(os.environ.get("PINOT_TPU_MESH_FORWARD_GRACE_S", "0.05"))
            except ValueError:
                grace = 0.05
            done, _pending = concurrent.futures.wait(
                futures, timeout=grace,
                return_when=concurrent.futures.FIRST_EXCEPTION,
            )
            dead = [f.exception() for f in done if f.exception() is not None]
            if dead:
                self.degraded = f"follower died after forward: {dead[0]}"
                return self._error_reply(
                    f"mesh follower failed between preflight and collective "
                    f"entry: {dead[0]}; group requires restart"
                )
            reply = self.server.handle_request(payload)
            for f in futures:
                try:
                    f.result(timeout=600.0)
                except Exception as e:
                    logger.exception("follower fan-out failed")
                    # the local kernel came back (possibly via timeout)
                    # but a peer never completed: collective order is no
                    # longer consistent across processes
                    self.degraded = f"follower fan-out failed: {e}"
            return reply

    def stop(self) -> None:
        self.tcp.stop()
        self._fanout.shutdown(wait=False)
