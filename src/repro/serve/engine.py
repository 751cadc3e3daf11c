"""The continuous-batching serving engine.

`ServingEngine` owns the request queue, the coalescing policy, the
health monitor, and the telemetry — the route owns the model. The loop
runs a hybrid clock: arrivals/launches/finishes advance on a VIRTUAL
event clock driven by the coalescer (`next_batch`), while each batch's
service time is the REAL measured wall time of the route's jitted run.
That split makes offered-QPS latency sweeps exact and reproducible
(queue dynamics are computed, not raced against the host scheduler)
while every latency still contains the true model cost.

An optional ``service_model`` replaces the measured wall time with a
modelled virtual service time — ``(measured_s, batch_no) -> virtual_s``.
The cluster layer uses it for two things: injecting a chaos plan's
slow-replica latency, and pinning a FIXED per-batch cost so a whole
chaos drill (routing, retries, hedges, timestamps) is bitwise
reproducible across runs.

The batch entry point is `serve_batch` — serve exactly this list of
requests now — which `drain`'s queue loop is built on and which the
cluster dispatcher calls directly (its replicas never own a queue; the
dispatcher shards one global stream). A `ReplicaFailure` raised by the
route answers nothing: the batch comes back in `DrainResult.abandoned`
with the failure attached, never silently lost — the cluster's re-queue
logic feeds on exactly that signal.

Telemetry (repro.obs bus, drained once per batch — the same
record-then-drain discipline as the trainer):

    serve_queue_wait     timing, per request (launch - arrival)
    serve_latency        timing, per request (finish - arrival)
    serve_batch_service  timing, per batch (virtual service time)
    serve_batch_size     gauge, per batch (real rows in the pad)
    serve_occupancy      gauge, per batch (real rows / max_batch)
    serve_requests       counter
    serve_abandoned      counter, requests a failed dispatch returned
    index_health         events, when the degradation ladder is armed
    ivf.live_tile_share  gauge, the route's index's live cap tiles over
                         all of them: at construction and after a
                         ladder compaction or rebuild, never per batch

Engines owned by a cluster replica carry a ``labels={"replica": i}``
tag on every record, so per-replica occupancy/queue-wait series fall
out of the one shared bus.

The ladder rides exactly as in the trainer: an `IndexHealthConfig`
arms an `IndexHealthMonitor`; every ``probe_every`` batches the route's
sampled-recall probe + overflow counter feed `observe()`, and the
monitor's verdicts execute through the route's ladder hooks
(compact -> rebuild -> pre-warmed exact fallback). Requests keep
answering through every rung — that is the whole point.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax

from repro.health.faults import ReplicaFailure
from repro.obs.trace import span
from repro.serve.coalescer import CoalescePolicy, Request, next_batch, pad_payloads

__all__ = ["DrainResult", "RequestRecord", "ServingEngine"]


@dataclasses.dataclass(frozen=True)
class RequestRecord:
    """One answered request, with its full timing decomposition."""

    rid: int
    arrival: float
    launch: float
    finish: float
    batch_size: int
    result: Any

    @property
    def queue_wait(self) -> float:
        return self.launch - self.arrival

    @property
    def latency(self) -> float:
        return self.finish - self.arrival


class DrainResult(list):
    """The records a drain/serve call answered — a plain list of
    `RequestRecord`s (so existing callers keep indexing/len'ing it) —
    plus the requests it could NOT answer, explicit instead of invisible:

    abandoned   `Request`s a failed dispatch returned unanswered (the
                in-flight batch of a dead replica, plus everything still
                queued when `drain` stopped). The cluster dispatcher
                re-queues these onto surviving replicas.
    failure     the `ReplicaFailure` that stopped serving, or None.
    """

    def __init__(self, records=(), abandoned=(), failure=None):
        super().__init__(records)
        self.abandoned: list[Request] = list(abandoned)
        self.failure = failure


class ServingEngine:
    """Queue + coalesce + execute + observe, against one route."""

    def __init__(
        self,
        route,
        policy: CoalescePolicy | None = None,
        *,
        bus=None,
        health=None,  # IndexHealthConfig | None — arms the ladder
        service_model: Callable[[float, int], float] | None = None,
        labels: dict | None = None,
    ):
        from repro.obs.bus import MetricsBus

        self.route = route
        self.policy = policy or CoalescePolicy()
        self.bus = bus if bus is not None else MetricsBus()
        self.service_model = service_model
        self.labels = dict(labels or {})
        self.monitor = None
        if health is not None:
            from repro.health.index_health import IndexHealthMonitor

            self.monitor = IndexHealthMonitor(health, self.bus)
        self.queue: list[Request] = []
        self.records: list[RequestRecord] = []
        self.free_at = 0.0
        self.batches = 0
        self._rid = 0
        self._gauge_live_tile_share()

    # -- intake ---------------------------------------------------------
    def submit(self, payload, arrival: float) -> int:
        """Enqueue one request at virtual time ``arrival`` (must be
        non-decreasing across submits — the queue is FIFO)."""
        if self.queue and arrival < self.queue[-1].arrival:
            raise ValueError(
                f"arrival {arrival} < last queued {self.queue[-1].arrival} "
                "(submit in arrival order)"
            )
        rid = self._rid
        self._rid += 1
        self.queue.append(Request(rid=rid, payload=payload, arrival=arrival))
        return rid

    def warmup(self) -> None:
        """Compile the route's traces (primary AND fallback) before
        traffic, so no request's latency pays a jit compile."""
        if hasattr(self.route, "warmup"):
            self.route.warmup(self.policy.max_batch)

    # -- the loop -------------------------------------------------------
    def drain(self) -> DrainResult:
        """Serve everything queued; returns the new records (appended
        to ``self.records`` too). Callable repeatedly — the virtual
        clock (`free_at`) persists, so submit/drain/submit/drain
        composes into one continuous timeline (the chaos bench corrupts
        the index between two drains).

        If the route fails a dispatch (`ReplicaFailure`), serving stops
        and EVERY unanswered request — the failed batch and the rest of
        the queue — is reported in ``DrainResult.abandoned`` instead of
        rotting invisibly; single-replica callers can re-submit, the
        cluster dispatcher re-queues onto survivors."""
        out = DrainResult()
        while self.queue:
            res = self._launch_one()
            out.extend(res)
            out.abandoned.extend(res.abandoned)
            if res.failure is not None:
                out.failure = res.failure
                out.abandoned.extend(self.queue)
                self.queue = []
        return out

    def _launch_one(self) -> DrainResult:
        size, launch = next_batch(
            [r.arrival for r in self.queue], self.free_at, self.policy
        )
        batch, self.queue = self.queue[:size], self.queue[size:]
        return self.serve_batch(batch, launch)

    def serve_batch(self, batch: list[Request], not_before: float = 0.0) -> DrainResult:
        """Serve exactly ``batch`` (bypassing the queue) at virtual time
        ``max(free_at, not_before, latest arrival)`` — the cluster
        dispatcher's entry point; the queue loop routes through here
        too. On `ReplicaFailure` nothing is answered: the batch comes
        back in ``.abandoned`` and the virtual clock does not advance
        (the replica never did the work)."""
        if not batch:
            return DrainResult()
        b = self.batches
        with span("serve_batch", batch=b, n=len(batch)):
            return self._serve(batch, not_before, b)

    def _serve(self, batch: list[Request], not_before: float, b: int) -> DrainResult:
        """`serve_batch`'s body, one child span a phase (``batch=b``)."""
        size = len(batch)
        launch = max(self.free_at, not_before, max(r.arrival for r in batch))
        try:
            with span("serve_prepare", batch=b):
                payloads = pad_payloads(
                    [r.payload for r in batch], self.policy.max_batch,
                    self.route.pad_payload,
                )
                prepared = self.route.prepare(payloads)
            t0 = time.perf_counter()
            with span("serve_run", batch=b):
                out = self.route.run(prepared)
            with span("serve_wait", batch=b):
                out = jax.block_until_ready(out)
            measured = time.perf_counter() - t0
        except ReplicaFailure as exc:
            self.bus.counter("serve_abandoned", size, **self.labels)
            self.bus.drain()
            return DrainResult([], abandoned=batch, failure=exc)
        service = (
            measured
            if self.service_model is None
            else float(self.service_model(measured, b))
        )
        finish = launch + service
        self.free_at = finish
        with span("serve_finalize", batch=b):
            results = self.route.finalize(out, size)
        with span("serve_record", batch=b):
            recs = []
            for req, result in zip(batch, results):
                rec = RequestRecord(
                    rid=req.rid, arrival=req.arrival, launch=launch,
                    finish=finish, batch_size=size, result=result,
                )
                recs.append(rec)
                self.records.append(rec)
                self.bus.timing(
                    "serve_queue_wait", rec.queue_wait, step=req.rid, **self.labels
                )
                self.bus.timing(
                    "serve_latency", rec.latency, step=req.rid, **self.labels
                )
            self.bus.timing("serve_batch_service", service, step=b, **self.labels)
            self.bus.gauge("serve_batch_size", float(size), step=b, **self.labels)
            self.bus.gauge(
                "serve_occupancy", size / self.policy.max_batch, step=b,
                **self.labels,
            )
            self.bus.counter("serve_requests", size, **self.labels)
            self.batches += 1
            self._maybe_probe()
            self.bus.drain()
        return DrainResult(recs)

    # -- the degradation ladder ----------------------------------------
    def _maybe_probe(self) -> None:
        """Same cadence/verdict/execute split as the trainer's
        `_maybe_probe_index`: the monitor decides, the route's hooks
        act. Probing blocks the loop (host-side recall), which is why
        it is periodic — its cost shows up honestly as engine busy
        time, not inside any request's service time."""
        monitor = self.monitor
        if monitor is None or getattr(self.route, "degraded", False):
            return
        ih = monitor.cfg
        cadence = ih.probe_every if ih.probe_every else 1
        if self.batches % cadence != 0:
            return
        recall = self.route.probe() if ih.probe_every else None
        overflow = self.route.overflow()
        action = monitor.observe(recall, overflow)
        if recall is not None or action:
            self.bus.event(
                "index_health",
                {"step": self.batches, "recall": recall,
                 "overflow": overflow, "action": action},
                step=self.batches,
            )
        if action in ("compact", "rebuild"):
            with span(f"index_{action}", batch=self.batches):
                self.route.heal(action)
            self._gauge_live_tile_share()
        elif action == "fallback":
            self.route.degrade()

    def _gauge_live_tile_share(self) -> None:
        """The route's index as built or healed (a host float the planner
        read from the lists then): retrieval routes only."""
        share = getattr(self.route, "live_tile_share", None)
        if share is not None:
            self.bus.gauge(
                "ivf.live_tile_share", share, step=self.batches, **self.labels
            )

    # -- summaries ------------------------------------------------------
    def occupancy(self) -> float:
        """Mean real rows per launched batch (> 1 means batching won)."""
        if not self.records:
            return 0.0
        return len(self.records) / self.batches

    def latencies(self) -> list[float]:
        return [r.latency for r in self.records]
