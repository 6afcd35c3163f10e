"""Device-put spine: staged host ring + async double-buffered puts.

The last host-side hop of the ingest spine (ROADMAP item 1): between
the pipeline's batch assembly and the donated ``observe_packed`` step
sits a pack (pad + hash) and a host→device transfer. Without the
spine both run on the pump thread inside the dispatch tick, so the
transfer of batch *k+1* cannot begin until batch *k*'s dispatch tick
is over. This module moves pack+put onto a dedicated **stager thread**
working through a small ring of pre-allocated host staging buffers:

- ``stage(cols, width, ...)`` (pump thread) enqueues the assembled
  columns and returns immediately; the stager packs them into ring
  slot ``seq % depth`` (``SpanTensorizer.pack_columns_into`` — zero
  allocations, stable host memory) and issues ``jax.device_put`` for
  every lane. ``device_put`` is asynchronous on real accelerators, so
  the transfer of batch *k+1* rides the wire WHILE the device executes
  batch *k*'s donated step — the overlap the e2e SLO measures.
- ``take(wait=..., idle=...)`` (pump thread) pops the oldest staged
  batch. The pump asks the device whether a step is still running
  (the newest in-flight report's ``is_ready()``), not whether a report
  is still unharvested. With a step running it takes only a batch
  whose put already completed (``overlap_hits``; the rest dispatch on a
  later pump, their transfer riding behind the step), and it waits
  only at the ring bound or under ``drain()`` (``overlap_misses``). With the device idle it waits for every staged
  batch, the one it just staged included, and dispatches them in the
  same pass (``idle_waits``): a batch waits behind a running step,
  never behind an idle device, and the low-rate regime pays the pack
  and put alone.
- **Double-buffer discipline**: a ring slot is repacked only after the
  device arrays created from its PREVIOUS use are ready
  (``jax.block_until_ready`` — i.e. the transfer consumed the host
  bytes). Depth 2 is classic double buffering: pack k+1 while k
  transfers; deeper rings absorb put-latency jitter.

The spine owns NO detector state: dispatch (and every
``detector.state`` touch) stays on the pump thread under the
pipeline's ``_dispatch_lock``, so the PR 7 donation-race pass has
nothing new to flag — the stager only ever touches its own ring and
the host column views (whose lifetime the ingest pool's scratch
tickets already manage). tests/test_spine.py hammers dispatch-vs-put
concurrency under donation to pin that.

Knobs ride ``utils.config.SPINE_KNOBS`` (ring depth / chunk rows), threaded daemon → compose → k8s like every family.
"""

from __future__ import annotations

import threading
from collections import deque

from . import selftrace
from .selftrace import SPAN_PUT, SPAN_STAGE
from .tensorize import SpanColumns, SpanTensorizer, TensorBatch


class SpineError(RuntimeError):
    """A staging job failed (pack or device put) — surfaced to the
    dispatcher that tries to take the batch, never swallowed."""


class StagedBatch:
    """One assembled batch riding the spine: host columns in, device
    arrays out once the stager's put has been issued.

    ``trace`` carries the batch's sampled self-trace (runtime.selftrace
    BatchTrace, or None) across the stage→take hand-off; the stage and
    put-wait spans append to it and observe the
    anomaly_phase_seconds{phase="stage"} and
    anomaly_spine_put_wait_seconds histograms. ``t_take`` is the pump's stamp when it took the rows off its queue
    (``selftrace.clock``): the start of the batch's ring wait."""

    __slots__ = (
        "cols", "width", "t_now", "t_oldest", "t_take", "batch", "error",
        "ready", "trace",
    )

    def __init__(
        self, cols: SpanColumns, width: int, t_now, t_oldest, t_take=None,
        trace=None,
    ):
        self.cols = cols
        self.width = width
        self.t_now = t_now
        self.t_oldest = t_oldest
        self.t_take = t_take
        self.batch: TensorBatch | None = None  # device arrays
        self.error: BaseException | None = None
        self.ready = threading.Event()
        self.trace = trace


class DevicePutSpine:
    """Staging ring + stager thread (see module doc)."""

    def __init__(
        self,
        tensorizer: SpanTensorizer,
        depth: int = 2,
        chunk_rows: int = 0,
        device_put=None,
        phase_observe=None,
    ):
        if depth < 1:
            raise ValueError(f"spine ring depth must be >= 1 (got {depth})")
        self.tensorizer = tensorizer
        self.depth = int(depth)
        self.chunk_rows = int(chunk_rows)
        self._device_put = device_put
        # The stage and put-wait spans observe their phases here (the
        # pipeline's ``phase_observe`` hook, or None).
        self.phase_observe = phase_observe
        # Ring slots: per-slot {width: host TensorBatch} (the adaptive
        # controller moves along a pow2 width ladder; each width's
        # buffers are allocated once and reused).
        self._slots: list[dict[int, TensorBatch]] = [
            {} for _ in range(self.depth)
        ]
        # Device arrays from each slot's previous use: the transfer
        # that must complete before the slot's host memory is repacked.
        self._slot_prev: list[TensorBatch | None] = [None] * self.depth
        self._seq = 0
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._jobs: deque[StagedBatch] = deque()
        self._staged: deque[StagedBatch] = deque()
        self._stop = False
        # Stats (read by the daemon's scrape via stats()).
        self.puts_total = 0
        # Takes made while a step ran: the put already complete (hit)
        # or waited on (miss). Takes with the device idle count apart.
        self.overlap_hits = 0
        self.overlap_misses = 0
        self.idle_waits = 0
        self._thread = threading.Thread(
            target=self._run, name="spine-stager", daemon=True
        )
        self._thread.start()

    # -- pump-thread API ----------------------------------------------

    def stage(
        self, cols: SpanColumns, width: int, t_now, t_oldest, t_take=None,
        trace=None,
    ) -> None:
        """Enqueue one assembled batch for pack+put (never blocks —
        the PUMP enforces the ring bound by wait-dispatching the head
        before staging past ``depth``; the pump thread is the spine's
        only consumer, so blocking here would deadlock it against
        itself). ``trace`` rides the StagedBatch to dispatch."""
        staged = StagedBatch(
            cols, int(width), t_now, t_oldest, t_take, trace=trace
        )
        with self._work:
            if self._stop:
                raise SpineError("spine is closed")
            self._jobs.append(staged)
            self._staged.append(staged)
            self._work.notify_all()

    def take(
        self, wait: bool, timeout: float = 30.0, idle: bool = False
    ) -> StagedBatch | None:
        """Oldest staged batch, device-resident — or None when nothing
        is ready and ``wait`` is False (the overlap regime: the pump
        dispatches it next tick, after the put finished behind the
        in-flight step). ``idle`` says no step is running on the
        device: the take counts as an idle wait, not as an overlap hit
        or miss."""
        with self._lock:
            staged = self._staged[0] if self._staged else None
        if staged is None:
            return None
        hit = staged.ready.is_set()
        if not hit and not wait:
            return None
        # The put-wait span: ~0 on a hit (the put finished behind the
        # in-flight step), the blocked wait on a miss or an idle wait.
        with selftrace.span(
            SPAN_PUT, observe=self.phase_observe, trace=staged.trace,
            attrs=(("overlap.hit", str(int(hit))),),
        ):
            if not hit and not staged.ready.wait(timeout):
                raise SpineError(
                    f"staged batch not ready after {timeout}s "
                    "(stager dead or device put wedged)"
                )
        with self._work:
            # Still the head (single consumer — the pump thread).
            if self._staged and self._staged[0] is staged:
                self._staged.popleft()
            if idle:
                self.idle_waits += 1
            elif hit:
                self.overlap_hits += 1
            else:
                self.overlap_misses += 1
            self._work.notify_all()
        if staged.error is not None:
            raise SpineError(
                f"staging failed: {type(staged.error).__name__}: "
                f"{staged.error}"
            ) from staged.error
        return staged

    def pending(self) -> int:
        with self._lock:
            return len(self._staged)

    def discard_pending(self) -> int:
        """Drop every undispatched staged batch (detector flag turned
        off mid-stream), returning the row count dropped — the
        pipeline counts them beside its own pending-queue drop.
        Non-blocking: unstarted jobs are cancelled outright, and a
        batch the stager is packing RIGHT NOW simply completes into an
        orphan (its put is wasted, nothing references it) — waiting on
        a wedged put here would stall the pump's disabled branch."""
        with self._work:
            dropped = list(self._staged)
            self._staged.clear()
            gone = {id(s) for s in dropped}
            self._jobs = deque(
                j for j in self._jobs if id(j) not in gone
            )
            self._work.notify_all()
        return sum(s.cols.rows for s in dropped)

    def alive(self) -> bool:
        return self._thread.is_alive() and not self._stop

    def close(self) -> None:
        with self._work:
            self._stop = True
            self._work.notify_all()
        self._thread.join(timeout=5.0)

    def stats(self) -> dict:
        with self._lock:
            puts = self.puts_total
            hits = self.overlap_hits
            misses = self.overlap_misses
            taken = hits + misses
            return {
                "ring_depth": self.depth,
                "puts_total": puts,
                "overlap_hits": hits,
                "overlap_misses": misses,
                "idle_waits": self.idle_waits,
                # Of the batches taken while a step ran, the fraction
                # whose host→device put completed entirely behind it —
                # transfer hidden by compute.
                "overlap_ratio": (hits / taken) if taken else 0.0,
            }

    # -- stager thread -------------------------------------------------

    def _host_slot(self, idx: int, width: int) -> TensorBatch:
        slot = self._slots[idx].get(width)
        if slot is None:
            slot = self._slots[idx][width] = self.tensorizer.alloc_batch(
                width
            )
        return slot

    def _put(self, host: TensorBatch) -> TensorBatch:
        if self._device_put is not None:
            return TensorBatch(*(self._device_put(a) for a in host))
        import jax

        return TensorBatch(*(jax.device_put(a) for a in host))

    def _pack_and_put(self, staged: StagedBatch) -> TensorBatch:
        idx = self._seq % self.depth
        self._seq += 1
        prev = self._slot_prev[idx]
        if prev is not None:
            # Double-buffer guard: never repack host memory a previous
            # put may still be reading. block_until_ready on PUT arrays
            # waits for the transfer only (they are inputs, not
            # computation results).
            import jax

            jax.block_until_ready(tuple(prev))
        slot = self._host_slot(idx, staged.width)
        host = self.tensorizer.pack_columns_into(
            slot, staged.cols, chunk_rows=self.chunk_rows
        )
        dev = self._put(host)
        self._slot_prev[idx] = dev
        return dev

    def _run(self) -> None:
        while True:
            with self._work:
                while not self._jobs and not self._stop:
                    self._work.wait(0.05)
                if self._stop:
                    # Fail any batch nobody will ever put: a waiting
                    # take()/discard must not hang on a dead stager.
                    for staged in self._jobs:
                        staged.error = SpineError("spine closed mid-stage")
                        staged.ready.set()
                    self._jobs.clear()
                    return
                staged = self._jobs.popleft()
            try:
                # The stage span (stager thread): pack + put issue +
                # slot wait. The batch's trace is this thread's until
                # ready.set() hands the batch to the pump.
                with selftrace.span(
                    SPAN_STAGE, observe=self.phase_observe,
                    trace=staged.trace,
                ):
                    staged.batch = self._pack_and_put(staged)
                with self._lock:
                    self.puts_total += 1
            except Exception as e:  # noqa: BLE001 — surfaced via
                # staged.error to the taking dispatcher; the stager
                # thread itself must survive (it is the only producer
                # of ready events and close() joins it).
                staged.error = e
            finally:
                staged.ready.set()
