"""Pipeline-parallel conv-DAG serving engine — persistent per-stage
weights, microbatched requests, the executable Fig 7.  Serves any model
exposing the zoo protocol (``cfg.graph()``/``cfg.apply()`` —
DESIGN.md §12): ResNet50, MobileNetV2, fused RepVGG.

Mirrors ``serving/engine.py``'s submit/step/run surface for the CNN path:
requests carry image batches, the engine splits them into rows, and a
``distributed.conv_pipeline.ConvPipeline`` rotates microbatches through
per-device stages whose (disjoint) constant weights were placed at
construction time.

Stage planning accepts, in precedence order:

* ``plan``        — explicit ``partition.StagePlan`` list (or a
                    ``PartitionResult``, re-balanced to the device count);
* ``stage_blocks``— an explicit stage map: tuple of block-id tuples;
* ``n_stages``    — MAC-balanced contiguous split (partition.plan_stages).

Quantization domains are PER ROW (per image, DESIGN.md §9): every edge of
the compiled forward carries ``(int8, scale[row])``, so one row's logits
depend only on its own pixels — never on whoever shares its microbatch.
That is what makes **continuous cross-request batching** sound: the
engine packs rows from *different* requests into one microbatch
(``_next_microbatch``), keeping the pipe full under heavy small-request
traffic, and every request is still bit-identical to the per-row
single-device reference (``reference_logits``) for ANY packing,
``pack_requests`` setting, stage count, or arrival order.  Each injected
microbatch carries per-row segment tags ``(request, start_row, n_rows)``
so completed logits scatter back to their requests.

The host step is traced at its phase boundaries (``repro.obs.trace``'s
``host_span``, always on): ``engine.step`` around a step that has work,
inside it ``engine.pack`` (the row gather and the copy to the device),
the pipe's ``pipe.put`` / ``pipe.launch``, ``engine.fetch`` (the wait for
a microbatch's logits and their copy back) and ``engine.scatter`` (the
copy into the requests).  Each phase adds its host nanoseconds to a
wave counter of the engine's registry (``engine.step_ns``,
``engine.pack_ns``, ``pipe.put_ns``, ``pipe.launch_ns``,
``engine.fetch_ns``, ``engine.scatter_ns``); every span of one
microbatch carries its ordinal as ``mb``.

Deferred fetch: the step launches microbatch n+1 before it blocks on
microbatch n's logits.  A completed microbatch's output stays an
unfetched device array until the next step has launched its successor,
whenever more work is known to be waiting (rows in the engine's queue,
or ``door_rows`` held at the front door); otherwise the step fetches it
at once.  The device then always has the next program, and its input
copy, queued behind the one it runs, while the host waits, validates
the next request and packs.  At most one microbatch stays unfetched
between steps; ``engine.mb_overlapped`` counts the microbatches
injected while one was.
"""
from __future__ import annotations

import collections
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import partition
from repro.core.compiled_linear import ensure_compiled
from repro.distributed.conv_pipeline import ConvPipeline, PipelineStage
from repro.models.graph import compile_graph
from repro.obs.metrics import LIFE, MetricsRegistry
from repro.obs.trace import host_span


@dataclasses.dataclass
class PipelineRequest:
    rid: int
    images: np.ndarray                  # (n, H, W, 3) f32
    logits: np.ndarray | None = None
    rows_submitted: int = 0
    rows_done: int = 0
    done: bool = False


@dataclasses.dataclass
class _RowSpan:
    """A contiguous row range of one request waiting in the engine queue.

    ``cursor`` advances as rows enter microbatches; the span is spent when
    it reaches ``stop``.  Whole-request submission makes one span; the
    row-granular front door (serving/frontend.py) may enqueue several
    spans of one request — possibly on different replicas — and per-row
    quantization domains keep every split bit-identical.
    """

    req: PipelineRequest
    cursor: int
    stop: int

    @property
    def remaining(self) -> int:
        return self.stop - self.cursor


def _make_stage_fn(unit_fns, profiled: bool = False):
    if profiled:
        # profiled units return (carry, aux); the stage program merges
        # its units' aux dicts (layer names are globally unique) and the
        # pipe feeds them to telemetry.sparsity — still one jit per stage
        def stage_fn(stage_params, carry):
            aux = {}
            for fn, p in zip(unit_fns, stage_params):
                carry, a = fn(p, carry)
                aux.update(a)
            return carry, aux
    else:
        def stage_fn(stage_params, carry):
            for fn, p in zip(unit_fns, stage_params):
                carry = fn(p, carry)
            return carry
    return jax.jit(stage_fn)


def reference_logits(params, cfg, x, microbatch: int):
    """The single-device compiled path at microbatch granularity — the
    bit-identity reference for every stage count AND every packing of
    rows into microbatches: quantization domains are per-row, so the
    microbatch split here is a memory bound, not a numerics choice.

    Jitted, like the engine's stage programs: slicing the unit list into
    jitted stages is bit-exact vs the whole-model jit (no float op's
    fusion pair spans an int8 edge), whereas op-by-op eager execution
    differs by FMA-contraction ulps from ANY jitted lowering."""
    if x.shape[0] == 0:
        # zero-row input: jnp.concatenate over no microbatches would
        # raise — return the empty logits directly
        return jnp.zeros((0, cfg.num_classes), jnp.float32)
    fn = jax.jit(lambda p, mb: cfg.apply(p, mb))
    mbs = [x[i:i + microbatch] for i in range(0, x.shape[0], microbatch)]
    return jnp.concatenate([fn(params, mb) for mb in mbs])


def reference_profile(params, cfg, x, microbatch: int, groups: int,
                      lowering: str | None = None):
    """Single-device activation-sparsity oracle: run the PROFILED
    compiled units over ``x`` at microbatch granularity and return
    ``(logits, SparsityProfiler snapshot)``.

    ``lowering`` temporarily pins ``REPRO_PALLAS`` (e.g. ``"jnp"`` for
    the exact recount oracle the telemetry bench compares the serving
    path's histograms against); the jitted chain is built fresh here, so
    the pin takes effect for this call's tracing regardless of what the
    process served with before.  ``params`` must already be compiled
    (``ensure_compiled``)."""
    import os
    from repro.obs.sparsity import SparsityProfiler
    prof = SparsityProfiler(groups=groups)
    units = compile_graph(cfg.graph(), params, sparsity_groups=groups)
    unit_fns = tuple(u.fn for u in units)
    unit_ps = tuple(u.params for u in units)

    def chain(ps, mb):
        aux_all = {}
        for f, p in zip(unit_fns, ps):
            mb, aux = f(p, mb)
            aux_all.update(aux)
        return mb, aux_all

    jfn = jax.jit(chain)
    old = os.environ.get("REPRO_PALLAS")
    if lowering is not None:
        os.environ["REPRO_PALLAS"] = lowering
    try:
        outs = []
        for i in range(0, x.shape[0], microbatch):
            out, aux = jfn(unit_ps, jnp.asarray(x[i:i + microbatch],
                                                jnp.float32))
            prof.add(aux)
            outs.append(np.asarray(out))
    finally:
        if lowering is not None:
            if old is None:
                os.environ.pop("REPRO_PALLAS", None)
            else:
                os.environ["REPRO_PALLAS"] = old
    logits = (np.concatenate(outs) if outs
              else np.zeros((0, cfg.num_classes), np.float32))
    return logits, prof.snapshot()


class PipelineEngine:
    """Persistent pipeline-parallel serving of any compiled conv-DAG.

    ``cfg`` is any model config exposing the zoo protocol — ``graph()``
    (a ``models.graph.Graph``), ``apply(params, x)``, and
    ``num_classes`` — e.g. ``ResNetConfig``, ``MobileNetV2Config``, or
    ``RepVGGConfig`` (fused params).  The engine compiles the graph into
    pipeline units and plans stages from the graph's own per-unit conv
    specs and cut-edge byte counts."""

    def __init__(self, cfg, params, *,
                 mode: str = "int8", sparsity: float = 0.8,
                 n_stages: int | None = None, stage_blocks=None, plan=None,
                 microbatch: int = 2, devices=None, replica: int = 0,
                 pack_requests: bool = True, telemetry=None):
        assert mode != "dense", "the pipeline serves the compiled network"
        self.cfg = cfg
        self.microbatch = microbatch
        # continuous cross-request batching: fill microbatches across
        # request boundaries (sound under per-row quantization domains).
        # False restores whole-request microbatch packing — kept as the
        # measurable baseline for benchmarks/frontend_bench.py.
        self.pack_requests = pack_requests
        # params: the boxed training tree (compiled here, like
        # ServingEngine) or an already-compiled unboxed tree
        self.params = ensure_compiled(params, mode, sparsity)
        self.telemetry = telemetry
        # one registry per engine; the pipe shares it so engine+pipe
        # export as one snapshot() surface
        self.metrics = MetricsRegistry()
        self._mb_injected = self.metrics.counter("engine.mb_injected")
        self._rows_injected = self.metrics.counter("engine.rows_injected")
        # lifetime odometer (LIFE scope: survives reset_counters, unlike
        # the wave counters): rows delivered back to requests — the
        # front door differences it to estimate fleet service rate, and
        # the watchdog folds it into progress_marker
        self._rows_completed = self.metrics.counter(
            "engine.rows_completed", scope=LIFE)
        # host nanoseconds of each phase of the step (the spans' blocks)
        self._step_ns = self.metrics.counter("engine.step_ns")
        self._pack_ns = self.metrics.counter("engine.pack_ns")
        self._fetch_ns = self.metrics.counter("engine.fetch_ns")
        self._scatter_ns = self.metrics.counter("engine.scatter_ns")
        # microbatches injected while an earlier one's logits were still
        # unfetched: how often the deferred fetch engages
        self._mb_overlapped = self.metrics.counter("engine.mb_overlapped")
        # activation-sparsity profiling compiles DIFFERENT stage
        # programs (units return (carry, aux)); off by default
        groups = (telemetry.sparsity.groups
                  if telemetry is not None and telemetry.profiled else None)
        self.graph = cfg.graph()
        units = compile_graph(self.graph, self.params,
                              sparsity_groups=groups)
        self._profiled = groups is not None
        n_blocks = len(units) - 1              # head rides the last stage
        self.plan = self._resolve_plan(plan, stage_blocks, n_stages,
                                       n_blocks, devices)
        self.stage_block_ids = [p.block_ids for p in self.plan]
        devices = self._resolve_devices(devices, len(self.plan))
        self.pipe = ConvPipeline(
            self._build_stages(units, self.stage_block_ids, devices),
            replica=replica, metrics=self.metrics, telemetry=telemetry)
        self.queue: list[_RowSpan] = []
        # ordinals (pipe.mb_next at injection) of the microbatches in the
        # pipe, oldest first: the pipe completes them in that order
        self._mbs_in_flight: collections.deque = collections.deque()
        # completed microbatches whose logits are still on the device,
        # oldest first, as (mb, segments, device output)
        self._unfetched: collections.deque = collections.deque()
        # incremental row accounting (kept exactly in sync with the span
        # queue; _scan_pending_rows is the O(queue) oracle tests assert
        # against) — pending_rows is O(1) so the front door's routing
        # loop stays linear in admitted requests
        self._queued_rows = 0
        self._rows_in_flight = 0
        # host-dispatch-gap hint for bubble attribution: rows the FRONT
        # DOOR holds undispatched (the frontend refreshes this every
        # step; standalone engines leave it 0)
        self.door_rows = 0

    @property
    def rows_completed(self) -> int:
        return self._rows_completed.value

    # -- stage planning -------------------------------------------------
    def _resolve_plan(self, plan, stage_blocks, n_stages, n_blocks,
                      devices):
        blocks = self.graph.blocks()
        edge_bytes = self.graph.edge_bytes()
        assert len(blocks) == n_blocks, (len(blocks), n_blocks)
        if isinstance(plan, partition.PartitionResult):
            want = n_stages or (len(devices) if devices else None)
            return plan.stage_plans(blocks, want, edge_bytes)
        if plan is not None:                   # explicit StagePlan list
            return list(plan)
        if stage_blocks is not None:           # explicit stage map
            return partition.explicit_stage_plans(blocks, stage_blocks,
                                                  edge_bytes)
        return partition.plan_stages(blocks, n_stages or 1, edge_bytes)

    @staticmethod
    def _resolve_devices(devices, n_stages):
        if devices is None:
            from repro.launch.mesh import pipeline_stage_devices
            devices = pipeline_stage_devices(n_stages)
        assert len(devices) >= n_stages, (len(devices), n_stages)
        return list(devices[:n_stages])

    def _build_stages(self, units, stage_block_ids, devices):
        covered = [b for ids in stage_block_ids for b in ids]
        assert covered == list(range(len(units) - 1)), (
            "stage map must cover blocks 0..%d contiguously" % (len(units) - 2),
            stage_block_ids)
        stages = []
        for s, ids in enumerate(stage_block_ids):
            mine = [u for u in units if u.block_id in ids]
            if s == len(stage_block_ids) - 1:
                mine.append(units[-1])         # the head
            # the stage's device holds ONLY these units' constant weights
            stage_params = jax.device_put(
                tuple(u.params for u in mine), devices[s])
            stages.append(PipelineStage(
                index=s, device=devices[s],
                fn=_make_stage_fn(tuple(u.fn for u in mine),
                                  profiled=self._profiled),
                params=stage_params,
                unit_names=tuple(u.name for u in mine)))
        return stages

    # -- request management --------------------------------------------
    def submit(self, req: PipelineRequest):
        """Enqueue a whole request (resets its lifecycle)."""
        req.logits = None
        req.rows_submitted = req.rows_done = 0
        req.done = False
        self.queue.append(_RowSpan(req, 0, len(req.images)))
        self._queued_rows += len(req.images)

    def submit_rows(self, req: PipelineRequest, start: int, stop: int):
        """Enqueue one row span of a request WITHOUT touching its
        lifecycle — the front door's row-granular dispatch path: a
        request's rows may be spread over several spans (even on
        different replicas) and per-row quantization domains keep every
        split bit-identical.  The caller owns the lifecycle reset."""
        assert 0 <= start <= stop <= len(req.images), (
            start, stop, len(req.images))
        self.queue.append(_RowSpan(req, start, stop))
        self._queued_rows += stop - start

    @staticmethod
    def _complete_empty(req, num_classes):
        req.logits = np.zeros((0, num_classes), np.float32)
        req.done = True

    def _next_microbatch(self):
        """Pack up to ``microbatch`` head-of-queue rows into one
        microbatch.  With ``pack_requests`` (the default) rows from
        DIFFERENT requests share a microbatch — continuous batching,
        sound because quantization domains are per-row; otherwise a
        microbatch stops at the first span boundary (the whole-request
        baseline).  Returns (segments, rows): segments are per-row
        request tags ``(request, start_row, n_rows)`` in row order."""
        segs, parts = [], []
        need = self.microbatch
        while self.queue and need > 0:
            span = self.queue[0]
            if span.remaining == 0:            # zero-row request: complete
                if len(span.req.images) == 0:
                    self._complete_empty(span.req, self.cfg.num_classes)
                self.queue.pop(0)
                continue
            take = min(need, span.remaining)
            segs.append((span.req, span.cursor, take))
            parts.append(span.req.images[span.cursor:span.cursor + take])
            span.cursor += take
            span.req.rows_submitted += take
            self._queued_rows -= take
            need -= take
            if span.remaining == 0:
                self.queue.pop(0)
            if not self.pack_requests:
                break                          # never cross a span boundary
        if not segs:
            return None, None
        n_rows = sum(n for _, _, n in segs)
        with host_span("engine.pack", self._pack_ns, mb=self.pipe.mb_next,
                       rows=n_rows):
            rows = parts[0] if len(parts) == 1 else np.concatenate(parts)
            return segs, jnp.asarray(rows, jnp.float32)

    def step(self) -> bool:
        """Inject one microbatch (if any rows are queued) and advance the
        schedule one tick; completed rows scatter back to their segments'
        requests, the newest possibly one step later (deferred fetch).
        Returns False once idle."""
        if not self.queue and not self.pipe.busy and not self._unfetched:
            return False                # idle: no span for an idle poll
        with host_span("engine.step", self._step_ns, mb=self.pipe.mb_next):
            return self._step()

    def _step(self) -> bool:
        tag = x = None
        if self.pipe.inlet_free:
            tag, x = self._next_microbatch()
        if x is None and not self.pipe.busy and not self._unfetched:
            return False
        if x is not None:
            self._rows_in_flight += int(x.shape[0])
            self._mb_injected.inc()
            self._rows_injected.inc(int(x.shape[0]))
            if self._unfetched:
                self._mb_overlapped.inc()
            self._mbs_in_flight.append(self.pipe.mb_next)
        done = []
        if x is not None or self.pipe.busy:
            self.pipe.door_rows = self.door_rows
            done = self.pipe.tick(inject=x, tag=tag)
        for segs, out in done:
            self._unfetched.append((self._mbs_in_flight.popleft(), segs, out))
        # the newest output waits for the next step's launch only if it
        # left the pipe in this tick and more work is waiting; anything
        # older is fetched now, so no output waits more than one step
        keep = int(bool(done) and (self._queued_rows > 0
                                   or self.door_rows > 0))
        while len(self._unfetched) > keep:
            self._fetch(*self._unfetched.popleft())
        return True

    def _fetch(self, mb, segs, out):
        """Wait for one microbatch's logits, copy them back, and scatter
        them into their requests."""
        with host_span("engine.fetch", self._fetch_ns, mb=mb):
            out = np.asarray(out)
        with host_span("engine.scatter", self._scatter_ns, mb=mb,
                       rows=out.shape[0]):
            off = 0
            for req, start, n in segs:
                if req.logits is None:
                    req.logits = np.zeros(
                        (len(req.images), out.shape[-1]), out.dtype)
                req.logits[start:start + n] = out[off:off + n]
                req.rows_done += n
                req.done = req.rows_done >= len(req.images)
                off += n
        assert off == out.shape[0], (off, out.shape)
        self._rows_in_flight -= out.shape[0]
        self._rows_completed.inc(int(out.shape[0]))

    def run(self, requests: list) -> list:
        for r in requests:
            self.submit(r)
        while self.step():
            pass
        return requests

    @property
    def pending_rows(self) -> int:
        """Rows accepted but not yet delivered: the queue's unsubmitted
        rows plus the exact rows still rotating through the stages or
        waiting unfetched (partial microbatches count their real size)
        — the load metric ``serving.frontend.ResNetFrontend``'s
        least-loaded router compares across replicas.  O(1): incrementally maintained (the
        router reads it once per admitted row chunk, so a linear scan
        here made dispatch O(requests²) under load — tests assert it
        equals ``_scan_pending_rows``)."""
        return self._queued_rows + self._rows_in_flight

    def _scan_pending_rows(self) -> int:
        """The linear-scan oracle for ``pending_rows`` (tests only)."""
        return sum(sp.remaining for sp in self.queue) + self._rows_in_flight

    # -- health surface (consumed by serving/frontend.py) ----------------
    @property
    def progress_marker(self) -> tuple:
        """A snapshot that changes on EVERY healthy busy step: rows
        delivered, rows queued, rows in flight, and the stage-inlet
        occupancy pattern (a microbatch advancing one stage flips two
        cells even when the aggregate counts hold still, e.g. one
        microbatch traversing a deep pipe).  The front door's watchdog
        marks a replica failed when this freezes for ``watchdog_ticks``
        steps while ``pending_rows``/``pipe.busy`` say it has work
        (DESIGN.md §10)."""
        return (self.rows_completed, self._queued_rows,
                self._rows_in_flight, self.pipe.inlet_occupancy)

    def extract_pending(self) -> list:
        """Cancel everything this engine still owes and return it as
        ``(request, start, stop)`` row spans — the drain half of replica
        failure recovery.  Covers the un-injected queue spans, the rows
        of unfetched microbatches, and the rows buffered in stage inlets
        (via ``ConvPipeline.cancel_in_flight``); the last two have their
        ``rows_submitted`` rewound so re-execution accounting starts
        clean.  Rows that already scattered back to their requests are
        NOT extracted — they were delivered by the same program every
        replica runs, and per-row quantization domains make the
        re-executed remainder bit-identical to the never-failed
        reference (DESIGN.md §9/§10).
        Leaves the engine idle: empty queue, empty inlets, nothing
        unfetched, zeroed pending-row accounting."""
        spans = []
        owed = [segs for _, segs, _ in self._unfetched]
        for segs in owed + self.pipe.cancel_in_flight():
            for req, start, n in segs:
                req.rows_submitted -= n
                spans.append((req, start, start + n))
        self._rows_in_flight = 0
        self._mbs_in_flight.clear()
        self._unfetched.clear()
        for sp in self.queue:
            if sp.remaining:
                spans.append((sp.req, sp.cursor, sp.stop))
            elif len(sp.req.images) == 0 and not sp.req.done:
                # a queued zero-row request completes here, as
                # _next_microbatch would have
                self._complete_empty(sp.req, self.cfg.num_classes)
        self.queue.clear()
        self._queued_rows = 0
        return spans

    def run_batch(self, x) -> jnp.ndarray:
        """Convenience: one anonymous request, returns stacked logits."""
        req = PipelineRequest(rid=-1, images=np.asarray(x))
        self.run([req])
        return jnp.asarray(req.logits)

    def reset_counters(self):
        """Zero the wave-scoped schedule + occupancy counters (idle only
        — nothing unfetched, and the pipe's own busy check in
        ConvPipeline.reset_counters); the lifetime ``rows_completed``
        odometer is LIFE-scoped and survives."""
        assert not self._unfetched, "reset_counters with logits unfetched"
        self.pipe.reset_counters()
        self.metrics.reset_wave()

    def snapshot(self) -> dict:
        """The registry behind ``stats()``: every engine + pipe metric
        (the pipe shares this engine's registry) by name."""
        return self.metrics.snapshot()

    def stats(self) -> dict:
        out = self.pipe.stats()
        out["microbatch"] = self.microbatch
        out["pack_requests"] = self.pack_requests
        out["mb_injected"] = self._mb_injected.value
        out["rows_injected"] = self._rows_injected.value
        # continuous batching's gate metric: mean fraction of microbatch
        # slots actually filled (1.0 = the pipe runs full)
        out["microbatch_occupancy"] = (
            self._rows_injected.value
            / (self._mb_injected.value * self.microbatch)
            if self._mb_injected.value else None)
        out["stage_blocks"] = [list(ids) for ids in self.stage_block_ids]
        out["planned_link_bytes"] = [p.link_bytes for p in self.plan[:-1]]
        return out
