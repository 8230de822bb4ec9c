"""Multi-device pipeline-parallel conv execution — the executable Fig 7.

The paper's multi-chip deployment is a *layer pipeline*: each chip holds
one contiguous slice of the network as constant parameters (persistent
weights), 8-bit feature maps cross the chip boundaries, and every chip
processes a different image at once (HPIPE's layer-pipelined discipline).
This module is the TPU/CPU-device analogue:

* each ``PipelineStage`` owns a *device-resident, disjoint* subtree of the
  compiled parameters (only its own units' constant weights — the
  "persistent" property, spy-tested in tests/test_pipeline.py) and one
  jitted stage program;
* edges carry the quantization-domain pair ``(int8 activations, f32
  scale)`` — the 8-bit inter-chip link.  Per-edge payload bytes are
  *measured* from the arrays actually transferred and cross-checked
  against ``partition.StagePlan.link_bytes``;
* microbatches rotate through the stages on a GPipe-style fill/steady/
  drain schedule (``tick``): at every tick each stage that holds an input
  launches its program and hands the output to its successor's inlet
  buffer.  Stages are visited in reverse order, so stage ``s``'s launch
  for microbatch ``m`` and the transfer of microbatch ``m+1`` into its
  inlet are both in flight in the same tick — the double-buffered stage
  boundary of paper SS II-D.1.  JAX's async dispatch overlaps the
  per-device launches; nothing here blocks until the caller consumes an
  output.

Why not a ``shard_map``/``ppermute`` collective: ResNet stages have
*heterogeneous* edge shapes (56x56x256 -> 7x7x2048), and a rotating
collective needs one uniform carrier buffer padded to the largest edge —
8-bit links exist precisely to keep boundary traffic small, so we keep
the native shapes and explicit per-edge transfers (DESIGN.md §7).

Bubble accounting: a schedule of M microbatches over S stages runs
``M + S - 1`` ticks -> bubble fraction ``(S-1)/(M+S-1)`` of stage-ticks
idle, measured and reported alongside the analytic value.  Every idle
stage-tick is additionally *attributed* to exactly one cause
(DESIGN.md §11) — ``fill`` (work exists upstream but has never reached
this stage since the pipe was last empty), ``starved`` (the stage ran
before but its inlet is empty while work is still upstream: an
injection gap), ``drain`` (nothing upstream will ever arrive), or
``host`` (stage 0 idle while the front door holds undispatched rows —
the dispatch gap is on the host, not the schedule) — so the per-cause
counts sum to ``S·ticks − launches`` and hence to
``bubble_fraction · S · ticks`` by construction.

Counters live in a ``repro.obs.metrics.MetricsRegistry`` (the engine
shares its own registry down); ``ticks``/``microbatches_done`` remain
readable as properties so existing callers and tests see the same
surface.  With a ``repro.obs.Telemetry`` attached, each busy stage-tick
records a span (pid ``1 + replica``, tid = stage) covering the host-side
launch window, idle stage-ticks and edge transfers become instant
events, and profiled stage programs' sparsity aux feeds
``telemetry.sparsity``.

Always, telemetry or not: every ``device_put`` into a stage inlet runs
under a ``pipe.put`` profiler span and every stage program's launch
under ``pipe.launch`` (args ``mb``, the pipe's microbatch ordinal, and
``stage``), each adding its host nanoseconds to the ``pipe.put_ns`` /
``pipe.launch_ns`` counters.  The Chrome ``stage{s}`` span is stamped
from the same ``pipe.launch`` reading, on ``time.perf_counter``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import host_span

# every idle stage-tick gets exactly one of these (DESIGN.md §11)
BUBBLE_CAUSES = ("fill", "starved", "drain", "host")


@dataclasses.dataclass
class PipelineStage:
    """One device's slice of the network: jitted program + resident params."""

    index: int
    device: object
    fn: object                 # jitted (stage_params, carry) -> carry
    params: object             # device-resident param subtree (disjoint)
    unit_names: tuple

    def weight_bytes(self) -> int:
        return int(sum(np.prod(l.shape) * l.dtype.itemsize
                       for l in jax.tree.leaves(self.params)))


def carry_bytes(carry) -> dict:
    """Measured payload of one edge transfer: int8 feature-map bytes vs
    everything else (the f32 scale scalar)."""
    int8_b = meta_b = 0
    for leaf in jax.tree.leaves(carry):
        if leaf.dtype == jnp.int8:
            int8_b += leaf.nbytes
        else:
            meta_b += leaf.nbytes
    return {"int8_bytes": int(int8_b), "meta_bytes": int(meta_b)}


class ConvPipeline:
    """Rotating-microbatch schedule over per-device pipeline stages.

    ``tick(inject=None, tag=None)`` advances every stage by one
    microbatch slot and returns the ``(tag, output)`` pairs that left the
    last stage this tick, in the order they were injected;
    ``serving.pipeline.PipelineEngine`` drives the fill/steady/drain loop
    and consumes ``stats()``.  ``mb_next`` is the ordinal the next
    injected microbatch gets, which its spans carry as ``mb``.
    """

    def __init__(self, stages: list, replica: int = 0, metrics=None,
                 telemetry=None):
        self.stages = stages
        self.replica = replica          # which fleet replica owns this chain
        self.n_stages = len(stages)
        self._inlet = [None] * self.n_stages    # per-stage input buffer
        self._tags = [None] * self.n_stages
        self._mbs = [None] * self.n_stages      # their microbatch ordinals
        self.mb_next = 0
        self.edge_bytes: list = [None] * max(self.n_stages - 1, 0)
        self.sample_inputs: list = [None] * self.n_stages
        # schedule counters live in the registry (shared with the owning
        # engine when it passes its own); direct references keep the hot
        # path at one attribute add per event
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        m = self.metrics
        self._ticks = m.counter("pipe.ticks")
        self._mb_done = m.counter("pipe.microbatches_done")
        self._launches = [m.counter(f"pipe.stage{s}.launches")
                          for s in range(self.n_stages)]
        self._idle = {c: [m.counter(f"pipe.stage{s}.idle.{c}")
                          for s in range(self.n_stages)]
                      for c in BUBBLE_CAUSES}
        self._put_ns = m.counter("pipe.put_ns")
        self._launch_ns = m.counter("pipe.launch_ns")
        # attribution state: has stage s launched since the pipe was last
        # empty?  (distinguishes fill from starved)
        self._seen = [False] * self.n_stages
        # host-dispatch-gap hint: rows the front door holds undispatched
        # (the owning engine refreshes this every step; 0 standalone)
        self.door_rows = 0
        self.telemetry = telemetry
        self._profiled = bool(telemetry is not None and telemetry.profiled)
        tr = telemetry.trace if telemetry is not None else None
        if tr is not None:
            pid = 1 + replica
            tr.name_process(pid, f"replica {replica}")
            for s in range(self.n_stages):
                tr.name_thread(pid, s, f"stage {s}")

    @property
    def ticks(self) -> int:
        return self._ticks.value

    @property
    def microbatches_done(self) -> int:
        return self._mb_done.value

    @property
    def busy(self) -> bool:
        return any(b is not None for b in self._inlet)

    @staticmethod
    def _tag_args(tag) -> dict:
        """Span args from an engine segment tag (best-effort: direct
        ``ConvPipeline`` users may pass arbitrary tags)."""
        try:
            return {"rids": [req.rid for req, _, _ in tag],
                    "rows": sum(n for _, _, n in tag)}
        except (TypeError, ValueError, AttributeError):
            return {}

    def tick(self, inject=None, tag=None) -> list:
        """One schedule step.  ``inject`` (optional) enters stage 0's
        inlet and is computed this tick; returns completed ``(tag, out)``
        pairs (possibly empty during fill).  Raises if stage 0 is still
        busy — callers gate injection on ``inlet_free``.  M microbatches
        over S stages complete in exactly M + S - 1 ticks."""
        done = []
        self._ticks.inc()
        tel = self.telemetry
        tr = tel.trace if tel is not None else None
        pid = 1 + self.replica
        if inject is not None:
            assert self._inlet[0] is None, "stage 0 inlet busy"
            mb = self.mb_next
            self.mb_next += 1
            with host_span("pipe.put", self._put_ns, mb=mb, stage=0):
                self._inlet[0] = jax.device_put(inject,
                                                self.stages[0].device)
            self._tags[0], self._mbs[0] = tag, mb
        # bubble attribution over the post-injection occupancy: every
        # stage-tick is either a launch or gets exactly ONE idle cause,
        # so per-cause counts sum to S·ticks − launches — the measured
        # bubble_fraction's numerator — by construction (tested)
        occ = [b is not None for b in self._inlet]
        for s, busy_s in enumerate(occ):
            if busy_s:
                self._launches[s].inc()
                self._seen[s] = True
                continue
            if not any(occ[:s]):
                cause = ("host" if s == 0 and self.door_rows > 0
                         else "drain")
            else:
                cause = "starved" if self._seen[s] else "fill"
            self._idle[cause][s].inc()
            if tr is not None:
                tr.instant("idle", "pipeline", pid, s, cause=cause,
                           tick=self._ticks.value)
        # reverse stage order: stage s launches on the microbatch its
        # inlet buffered, then frees the inlet for the predecessor's
        # output issued later in this same tick — stage s's compute and
        # the transfer into its inlet are concurrently in flight (the
        # double-buffered boundary; JAX dispatch is async)
        for s in reversed(range(self.n_stages)):
            if self._inlet[s] is None:
                continue
            stage = self.stages[s]
            carry, t, mb = self._inlet[s], self._tags[s], self._mbs[s]
            if self.sample_inputs[s] is None:
                self.sample_inputs[s] = carry
            self._inlet[s] = None
            with host_span("pipe.launch", self._launch_ns, mb=mb,
                           stage=s) as launch:
                out = stage.fn(stage.params, carry)
            if self._profiled:
                out, aux = out
                tel.sparsity.add(aux, count_microbatch=(s == 0))
            if tr is not None:
                # the span covers the host-side launch window (JAX
                # dispatch is async; blocking for device time here would
                # serialize the very overlap the pipe exists for)
                tr.span(f"stage{s}", "pipeline", pid, s,
                        launch.t0_ns * 1e-9, launch.t1_ns * 1e-9,
                        tick=self._ticks.value, **self._tag_args(t))
            if s + 1 < self.n_stages:
                if self.edge_bytes[s] is None:
                    self.edge_bytes[s] = carry_bytes(out)
                with host_span("pipe.put", self._put_ns, mb=mb,
                               stage=s + 1):
                    out = jax.device_put(out, self.stages[s + 1].device)
                self._inlet[s + 1], self._tags[s + 1] = out, t
                self._mbs[s + 1] = mb
                if tr is not None:
                    tr.instant("edge", "pipeline", pid, s, edge=s,
                               **self.edge_bytes[s])
            else:
                self._mb_done.inc()
                done.append((t, out))
        if not self.busy:
            # pipe drained: the next wave's early idle stage-ticks are
            # fill again, not starvation
            self._seen = [False] * self.n_stages
        return done

    @property
    def inlet_free(self) -> bool:
        return self._inlet[0] is None

    @property
    def inlet_occupancy(self) -> tuple:
        """Which stage inlets hold a buffered microbatch — a microbatch
        advancing one stage flips two cells, so any healthy busy tick
        changes this pattern.  Part of the progress marker the serving
        front-end's per-replica watchdog hashes (DESIGN.md §10)."""
        return tuple(b is not None for b in self._inlet)

    def cancel_in_flight(self) -> list:
        """Drop every buffered microbatch and return their tags (the
        per-row segment lists the engine injected) so the caller can
        requeue the rows elsewhere — the drain half of replica failure
        recovery.  Cancelled microbatches never reach
        ``microbatches_done``; the chain is idle afterwards."""
        tags = []
        for s in range(self.n_stages):
            if self._inlet[s] is not None and self._tags[s] is not None:
                tags.append(self._tags[s])
            self._inlet[s] = None
            self._tags[s] = None
            self._mbs[s] = None
        self._seen = [False] * self.n_stages
        return tags

    def reset_counters(self):
        """Zero the schedule counters (ticks, microbatches done, launch
        and bubble-attribution tallies — the bubble-fraction basis) so
        the next wave's stats stand alone; only legal while idle, since
        mid-flight microbatches would straddle the accounting boundary."""
        assert not self.busy, "reset_counters with microbatches in flight"
        self._ticks.reset()
        self._mb_done.reset()
        for c in self._launches:
            c.reset()
        for per_stage in self._idle.values():
            for c in per_stage:
                c.reset()
        self._seen = [False] * self.n_stages

    @property
    def in_flight(self) -> int:
        """Microbatches currently buffered in stage inlets — the chain's
        occupancy (a full chain holds ``n_stages``; 0 means idle),
        surfaced in ``stats()``.  The serving front-end's least-loaded
        router uses the row-granular ``PipelineEngine.pending_rows``
        instead, which counts partial microbatches at their real size;
        ``inlet_free`` gates injection."""
        return sum(b is not None for b in self._inlet)

    def stats(self) -> dict:
        s, m = self.n_stages, self.microbatches_done
        total = s * self.ticks
        launches = [c.value for c in self._launches]
        return {
            "replica": self.replica,
            "n_stages": s,
            "in_flight": self.in_flight,
            "microbatches": m,
            "ticks": self.ticks,
            "bubble_fraction": 1.0 - (s * m) / total if total else 0.0,
            "bubble_fraction_analytic": (s - 1) / (m + s - 1) if m else 0.0,
            # which stage, which cause, for every idle stage-tick: the
            # per-cause counts sum to S·ticks − Σlaunches exactly
            "stage_launches": launches,
            "bubble_attribution": {
                cause: [c.value for c in per_stage]
                for cause, per_stage in self._idle.items()},
            "idle_stage_ticks": total - sum(launches),
            "edge_bytes": list(self.edge_bytes),
            "stage_weight_bytes": [st.weight_bytes() for st in self.stages],
            "stage_devices": [str(st.device) for st in self.stages],
        }
