"""Runtime performance observability: the seventh subsystem (docs/OBSERVABILITY.md).

The repo's perf story so far is *predictive*: analyzer Pass C derives
bytes/tick, live-set peak, and donation status from the lowered programs and
gates them against golden pins (analysis/cost_model.py). What it cannot see is
anything that happens at RUN time -- host stalls between chunks, dispatch
gaps, compile time bleeding into "steady state", device-memory pressure, a jit
cache quietly growing mid-soak. This package closes the loop:

- `timer.ChunkTimer` -- per-chunk runtime attribution woven into every
  standing loop (sim/chunked, sim/telemetry soak, serve/loop, scenario
  search): wall time split into dispatch / host gap / device wait, warmup vs
  steady state, chunk-boundary device-memory occupancy and jit-cache sizes,
  streamed as schema'd perf.jsonl into the telemetry sink. Opt-in (a loop
  given no timer syncs nothing for it) and host-side only: with it enabled
  no traced code changes and no new programs compile.
- `timer.span` / `timer.SPANS` -- the program's host spans, ALWAYS on and
  never syncing: each loop step (`fleet.init`/`copy`/`dispatch`/`merge`/`callback`
  in the fleet loops, `serve.dispatch`/`export`/`pack`/`rounds`/`merge`/
  `drain`/`progress` in the serve loop, a timer's `<label>.sync`, and JAX's
  `jax.trace`/`lower`/`compile`/`cache_load`) is a profiler TraceAnnotation
  (its count, where it has one, as the event's `count` argument) plus a
  (name, start, end, count) row in a bounded in-memory ring. A ChunkTimer
  beside them takes its dispatch and sync readings from those spans.
- `scopes` -- the `tick.<phase>` named scopes of the batch-minor tick
  (`TICK_PHASES`): op metadata only, so a profiler trace or the optimized
  HLO names the Raft phase of every device op of the chunk programs.
- `reconcile` -- joins what a run *measured* (bench rows, perf.jsonl) against
  what Pass C *predicted* (tests/golden_cost_model.json): achieved bytes/s,
  roofline fraction per config, live-peak headroom -- with CPU / smoke /
  non-production rows explicitly marked non-anchor, so a CPU run can never
  rebase the roofline (the same trap class PR 5 closed for smoke rows).

The one-command consumer is `python bench.py --measurement-pass`
(docs/PERF.md "chip measurement-pass checklist").
"""

from raft_sim_tpu.obs.scopes import TICK_PHASES, PhaseScopes, tick_scope
from raft_sim_tpu.obs.timer import SPANS, ChunkTimer, Span, SpanRing, device_live_bytes, span
from raft_sim_tpu.obs.reconcile import (
    load_pins,
    reconcile_matrix,
    reconcile_perf_dir,
    reconcile_row,
)

__all__ = [
    "SPANS",
    "TICK_PHASES",
    "ChunkTimer",
    "PhaseScopes",
    "Span",
    "SpanRing",
    "device_live_bytes",
    "span",
    "tick_scope",
    "load_pins",
    "reconcile_matrix",
    "reconcile_perf_dir",
    "reconcile_row",
]
