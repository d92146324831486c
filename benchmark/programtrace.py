"""The program's own names for what a traced window did: the Raft phase of
each device op, and the program's host spans over the device's idle time.

Two sources survive `harness.Run.read_trace` (which deletes the trace file
and keeps a `tracereduce.Reduction`):

- Op self-times by HLO instruction name (`Reduction.op_ns`). The tick's ops
  carry their phase in the `op_name` metadata of the optimized HLO
  (`tick.<phase>` named scopes, raft_sim_tpu/obs/scopes.py). After the
  window the chunk program the window ran is lowered and compiled again
  from the cell's config and shapes (outside set-up and the window), its
  optimized HLO text is read, and each instruction of an executed
  computation (not one inside a fusion or a reducer) is mapped to the
  innermost `tick.<phase>` of its op_name, or to `unscoped`. Only the fleet
  loop's `_chunk_donate` is compiled so: a window that ran another chunk
  program reads as nothing. Op names merge across XLA modules in `op_ns`: a
  chunk-program name that another module also uses (the eager merges' tiny
  ops) adds that module's time to a phase. So the phases must sum to 90-102%
  of the chunk program's own device time, or every phase reads as nothing.
- Idle gaps on the trace clock (`Reduction.gaps`) and the program's host
  spans (`raft_sim_tpu.obs.timer.SPANS`, perf_counter seconds), placed on
  the trace clock by the `bench.window` span's start in both clocks, the
  anchor tracereduce uses. Benchmark spans (`bench.*`) are not program
  spans.

A program without the scopes or the spans (a checkout before them) reads
as nothing: every function here returns None there, and raises nothing.
"""

from __future__ import annotations

import bisect
import functools
import json
import re
import time

from benchmark.harness import info

UNSCOPED = "unscoped"
COMPILED = "_chunk_donate"  # the chunk program chunk_phase_map compiles
SUM_RANGE = (90.0, 102.0)  # phases' sum, % of the chunk program's time
WINDOW_SPAN = "bench.window"
BENCH_PREFIX = "bench."
_NAME = re.compile(r"^\s*(?:ROOT\s+)?(%[^\s=]+)\s*=")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%[^\s(]+)\s*\(")
_CALLED = re.compile(r"(?:calls|to_apply)=(%[A-Za-z0-9_.\-]+)")


def tick_phases():
    """The program's tick phases (obs/scopes.TICK_PHASES), or None for a
    program that has no scopes."""
    try:
        from raft_sim_tpu.obs.scopes import TICK_PHASES
    except ImportError:
        return None
    return TICK_PHASES


def phase_of(op_name: str, phases) -> str:
    """The innermost `tick.<phase>` segment of an op_name path."""
    for seg in reversed(op_name.split("/")):
        if seg.startswith("tick.") and seg[5:] in phases:
            return seg[5:]
    return UNSCOPED


def hlo_phase_map(text: str, phases) -> dict[str, str]:
    """Instruction name (`%fusion.12`, as trace events name ops) -> phase,
    over the computations that run as ops of their own: those a fusion
    calls, and reducers' `to_apply` bodies, never appear in a trace."""
    called = set(_CALLED.findall(text))
    out, comp = {}, None
    for line in text.splitlines():
        if line and not line[0].isspace():
            m = _COMPUTATION.match(line)
            comp = m.group(1) if m else None
            continue
        if comp is None or comp in called:
            continue
        m = _NAME.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = phase_of(op.group(1) if op else "", phases)
    return out


@functools.cache
def chunk_phase_map(fields_json: str, batch: int, chunk: int) -> dict[str, str] | None:
    """Instruction -> phase of the fleet loop's chunk program
    (`sim/chunked._chunk_donate`) at one cell's config, batch and chunk,
    from abstract shapes; None for a program without tick scopes, or
    whose optimized HLO carries no op_name metadata."""
    phases = tick_phases()
    if phases is None:
        return None
    import jax

    from raft_sim_tpu import RaftConfig, init_batch
    from raft_sim_tpu.sim import chunked

    t0 = time.perf_counter()
    cfg = RaftConfig(**json.loads(fields_json))
    key = jax.random.key(0)
    state = jax.eval_shape(lambda k: init_batch(cfg, k, batch), key)
    keys = jax.eval_shape(lambda k: jax.random.split(k, batch), key)
    lowered = chunked._chunk_donate.lower(cfg, state, keys, chunk, None, 1)
    text = lowered.compile().as_text()
    info(f"programtrace: the chunk program's optimized HLO in "
         f"{time.perf_counter() - t0:.3f}s (after the window)")
    if not text or "op_name=" not in text:
        info("programtrace: the chunk program's HLO has no op_name metadata; "
             "no tick phases")
        return None
    return hlo_phase_map(text, phases)


def phase_ns(op_ns: dict[str, int], mapping: dict[str, str], phases) -> dict:
    """Device self-time per phase (and unscoped) of the mapped program's
    instructions, with each phase's three largest ops."""
    total = {p: 0 for p in (*phases, UNSCOPED)}
    ops = {p: [] for p in total}
    for name, ns in op_ns.items():
        p = mapping.get(name)
        if p is not None:
            total[p] += ns
            ops[p].append((ns, name))
    return {p: (total[p], sorted(ops[p], reverse=True)[:3]) for p in total}


@functools.cache
def _phase_table(fields_json, batch, chunk, op_items, cluster_ticks, program_ns):
    """The per-phase ns per cluster-tick of one traced run, printed once;
    None where the phases do not sum to SUM_RANGE of the chunk program's
    time (an op name of another module, or another executable's names)."""
    mapping = chunk_phase_map(fields_json, batch, chunk)
    if mapping is None:
        return None
    table = phase_ns(dict(op_items), mapping, tick_phases())
    per = {p: ns / cluster_ticks for p, (ns, _) in table.items()}
    whole = program_ns / cluster_ticks
    share = 100 * sum(per.values()) / whole
    info(f"tick phases (ns per cluster-tick; chunk program {whole:.3f}, phases "
         f"sum {sum(per.values()):.3f} = {share:.2f}%; "
         f"{len(mapping)} instructions mapped):")
    for p, (ns, top) in sorted(table.items(), key=lambda kv: -kv[1][0]):
        tops = ", ".join(f"{n} {v / 1e9:.6f}s" for v, n in top)
        info(f"  tick.{p}: {per[p]:.3f} ({tops})")
    if not SUM_RANGE[0] <= share <= SUM_RANGE[1]:
        info(f"programtrace: the phases sum to {share:.2f}% of the chunk "
             f"program, outside {SUM_RANGE[0]:g}-{SUM_RANGE[1]:g}%: op names "
             "do not match the program the window ran; no tick phases")
        return None
    return per


def tick_phase_ns_per_cluster_tick(run, out, phase: str) -> float | None:
    """One phase's device ns per cluster-tick of the window (a fleet cell's
    `tick.<phase>.device_ns_per_cluster_tick`); None for a window that ran
    another chunk program than the one the map is compiled from."""
    if out.get("chunk_program") != COMPILED:
        return None
    t = run.trace
    program = t.program_ns(COMPILED)
    if program <= 0 or not out["window_cluster_ticks"]:
        return None
    fields = json.dumps(run.cell.raft_fields, sort_keys=True)
    per = _phase_table(fields, run.batch, run.cell.traffic["chunk"],
                       tuple(sorted(t.op_ns.items())), out["window_cluster_ticks"],
                       program)
    return None if per is None else per[phase]


# ------------------------------------------------------- program host spans


def program_spans():
    """The program's host spans recorded so far (bench.* left out), or None
    for a program that records none."""
    try:
        from raft_sim_tpu.obs.timer import SPANS
    except ImportError:
        return None
    return [s for s in SPANS.rows if not s.name.startswith(BENCH_PREFIX)]


def to_trace_ns(run, spans):
    """(start_ns, end_ns, name) on the trace clock of perf_counter spans,
    by the window span's start in both clocks."""
    start = [a for name, a, _ in run.spans.rows if name == WINDOW_SPAN][-1]
    off = run.trace.lo - int(start * 1e9)
    return [(int(s.start * 1e9) + off, int(s.end * 1e9) + off, s.name) for s in spans]


def segments(spans):
    """Cut the spans' extent at every boundary: (points, labels), label i
    being the innermost (shortest) span open over [points[i], points[i+1]),
    or None."""
    pts = sorted({p for s, e, _ in spans for p in (s, e)})
    labels = []
    for a, b in zip(pts, pts[1:]):
        best = None
        for s, e, name in spans:
            if s <= a and b <= e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        labels.append(best[2] if best else None)
    return pts, labels


def gap_labels(gaps, spans):
    """For each gap, (its length, {innermost program span: idle ns under
    it}), None keying the idle under no program span; gaps and spans on one
    clock."""
    pts, labels = segments([s for s in spans if s[1] > s[0]])
    for a, b in gaps:
        by, covered = {}, 0
        i = max(bisect.bisect_right(pts, a) - 1, 0)
        while i < len(labels) and pts[i] < b:
            lo, hi = max(a, pts[i]), min(b, pts[i + 1])
            if hi > lo and labels[i] is not None:
                by[labels[i]] = by.get(labels[i], 0) + hi - lo
                covered += hi - lo
            i += 1
        by[None] = (b - a) - covered
        yield b - a, by


def idle_by_label(gaps, spans) -> dict:
    """Idle ns under each innermost program span (None: under none)."""
    out: dict = {}
    for _, by in gap_labels(gaps, spans):
        for k, ns in by.items():
            out[k] = out.get(k, 0) + ns
    return out


# Gap lengths that tell a device's own op-to-op gaps from host steps.
GAP_SIZES = ((1_000, "under 1 us"), (1_000_000, "1 us to 1 ms"), (None, "1 ms or more"))


def idle_unattributed_share(run, out) -> float | None:
    """Share (%) of the traced span in which the device is idle and no
    program span is open. Prints the idle seconds under each span label,
    and the unattributed idle by the length of the gap it sits in."""
    spans = program_spans()
    t = run.trace
    if spans is None or not t.devices or t.window_s <= 0:
        return None
    placed = [s for s in to_trace_ns(run, spans) if s[1] > t.lo and s[0] < t.hi]
    by: dict = {}
    sizes = {name: [0, 0] for _, name in GAP_SIZES}  # gaps, unattributed ns
    for length, labels in gap_labels(t.gaps, placed):
        for k, ns in labels.items():
            by[k] = by.get(k, 0) + ns
        if labels[None]:
            name = next(n for cut, n in GAP_SIZES if cut is None or length < cut)
            sizes[name][0] += 1
            sizes[name][1] += labels[None]
    info(f"idle by program span ({sum(by.values()) / 1e9:.9f}s idle over "
         f"{len(t.devices)} device(s), {len(placed)} program spans in the window):")
    for name, ns in sorted(by.items(), key=lambda kv: -kv[1]):
        info(f"  {name or '(no program span)'}: {ns / 1e9:.9f}s")
    info("idle under no program span, by gap length: " + "; ".join(
        f"{name}: {n} gaps, {ns / 1e9:.9f}s" for name, (n, ns) in sizes.items()))
    return 100.0 * by[None] / (t.window_s * 1e9 * len(t.devices)) if None in by else 0.0


def steady_mean_ms(out, name: str) -> float | None:
    """Mean milliseconds of the program's `name` spans that start inside the
    loop's steady span (`out["steady"]`, perf_counter seconds). The serve
    loop rebuilds that span's start from its first loaded chunk's timer
    row, whose end stamp is read after the timer's own sampling, so it
    falls a little after that chunk's dispatch span opens; the span closes
    before the last loaded chunk's drain. Each mean is over the loaded
    chunks but one."""
    spans, steady = program_spans(), out.get("steady")
    if spans is None or steady is None:
        return None
    vals = [s.end - s.start for s in spans
            if s.name == name and steady[0] <= s.start < steady[1]]
    return 1e3 * sum(vals) / len(vals) if vals else None
