"""The program's names for a traced window (benchmark/programtrace.py): the
HLO-metadata -> tick phase map on a recorded optimized HLO (cut from
`_chunk_donate` compiled for a described v5e), the phase sums, the idle
arithmetic against program spans, and a traced CPU run per loop kind that
reaches the new readers."""

import os
import types

import pytest

from benchmark import programtrace as pt
from benchmark.tests.test_correct import run_cell

DATA = os.path.join(os.path.dirname(__file__), "data", "chunk_hlo_v5e.txt")
PHASES = ("faults", "deliver", "elect", "replicate", "commit", "compact",
          "client", "outbox", "check")


def test_phase_of_takes_the_innermost_tick_scope():
    assert pt.phase_of("jit(f)/while/body/tick.elect/vmap()/add", PHASES) == "elect"
    assert pt.phase_of("jit(f)/tick.deliver/x/tick.commit/y", PHASES) == "commit"
    assert pt.phase_of("jit(f)/while/body/tick.bogus/add", PHASES) == "unscoped"
    assert pt.phase_of("", PHASES) == "unscoped"


def test_recorded_hlo_maps_executed_instructions_to_phases():
    text = open(DATA).read()
    m = pt.hlo_phase_map(text, PHASES)
    # Two fusions of every phase in the loop body; the while op and the
    # loop's own bookkeeping carry no tick scope.
    for p in PHASES:
        assert sum(v == p for v in m.values()) == 2, p
    assert m["%while.7"] == "unscoped"
    # Instructions inside a fused computation never run as ops of their
    # own: they are not in the map.
    assert "%slice.444" not in m and "%param_0.3486" not in m
    assert all(k.startswith("%") for k in m)


def test_phase_sums_and_top_ops():
    mapping = {"%a": "elect", "%b": "elect", "%c": "commit", "%w": "unscoped"}
    op_ns = {"%a": 30, "%b": 10, "%c": 5, "%w": 7, "%other_module_op": 1000}
    table = pt.phase_ns(op_ns, mapping, ("elect", "commit", "check"))
    assert table["elect"] == (40, [(30, "%a"), (10, "%b")])
    assert table["commit"][0] == 5 and table["check"] == (0, [])
    assert table["unscoped"][0] == 7  # the unmapped op counts nowhere


def test_phases_read_nothing_for_another_chunk_program():
    """The map is compiled from `_chunk_donate`: a window that ran the serve
    loop's program reads as nothing, before any compile."""
    assert pt.tick_phase_ns_per_cluster_tick(None, {"chunk_program": "_serve_chunk"},
                                             "elect") is None


@pytest.mark.parametrize("program_ns,ok", [(100, True), (111, True), (200, False),
                                           (80, False)])
def test_phases_must_sum_to_the_chunk_program(monkeypatch, program_ns, ok):
    """Phases summing outside 90-102% of the chunk program's own time (op
    names of another module or executable) read as nothing."""
    mapping = {"%a": "elect", "%b": "commit", "%w": "unscoped"}
    monkeypatch.setattr(pt, "chunk_phase_map", lambda *a: mapping)
    monkeypatch.setattr(pt, "tick_phases", lambda: ("elect", "commit"))
    op_items = (("%a", 60), ("%b", 30), ("%w", 10))
    per = pt._phase_table(f"sum-{program_ns}", 1, 1, op_items, 10, program_ns)
    if ok:
        assert per == {"elect": 6.0, "commit": 3.0, "unscoped": 1.0}
    else:
        assert per is None


def test_chunk_program_compiles_to_every_phase_on_cpu():
    """The reader's own compile path, at a tiny size on the CPU backend."""
    import json

    fields = {"n_nodes": 3, "log_capacity": 16, "compact_margin": 4,
              "client_interval": 2, "client_redirect": True,
              "read_interval": 2, "pre_vote": True}
    m = pt.chunk_phase_map(json.dumps(fields, sort_keys=True), 4, 4)
    assert set(PHASES) <= set(m.values())


def test_idle_by_label_splits_gaps_by_innermost_span():
    spans = [(0, 100, "fleet.dispatch"), (20, 40, "jax.compile"),
             (150, 200, "fleet.merge")]
    gaps = [(10, 30), (90, 160), (300, 310)]
    by = pt.idle_by_label(gaps, spans)
    assert by == {"fleet.dispatch": 10 + 10, "jax.compile": 10,
                  "fleet.merge": 10, None: 50 + 10}
    assert pt.idle_by_label([(5, 9)], []) == {None: 4}


def test_unattributed_share_on_the_trace_clock(monkeypatch, capsys):
    """Program spans (perf_counter s) are placed by the bench.window
    anchor; benchmark spans never count."""
    from raft_sim_tpu.obs.timer import Span

    spans = [Span("serve.drain", 10.0000002, 10.0000004, 3),
             Span("bench.slice.run", 10.0, 10.000001, None)]
    monkeypatch.setattr(pt, "program_spans",
                        lambda: [s for s in spans if not s.name.startswith("bench.")])
    trace = types.SimpleNamespace(lo=5000, hi=6000, window_s=1e-6, devices=["d0"],
                                  gaps=[(5100, 5300), (5800, 5900)])
    run = types.SimpleNamespace(
        trace=trace, spans=types.SimpleNamespace(rows=[("bench.window", 10.0, 10.000001)]))
    # serve.drain sits at [5200, 5400) on the trace clock: it covers 100 of
    # the first gap; 100 + 100 ns stay unattributed, 20% of 1,000 ns.
    assert pt.idle_unattributed_share(run, {}) == pytest.approx(20.0)
    err = capsys.readouterr().err
    assert "serve.drain: 0.000000100s" in err
    assert "under 1 us: 2 gaps, 0.000000200s" in err


def test_steady_mean_reads_spans_inside_the_steady_span(monkeypatch):
    from raft_sim_tpu.obs.timer import Span

    spans = [Span("serve.drain", 1.0, 1.5, 0), Span("serve.drain", 2.0, 2.1, 0),
             Span("serve.drain", 5.0, 9.0, 0), Span("serve.pack", 2.0, 3.0, None)]
    monkeypatch.setattr(pt, "program_spans", lambda: spans)
    assert pt.steady_mean_ms({"steady": (1.0, 4.0)}, "serve.drain") == pytest.approx(300.0)
    assert pt.steady_mean_ms({"steady": None}, "serve.drain") is None
    assert pt.steady_mean_ms({"steady": (6.0, 7.0)}, "serve.drain") is None


@pytest.fixture
def own_checkout(tmp_path, monkeypatch):
    """A copy of the benchmark files to run from: a traced run keeps its
    trace under its checkout, by cell name, so a run here never shares a
    directory with another test's traced run of the same cell."""
    import shutil

    from benchmark import cell as cell_mod

    shutil.copytree(os.path.join(cell_mod.ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(cell_mod.ROOT, "BENCHMARK.json"), tmp_path)
    monkeypatch.setattr(cell_mod, "ROOT", str(tmp_path))
    for var in ("JAX_COMPILATION_CACHE_DIR", "JAX_COMPILATION_CACHE_MAX_SIZE"):
        if var in os.environ:  # the harness sets both: put them back after
            monkeypatch.setenv(var, os.environ[var])
        else:
            monkeypatch.delenv(var, raising=False)
    return tmp_path


@pytest.mark.parametrize("workload", ["serve-etcd3-saturated", "fleet-etcd5-jepsen"])
def test_traced_rehearsal_reaches_the_new_readers(capsys, own_checkout, workload):
    """A traced CPU run per loop kind calls every per-layer reader of its
    cell, the new ones included, and none raises. The CPU trace has no
    device plane, so the device readers read nothing; the serve span
    readers read the loaded chunks' spans when there are two or more."""
    from benchmark.cell import Cell

    doc, checks = run_cell(capsys, workload, trace=1)
    assert doc["correct"], checks
    names = {m["name"] for m in Cell(str(own_checkout), workload).per_layer}
    if workload.startswith("serve"):
        new = {"serve.drain_ms", "serve.dispatch_ms",
               "device.idle_unattributed_share.serve"}
    else:
        new = {f"tick.{p}.device_ns_per_cluster_tick" for p in (*PHASES, "unscoped")}
        new.add("device.idle_unattributed_share.fleet")
    assert new <= names
    assert {k[len("cpu."):] for k in doc["metrics"]["cpu_rehearsal"]} <= names
