"""Golden jaxpr snapshots + the compile-count regression pin.

Two structural guards over the hot-path programs, both lowering-only (no XLA
compile, so this module costs seconds, not scan-compile minutes):

1. **Op-histogram snapshot** (tests/golden_jaxpr_hist.json): primitive counts
   bucketed by output dtype for the N=5 (config3) and N=51 (config5) step
   programs (the batch-minor kernel, `step_b`). A hot-path regression -- a new [N, N, B]
   materialization, a dtype flip, a lost fusion opportunity -- shows up as a
   reviewable count diff instead of a benchmark surprise on the next chip
   session. Counts are exact for a fixed jax version (recorded in the file);
   under a different jax the exact comparison is skipped and only the
   version-independent invariants (no float primitives) are asserted.

   Regenerate after an INTENDED kernel change:
       JAX_PLATFORMS=cpu python tests/test_golden_jaxpr.py --update

2. **Compile-count pin**: the number of distinct jit lowerings the preset
   matrix induces, for the step kernel and the full scan program. Every
   distinct scan program costs ~15-40 s of tier-1 compile time on CPU
   (ROADMAP's 870 s budget); this pin makes adding one a conscious, reviewed
   bump instead of a silent budget leak. The fork-pair rule (analysis
   rule recompile-fork, run in the tools/check.py gate) guards the other
   direction: tuning-only config changes must NOT add programs.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import pytest

from raft_sim_tpu.analysis import jaxpr_audit as JA
from raft_sim_tpu.utils.config import PRESETS

GOLDEN_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden_jaxpr_hist.json")

# The snapshotted step programs: (golden key, preset).
SNAPSHOT_PROGRAMS = (
    ("config3/step_b", "config3"),
    ("config5/step_b", "config5"),
)

# Distinct lowerings across the preset matrix (8 presets, all structurally
# distinct today: different N/CAP/E shapes or different feature gates). Bump
# ONLY with a new preset or a deliberate program fork -- each distinct scan
# program is ~15-40 s of tier-1 compile budget. The pins live in
# golden_jaxpr_hist.json ("lowerings"; these constants are the
# regeneration defaults) so a bump is a reviewable snapshot diff.
# The scenario engine adds AT MOST one scan-shaped lowering per preset (the
# genome input path; step kernels are untouched, so zero extra step
# lowerings) and NEVER one per genome or segment -- genome values are traced
# data, pinned by the analyzer's scenario fork check (jaxpr_audit).
# 10 = the 8 pre-v22 presets' programs + config3p (the PreVote bench row:
# pre_vote is a structural gate, so its program is a deliberate fork) +
# config8 (the reconfiguration plane: membership/transfer/read legs live).
# 11 adds config9 (lease-based reads: the lease serve predicate, vote
# denial, and the read_fr staleness leg are structural).
# 12 adds config5c (the compacted carry layout, ops/tile.py: pack/unpack at
# the kernel boundary is a structural fork by design -- one program per
# LAYOUT, never per tuning value, which the config5c fork pair pins).
# 14 adds the giant-N tiers config7 (N=101) / config7x (N=255, compacted):
# cluster size is a shape, so each is one deliberate program fork.
PINNED_STEP_LOWERINGS = 14
PINNED_SCAN_LOWERINGS = 14
PINNED_SCENARIO_SCAN_LOWERINGS = 14
# The standing-fleet serve program (serve/loop.py simulate_serve): one program
# per structurally distinct serve-mode config. Serve variants collapse the
# scheduled cadence (client_interval -> 0), so presets differing ONLY in their
# cadence share one serve program (config2's serve variant IS config3's) --
# which is why this pin sits below the preset count. Command values are traced
# data: a multi-chunk `driver serve` session compiles nothing after warmup.
# (+ config3p / config8 serve variants: 7 -> 9; + config9's lease-read
# serve variant: 10; + config5c's compacted-layout serve variant: 11;
# + config7 / config7x giant-N serve variants: 13.)
PINNED_SERVE_SCAN_LOWERINGS = 13
# The protocol-trace program (telemetry windowed scan + event ring + coverage
# legs, raft_sim_tpu/trace): at most one per preset -- these are "the pinned
# trace variants" ISSUE 9's acceptance names: tracing adds ZERO step lowerings
# (extraction is delta-based outside the kernel) and the coverage search's
# generations all reuse one trace program (genomes are traced data; the
# analyzer's trace fork pairs pin value-invariance).
# + config3p/config8/config9 trace variants; + config5c's compacted-layout
# trace variant (12); + the config7/config7x giant-N trace variants (14).
PINNED_TRACE_SCAN_LOWERINGS = 14


def _pins():
    try:
        with open(GOLDEN_PATH) as f:
            low = json.load(f).get("lowerings", {})
    except FileNotFoundError:
        low = {}
    return (
        low.get("step", PINNED_STEP_LOWERINGS),
        low.get("scan", PINNED_SCAN_LOWERINGS),
        low.get("scenario_scan", PINNED_SCENARIO_SCAN_LOWERINGS),
        low.get("serve_scan", PINNED_SERVE_SCAN_LOWERINGS),
        low.get("trace_scan", PINNED_TRACE_SCAN_LOWERINGS),
    )


def _histograms():
    out = {}
    for key, preset in SNAPSHOT_PROGRAMS:
        cfg, _ = PRESETS[preset]
        out[key] = JA.op_histogram(JA.step_jaxpr(cfg))
    return out


def test_golden_op_histograms():
    with open(GOLDEN_PATH) as f:
        golden = json.load(f)
    actual = _histograms()
    # Version-independent invariant first: the step programs are float-free.
    for key, hist in actual.items():
        floats = [k for k in hist if "float" in k or "bfloat" in k]
        assert not floats, f"{key}: float primitives in the step program: {floats}"
    if golden["jax_version"] != jax.__version__:
        pytest.skip(
            f"golden recorded under jax {golden['jax_version']}, running "
            f"{jax.__version__}: exact op counts are version-specific"
        )
    for key, hist in actual.items():
        want = golden["programs"][key]
        if hist != want:
            diff = {
                k: (want.get(k, 0), hist.get(k, 0))
                for k in sorted(set(want) | set(hist))
                if want.get(k, 0) != hist.get(k, 0)
            }
            raise AssertionError(
                f"{key}: op histogram drifted (golden, actual): {diff}\n"
                "If the kernel change is intended, regenerate with:\n"
                "  JAX_PLATFORMS=cpu python tests/test_golden_jaxpr.py --update"
            )


def test_compile_count_pin():
    pin_step, pin_scan, pin_scenario, pin_serve, pin_trace = _pins()
    step_hashes = set()
    scan_hashes = set()
    scenario_hashes = set()
    serve_hashes = set()
    trace_hashes = set()
    for name, (cfg, _) in PRESETS.items():
        # The giant-N tiers pay ~11s of N=101/255 tracing per family; their
        # fork-detection runs in the slow sweep below (CI mesh-smoke owns it
        # via test_nodeshard's slow set every PR). The pins cover them, so
        # the tier-1 subset can only under-count, never false-pass a fork
        # among the standing presets.
        if name.startswith("config7"):
            continue
        step_hashes.add(JA.program_hash(JA.step_jaxpr(cfg)))
        scan_hashes.add(JA.program_hash(JA.scan_jaxpr(cfg)))
        scenario_hashes.add(JA.program_hash(JA.scenario_scan_jaxpr(cfg)))
        serve_hashes.add(
            JA.program_hash(JA.serve_scan_jaxpr(JA.serve_variant(cfg)))
        )
        trace_hashes.add(
            JA.program_hash(JA.trace_scan_jaxpr(JA.trace_variant(cfg)))
        )
    assert len(step_hashes) <= pin_step, (
        f"{len(step_hashes)} distinct step_b lowerings across the preset "
        f"matrix (pinned {pin_step}): a config that should share "
        "a program now forks one. Each distinct scan program costs ~15-40 s "
        "of tier-1 compile budget -- deduplicate, or bump the pin consciously."
    )
    assert len(scan_hashes) <= pin_scan, (
        f"{len(scan_hashes)} distinct scan lowerings across the preset matrix "
        f"(pinned {pin_scan}); see golden_jaxpr_hist.json 'lowerings'."
    )
    # The scenario (genome-path) scan: at most ONE lowering per preset --
    # never one per genome or per segment count in use (genomes are traced
    # data; the analyzer's scenario fork pairs pin value-invariance, this
    # pins the preset-matrix total).
    assert len(scenario_hashes) <= pin_scenario, (
        f"{len(scenario_hashes)} distinct scenario_simulate lowerings across "
        f"the preset matrix (pinned {pin_scenario}): the genome path must add "
        "at most one program per preset; a genome- or segment-dependent "
        "structure is the exact recompile-per-sweep-point failure the "
        "scenario engine exists to remove."
    )
    # The serve program: at most one lowering per structurally distinct
    # serve-mode config (command values are traced data -- a standing
    # `driver serve` session must compile NOTHING after warmup).
    assert len(serve_hashes) <= pin_serve, (
        f"{len(serve_hashes)} distinct serve_simulate lowerings across the "
        f"preset matrix (pinned {pin_serve}): a command- or chunk-content-"
        "dependent structure would recompile the standing fleet mid-session."
    )
    # The trace program: at most one per preset, and ZERO extra step
    # lowerings (the step_hashes pin above already covers that claim --
    # trace-mode configs compile the same step kernels).
    assert len(trace_hashes) <= pin_trace, (
        f"{len(trace_hashes)} distinct trace_simulate lowerings across the "
        f"preset matrix (pinned {pin_trace}): a trace-depth- or coverage-"
        "dependent structural fork would recompile the coverage hunt per "
        "sweep point (the scenario-engine failure mode, ISSUE 4/9)."
    )


def _update():
    doc = {
        "jax_version": jax.__version__,
        "lowerings": {
            "step": PINNED_STEP_LOWERINGS,
            "scan": PINNED_SCAN_LOWERINGS,
            "scenario_scan": PINNED_SCENARIO_SCAN_LOWERINGS,
            "serve_scan": PINNED_SERVE_SCAN_LOWERINGS,
            "trace_scan": PINNED_TRACE_SCAN_LOWERINGS,
        },
        "programs": _histograms(),
    }
    with open(GOLDEN_PATH, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {GOLDEN_PATH} under jax {jax.__version__}")


if __name__ == "__main__":
    if "--update" in sys.argv:
        _update()
    else:
        print(__doc__)
