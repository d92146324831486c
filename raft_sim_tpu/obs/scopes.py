"""Named scopes of the batch-minor tick: which Raft phase each device op is.

`jax.named_scope` changes only the `op_name` metadata of the ops traced under
it (`.../while/body/tick.replicate/...` in the optimized HLO and in a
profiler trace's op details). It adds no jaxpr equation and no device work,
so the scoped chunk programs compile to the same instructions as before.

The names are defined here once; the kernel (`models/raft_batched._step_b`,
`sim/scan.tick_batch_minor`), the tests and the benchmark's per-phase
readers all import them.

| Scope | Code |
|---|---|
| `tick.faults` | input draws (`sim/faults.make_inputs`), offer overrides, the batch-minor transpose |
| `tick.deliver` | phases -1 (restart; recovery is `tick.storage`), 0 (delivery), 1 (term adoption) |
| `tick.elect` | phases 2, 3.5, 3.7, 4.5 and 7 (RequestVote, PreVote, TimeoutNow, promotion, timers) |
| `tick.replicate` | phases 3 and 4 (AppendEntries requests, snapshot install, responses) |
| `tick.commit` | phases 5 and 5.2 (quorum commit, reconfiguration), offer->commit latency |
| `tick.compact` | phase 5.5 (compaction) and the committed-prefix checksum |
| `tick.client` | phase 6 (client injection, redirect, election no-op), config origination |
| `tick.outbox` | phase 8 (outbox) |
| `tick.membership` | the config derivation from each node's log and the removed-server stepdown (end of tick), the compaction fold of config entries (phase 5.5); reconfiguration only |
| `tick.storage` | the durable storage plane: crash recovery (phase -1), the truncation clamp of the durable watermark (phase 3), the fsync flush and the section-3.8 gates (phase 7.5), the late vote responses (phase 8); `cfg.durable_storage` only |
| `tick.check` | the invariants and StepInfo (`_step_info_b`), metric accumulation |
"""

from __future__ import annotations

TICK_PHASES = (
    "faults", "deliver", "elect", "replicate", "commit", "compact", "client",
    "outbox", "membership", "storage", "check",
)  # each runs under the named scope `tick.<phase>`


def tick_scope(phase: str):
    """`jax.named_scope("tick.<phase>")` for one of TICK_PHASES."""
    import jax

    if phase not in TICK_PHASES:
        raise ValueError(f"unknown tick phase {phase!r}; have {TICK_PHASES}")
    return jax.named_scope(f"tick.{phase}")


class PhaseScopes:
    """Successive tick scopes over one straight-line body, whose phases
    interleave (elections run before, between and after replication), so
    that the body keeps its indentation:

    >>> with PhaseScopes() as phase:
    ...     phase("deliver"); ...
    ...     phase("elect"); ...   # closes tick.deliver, opens tick.elect
    ...     phase.close()          # or leave the `with`

    Leaving the `with` closes the open scope, on an exception too."""

    def __init__(self):
        self._open = None

    def __call__(self, phase: str) -> None:
        self.close()
        cm = tick_scope(phase)
        cm.__enter__()
        self._open = cm

    def close(self) -> None:
        cm, self._open = self._open, None
        if cm is not None:
            cm.__exit__(None, None, None)

    def __enter__(self) -> "PhaseScopes":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
