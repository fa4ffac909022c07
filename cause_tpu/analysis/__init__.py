"""causelint — trace-identity and jit-purity static analysis.

The framework's correctness leans on conventions nothing used to
enforce mechanically: every ``CAUSE_TPU_*`` knob read is a registered
one, and none reaches a traced program; obs reads only its own knobs;
jit-reachable code is free of host effects; lane-cache arena views are
never mutated in place outside their owner. Each convention is the
fossil of a real fixed bug — this package turns them into CI-gated
rules. v2 extends the catalog to the concurrent host
substrate: lock discipline (LCK — guarded-by inference, lock-order
cycles, blocking under a lock, commit-step reentrancy), durable
commit protocol (DUR — fsync-before-rename, dir-fsync,
journal-before-ack, crash seams under locks) and the refusal-evidence
contract (EVD). See ``rules`` for the TID/JPH/OBS/LCA catalog and the
parameterized guard-rule table, ``concurrency``/``protocol`` for the
LCK/DUR/EVD families, ``callgraph`` for the jit-reachability
machinery, and ``__main__`` for the CLI
(``python -m cause_tpu.analysis``, with ``--cache``/``--changed``
incremental modes).

Deliberately dependency-light: stdlib ``ast`` plus
``cause_tpu.switches`` (itself import-free) — no jax, no numpy, so
the lint gate runs before the test matrix installs anything.
"""

from .core import (AnalysisResult, Finding, cached_run, changed_files,
                   list_rules, run)
from .report import load_baseline, to_json, write_baseline

__all__ = [
    "AnalysisResult",
    "Finding",
    "cached_run",
    "changed_files",
    "list_rules",
    "load_baseline",
    "run",
    "to_json",
    "write_baseline",
]
