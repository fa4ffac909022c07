"""The program's span catalogue: the ``cause_tpu.obs`` spans the
benchmark reads, by layer, and the reader of the merge cells' spans.

Every ``obs.span`` goes to the profiler's trace whenever a profiler
session records, obs on or off, so a traced run's ``.xplane.pb`` holds
these spans on the device trace's clock, nested inside the harness's
own annotations. With obs on (the merge cells' traced runs) they are
also in the process's obs ring, which ``ring_ms_per_call`` reads.
"""

from __future__ import annotations

MERGE = (
    "wave.merge",          # one merge_wave call
    "wave.views",          # lane views, compatibility rebuilds, spot-check
    "wave.assemble",       # the [B, 2*cap] lane batch
    "wave.dispatch",       # upload + kernel + fetch
    "wave.upload",         # host to device (attr bytes)
    "wave.kernel",         # v5 kernel + digest, to completion
    "wave.fetch",          # device to host (attr bytes)
    "wave.dispatch.retry",  # overflow rows at a doubled budget
    "wave.scatter",        # per-pair result fill and WaveResult
)
SERVE = (
    "serve.tick",          # attrs ops, tenants, batches, wait_us_*
    "serve.apply",         # one touched tenant's coalesce + apply
    "sync.apply_delta",    # one side's merge of the delta
    "session.update",
    "serve.waves",         # attrs tenants, full
    "serve.batch_wave",    # one fused delta bucket
    "session.wave",        # one full-width re-establish
)
PROGRAM_SPANS = MERGE + SERVE


def ring_ms_per_call(name: str):
    """Milliseconds of the obs ring's ``name`` spans per ``wave.merge``
    span: the window's per-call share of that span. None when the ring
    holds neither (obs off, or a program without the span)."""
    from cause_tpu import obs

    total_us, seen, calls = 0, 0, 0
    for e in obs.events():
        if e.get("ev") != "span":
            continue
        if e.get("name") == name:
            total_us += e["dur_us"]
            seen += 1
        if e.get("name") == "wave.merge":
            calls += 1
    if not calls or not seen:
        return None
    return total_us / 1e3 / calls
