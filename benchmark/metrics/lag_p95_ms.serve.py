"""lag_p95_ms.serve: the 95th percentile of every batch offered in the
window, due time to the end of the tick that converged it; a refused or
never converged batch counts as late as the end of the post-window
drain."""

from benchmark.harness import percentile


def read(ctx):
    lags = ctx.data.get("lags_s")
    if not lags:
        return None
    return 1e3 * percentile(lags, 95)
