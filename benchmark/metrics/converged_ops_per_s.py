"""converged_ops_per_s: ops whose batch converged at a tick that ended
inside the window, over the window's length."""


def read(ctx):
    if "converged_ops" not in ctx.data:
        return None
    return ctx.data["converged_ops"] / ctx.seconds
