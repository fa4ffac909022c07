"""setup_s: process start to window start (JAX init, the build from the
seed, warm-up), host clock."""


def read(ctx):
    return ctx.data["setup_s"]
