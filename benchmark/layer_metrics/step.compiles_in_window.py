"""XLA compilations the compile sentry counted inside the window.  The run
is ``correct: false`` with one."""


def read(ctx):
    return float(sum(ctx["compiles"].values()))
