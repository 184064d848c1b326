"""Device time of one execution of the continuous-batching decode step
(the XLA module of ``cb_step``), from the trace. Moves ``itl_p95_ms``."""

PROGRAM = "jit_cb_step"


def read(ctx):
    if ctx.trace is None:
        return None
    s, n = ctx.trace.programs().get(PROGRAM, (0.0, 0))
    return s / n * 1e3 if n else None
