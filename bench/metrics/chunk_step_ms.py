"""Device time of one execution of the chunked-prefill step (the XLA
modules of ``chunk_step``, every chunk length), from the trace. A
request's time from slot reservation to first token is its prompt's
chunks, so it moves ``ttft_admitted_p50_ms``."""

PROGRAM = "jit_chunk_step"


def read(ctx):
    if ctx.trace is None:
        return None
    s, n = ctx.trace.programs().get(PROGRAM, (0.0, 0))
    return s / n * 1e3 if n else None
