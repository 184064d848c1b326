"""Device idle while the engine admits and prefills, per iteration: the
time in which no operation ran on device 0 inside the host spans
``serve.admit`` (slot clears, admission, opening a staged prefill),
``serve.chunk`` (dispatch of one prefill chunk) and ``serve.commit`` (the
first token's host read, the page commit, the scheduler's start), in ms,
over the ``serve.pull`` spans in the traced window. A request's tokens
wait on this time in the iterations that carry it, so it moves
``itl_p95_ms``."""

from bench.metrics.pull_idle_ms import idle_ms_per_pull


def read(ctx):
    return idle_ms_per_pull(ctx, "serve.admit", "serve.chunk", "serve.commit")
