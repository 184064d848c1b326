"""Device idle in the rest of the engine loop, per iteration: the time in
which no operation ran on device 0 inside the host spans
``serve.control`` (expiry, audits, autopilot, before admission),
``serve.decode`` (dispatch of the decode step) and ``serve.record``
(handing each token to the scheduler, page release, the end of the
iteration), in ms, over the ``serve.pull`` spans in the traced window.
Moves ``out_tok_s``."""

from bench.metrics.pull_idle_ms import idle_ms_per_pull


def read(ctx):
    return idle_ms_per_pull(ctx, "serve.control", "serve.decode", "serve.record")
