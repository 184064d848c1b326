"""Device idle inside the engine's token pull, per iteration: the time in
which no operation ran on device 0 inside the host spans ``serve.pull``
(``ContinuousBatchingEngine.run`` waiting for the decode step and copying
its tokens to the host), in ms, over the ``serve.pull`` spans in the
traced window, one per decode iteration. Moves ``out_tok_s``.

The other ``serve.*`` span readers use the helpers here. A span is
matched by the part of its name before any ``#``: keyword metadata
(``#rid=3,slot=5#``) may be encoded there. On a trace without the spans
(a program that does not open them) every span reader returns None."""

from bench import tracefile

PULL = "serve.pull"


def spans(trace, *names):
    """Sorted (start, end) of the host spans called one of ``names``,
    clipped to the traced window."""
    out = []
    for e in trace.host_events:
        if e.name.split("#", 1)[0] in names:
            s, t = max(e.start, trace.lo), min(e.end, trace.hi)
            if t > s:
                out.append((s, t))
    return sorted(out)


def idle_ms_per_pull(ctx, *names):
    """Device-0 idle inside the spans ``names``, in ms per ``serve.pull``."""
    t = ctx.trace
    if t is None:
        return None
    pulls = spans(t, PULL)
    if not pulls:
        return None
    ops = [(e.start, e.end) for e in t.ops()]
    # the engine never opens two spans at once, so none is counted twice
    idle = sum(b - a for s, e in spans(t, *names) for a, b in tracefile.gaps(ops, s, e))
    return idle * 1e-6 / len(pulls)


def read(ctx):
    return idle_ms_per_pull(ctx, PULL)
