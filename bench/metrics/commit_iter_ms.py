"""Length of the iterations that finish a prefill: an iteration runs from
the end of one ``serve.pull`` span to the end of the next (the gap between
one request's consecutive tokens); the median, in ms, of those inside the
traced window in which a ``serve.commit`` span starts. The commit reads
the first token on the host before the iteration's decode step is
dispatched, which lengthens the gap: moves ``itl_p95_ms``."""

import bisect
import statistics

from bench.metrics.pull_idle_ms import PULL, spans


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    # a span clipped at the window's edge has no end (or start) inside it
    ends = [e for _, e in spans(t, PULL) if e < t.hi]
    commits = [s for s, _ in spans(t, "serve.commit") if s > t.lo]
    lengths = [b - a for a, b in zip(ends, ends[1:])
               if bisect.bisect_left(commits, a) < bisect.bisect_left(commits, b)]
    return statistics.median(lengths) * 1e-6 if lengths else None
