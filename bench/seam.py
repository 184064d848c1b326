"""Delivery times of every token, recorded at the scheduler seam.

``ContinuousBatchingEngine.run`` builds its scheduler through the module
name ``repro.launch.serve.SlotScheduler`` and hands it every token right
after the token reached the host: ``start(slot, request, first_token)``
for a prompt's first token, ``record(slot, token)`` for each decoded one,
``reserve(slot)`` when a staged prefill takes a slot, and
``observe_step(step, latency)`` once at the end of every engine
iteration. :func:`install` puts a recording subclass under that name.

The recorder also opens and closes the measured window. It opens when
the ``open_after``-th request gets its first token (the slots have
filled; what comes before is set-up), and at the first iteration that
ends ``seconds`` later it raises :class:`WindowClosed` out of
``engine.run``. ``on_tick(t)`` is called at the end of every iteration
(the traced run starts its profiler from there).
"""

from __future__ import annotations

import time

clock = time.perf_counter


class WindowClosed(Exception):
    """Raised out of ``engine.run`` at the end of the measured window."""


class SeamMissing(RuntimeError):
    """The program no longer exposes the scheduler seam the bench reads."""


class Log:
    """What the recorder saw, on the host's ``perf_counter`` clock."""

    def __init__(self):
        self.reserved: dict[int, float] = {}  # slot -> time of reserve
        self.first: dict[int, tuple] = {}  # rid -> (t_reserve or None, t_first)
        self.prompt_len: dict[int, int] = {}
        self.tokens: dict[int, list] = {}  # rid -> [(t, token), ...]
        self.slot_rid: dict[int, int] = {}
        self.iterations: list[tuple] = []  # (t_end, decoded tokens this iteration)
        self.t_open = None  # window start: the open_after-th first token
        self.t_end = None
        self.schedulers = 0
        self.sched = None
        self._pending_records = 0

    def token_times(self, t0: float, t1: float) -> dict[int, list]:
        """rid -> delivery times inside [t0, t1]."""
        out = {}
        for rid, toks in self.tokens.items():
            ts = [t for t, _ in toks if t0 <= t <= t1]
            if ts:
                out[rid] = ts
        return out


def require(serve_module):
    """The scheduler class behind the seam; raises if it is gone."""
    base = getattr(serve_module, "SlotScheduler", None)
    needed = ("reserve", "start", "record", "observe_step")
    if base is None or not all(callable(getattr(base, n, None)) for n in needed):
        raise SeamMissing(
            "repro.launch.serve.SlotScheduler with reserve/start/record/observe_step "
            "is gone: the bench cannot see token delivery times"
        )
    return base


def install(serve_module, log: Log, seconds: float, open_after: int, on_tick=None):
    """Swap ``serve_module.SlotScheduler`` for a recorder; returns a
    function that puts the original back."""
    base = require(serve_module)

    class Recording(base):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            log.schedulers += 1
            log.sched = self

        def reserve(self, slot):
            super().reserve(slot)
            log.reserved[slot] = clock()

        def start(self, slot, request, first_token):
            t = clock()
            log.first[request.rid] = (log.reserved.pop(slot, None), t)
            log.prompt_len[request.rid] = int(request.tokens.size)
            log.tokens[request.rid] = [(t, int(first_token))]
            log.slot_rid[slot] = request.rid
            if log.t_open is None and len(log.first) >= open_after:
                log.t_open, log.t_end = t, t + seconds
            return super().start(slot, request, first_token)

        def record(self, slot, token):
            log.tokens[log.slot_rid[slot]].append((clock(), int(token)))
            log._pending_records += 1
            return super().record(slot, token)

        def observe_step(self, step, latency_s=float("nan")):
            super().observe_step(step, latency_s)
            t = clock()
            log.iterations.append((t, log._pending_records))
            log._pending_records = 0
            if on_tick is not None:
                on_tick(t)
            if log.t_end is not None and t >= log.t_end:
                raise WindowClosed()

    serve_module.SlotScheduler = Recording

    def restore():
        serve_module.SlotScheduler = base

    return restore
