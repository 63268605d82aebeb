"""What every engine shares: the compile clock, spans and the traced
stretch, the open-loop driver, and the outcome of a run."""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import glob
import math
import shutil
import tempfile
import time
from typing import Callable, Dict, List, Optional

from benchmarks.chip import tracereduce
from benchmarks.chip.traffic import Planned

# host spans the harness writes into a traced run
SPANS = ("step", "submit", "wait")


@contextlib.contextmanager
def compile_clock():
    """Yields a dict whose ``"s"`` and ``"n"`` sum the XLA backend-compile
    seconds and count the compiles inside the block."""
    import jax

    acc = {"s": 0.0, "n": 0}

    def listen(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            acc["s"] += secs
            acc["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield acc
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def profile_options():
    """The profiler's options for a traced run: no Python tracer (it would
    record every call of the host loop), no HLO protos."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


class Tracer:
    """Host spans, and a profiler trace of one stretch of the window.

    Off, every span is a null context.  On, ``start`` and ``stop`` bracket
    the stretch; ``collect``, called once the loop has ended, reduces the
    trace and deletes it (it is written under the temporary directory), so
    that the reduction holds up no request."""

    def __init__(self, on: bool):
        self.on = on
        self.active = False
        self.reduced: Optional[tracereduce.Reduced] = None
        self._dir: Optional[str] = None

    def span(self, name: str):
        if not self.active:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        import jax

        self._dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(self._dir,
                                 profiler_options=profile_options())
        self.active = True

    def stop(self) -> None:
        import jax

        self.active = False
        jax.profiler.stop_trace()

    def collect(self) -> None:
        if self._dir is None:
            return
        try:
            path = glob.glob(f"{self._dir}/plugins/profile/*/*.xplane.pb")[0]
            self.reduced = tracereduce.reduce(path, SPANS)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None


@dataclasses.dataclass
class Served:
    """One planned request as the open loop served it; times are seconds
    from the opening of the window, on the host clock."""

    plan: Planned
    req: object
    submitted: float = math.nan
    times: List[float] = dataclasses.field(default_factory=list)

    @property
    def in_window(self) -> bool:
        return self.plan.due >= 0.0

    @property
    def finished(self) -> bool:
        return len(self.times) >= self.plan.max_new


@dataclasses.dataclass
class Outcome:
    """What an engine hands back to ``run.py``."""

    attempted: int
    failed: int
    setup_s: float
    e2e: Dict[str, float]                     # end-to-end values by name
    compared: Dict[str, Dict[str, float]]     # name -> {"value", "limit"}
    correct: bool
    memory_peak_bytes: int
    counts: Dict[str, float] = dataclasses.field(default_factory=dict)
    reduced: Optional[tracereduce.Reduced] = None
    # control precision -> its reading of the compared number (calibration)
    controls: Dict[str, float] = dataclasses.field(default_factory=dict)


def drive_open_loop(batcher, make_request: Callable[[Planned], object],
                    plan: List[Planned], seconds: float, warmup_s: float,
                    drain_cap_s: float, tracer: Tracer,
                    trace_window=(0.0, 0.0),
                    on_step: Optional[Callable[[List[Served]], None]] = None,
                    tally: Optional[Dict[str, float]] = None,
                    clock=time.perf_counter, sleep=time.sleep):
    """Offer ``plan`` to ``batcher`` on its schedule and follow every
    request to its last token.

    The window opens ``warmup_s`` after the call; a request is submitted
    once it is due (its ``due`` is relative to the opening) and the batcher
    is stepped while any request is live.  After each step the time is
    written down for every token that appeared.  Arrivals stop with the
    plan; the loop ends when every request has finished, or
    ``drain_cap_s`` after the window closed.  ``trace_window`` is the
    stretch (seconds from the opening) that a traced run records;
    ``on_step`` sees the requests that gained a token in a traced step.
    ``tally``, where given, counts the steps that ended inside the window
    (``steps``), the requests that held a slot in them (``busy``, summed,
    and ``busy_max``: each gained one token) and those that waited for one
    (``queued``, summed).

    Returns ``(served, t_open)`` with ``t_open`` on ``clock``."""
    t_open = clock() + warmup_s
    pending = collections.deque(sorted(plan, key=lambda p: p.due))
    served: List[Served] = []
    live: List[Served] = []
    trace_from, trace_to = trace_window
    traced = False
    while True:
        now = clock() - t_open
        if tracer.on and not traced and now >= trace_from:
            tracer.start()
            traced = True
        if tracer.active and now >= trace_to:
            tracer.stop()
        if now > seconds + drain_cap_s:
            break
        while pending and pending[0].due <= now:
            p = pending.popleft()
            s = Served(p, make_request(p), submitted=now)
            with tracer.span("submit"):
                batcher.submit(s.req)
            served.append(s)
            live.append(s)
        if live:
            with tracer.span("step"):
                batcher.step()
            t = clock() - t_open
            grew = []
            for s in live:
                n = len(s.req.out)
                if n > len(s.times):
                    s.times.extend([t] * (n - len(s.times)))
                    grew.append(s)
            if tracer.active and on_step is not None:
                on_step(grew)
            if tally is not None and 0.0 <= t < seconds:
                tally["steps"] = tally.get("steps", 0) + 1
                tally["busy"] = tally.get("busy", 0) + len(grew)
                tally["busy_max"] = max(tally.get("busy_max", 0), len(grew))
                tally["queued"] = (tally.get("queued", 0)
                                   + len(live) - len(grew))
            live = [s for s in live if not s.finished]
        elif pending:
            with tracer.span("wait"):
                sleep(max(0.0, pending[0].due - (clock() - t_open)))
        else:
            break
    if tracer.active:
        tracer.stop()
    return served, t_open
