"""The measured window: the harness drives ``ServingEngine.submit`` and
``ServingEngine.step`` itself on the host's wall clock.

A request is submitted once its due time has passed.  Each token is
stamped with the time at which the ``step()`` that produced it returned, so
tokens produced by one dispatch reach the client together.  Time to first
token runs from the request's due time.  The window opens on the traffic's
steady state, not on an empty server (``Window._lead_in``)."""
from __future__ import annotations

import dataclasses
import time
from contextlib import nullcontext
from typing import Optional

from bench import flops


@dataclasses.dataclass
class Rec:
    """What the client of one request saw (times in seconds, perf_counter)."""

    rid: str
    due: float  # absolute due time
    prompt: list
    max_new: int
    counted: bool  # due inside the window
    req: object = None
    stamps: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    t_admit: Optional[float] = None
    status: str = "pending"  # pending | ok | shed | expired | cancelled | poisoned


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    work: dict  # bench.flops.step_work of the step's live slots


class Window:
    """Runs one window of ``seconds`` with generator ``gen`` on ``engine``.

    ``annotate(name)`` returns a context manager that marks host spans in the
    profiler's trace (``jax.profiler.TraceAnnotation`` in a traced run)."""

    def __init__(self, engine, gen, arch, c: dict, seconds: float, *, drain_s: float = 0.0,
                 annotate=None, on_step=None):
        from repro.serve.scheduler import Request

        self.Request = Request
        self.engine, self.gen, self.arch, self.c = engine, gen, arch, c
        self.seconds, self.drain_s = float(seconds), float(drain_s)
        self.annotate = annotate or (lambda name: nullcontext())
        self.on_step = on_step  # called with the step count before each step (tracing)
        self.recs: list[Rec] = []
        self.live: dict[str, Rec] = {}
        self.steps: list[Step] = []
        self.kv_dtype = engine.serve.kv_dtype

    # ------------------------------------------------------------ driving
    def _add(self, due: float, prompt, n_out: int, counted: bool) -> Rec:
        rid = f"r{len(self.recs):06d}"  # the scheduler breaks ties by rid: submission order
        rec = Rec(rid, self.t_open + due, prompt, n_out, counted)
        rec.req = self.Request(rid, list(prompt), int(n_out), arrival=self.engine.iteration)
        self.engine.submit(rec.req)
        self.recs.append(rec)
        self.live[rid] = rec
        return rec

    def _submit(self, t_rel: float) -> None:
        for due, prompt, n_out in self.gen.poll(t_rel, len(self.engine.sched.waiting)):
            self._add(due, prompt, n_out, counted=0.0 <= due < self.seconds)

    def _progress(self) -> dict:
        return {rid: (r.req.pos, len(r.req.out)) for rid, r in self.live.items()}

    def _record(self, before: dict, t1: float) -> dict:
        """Stamp new tokens; return the step's work from each request's
        progress (prompt rows consumed, tokens emitted)."""
        slots = []
        for rid, rec in list(self.live.items()):
            req = rec.req
            pos0, out0 = before.get(rid, (0, 0))
            dpos, dout = req.pos - pos0, len(req.out) - out0
            if dpos > 0:  # prompt rows; the last chunk samples the first token
                slots.append((pos0, dpos, int(out0 == 0 and dout > 0)))
            elif dout > 0:  # one decode row
                slots.append((len(req.prompt) + out0 - 1, 1, 1))
            if rec.t_admit is None and req.t_admit is not None:
                rec.t_admit = req.t_admit
            new = len(req.out) - len(rec.tokens)
            if new > 0:  # never resent: a request restarted after eviction re-emits silently
                rec.tokens.extend(int(t) for t in req.out[len(rec.tokens):])
                rec.stamps.extend([t1] * new)
            if req.state == "done":
                rec.status = req.status
                del self.live[rid]
        return flops.step_work(self.arch, self.c, slots, self.kv_dtype)

    def _pending_first(self) -> bool:
        return any(r.counted and not r.stamps and r.status == "pending" for r in self.recs)

    def _turn(self, now: float, until: float, in_window: bool) -> None:
        """Submit what is due, then run one step; with nothing to run, sleep
        toward the next due time, at most until ``until``."""
        eng = self.engine
        with self.annotate("bench.generate"):
            self._submit(now - self.t_open)
        if not eng.sched.busy() and not eng.sched.waiting:
            nxt = self.gen.next_due()
            if nxt is not None:
                time.sleep(max(min(self.t_open + nxt, until) - now, 0.0))
            return
        before = self._progress()
        if in_window and self.on_step is not None:
            self.on_step(len(self.steps))
        t0 = time.perf_counter()
        with self.annotate("engine.step"):
            eng.step()
        t1 = time.perf_counter()
        with self.annotate("bench.record"):
            work = self._record(before, t1)
        self.steps.append(Step(t0, t1, work))

    def _lead_in(self) -> None:
        """Bring the server to the traffic's steady state before the window
        opens: prefill the generator's preloaded requests (the slots of a
        long-running closed loop), and run an open loop's schedule for its
        ``lead_s``.  Requests submitted here are not counted."""
        eng = self.engine
        start = time.perf_counter()
        self.t_open = start + self.gen.lead_s
        pre = [self._add(-self.gen.lead_s, p, n, counted=False)
               for p, n in self.gen.preload(eng.serve.decode_batch, eng.serve.mixed_slab_width)]
        while (any(not r.stamps and r.status == "pending" for r in pre)
               or time.perf_counter() < self.t_open):
            self._turn(time.perf_counter(), self.t_open, in_window=False)
        if pre:
            self.t_open = time.perf_counter()
        self.lead_in_s = self.t_open - start

    def run(self) -> "Window":
        eng = self.engine
        self._lead_in()
        self.t_close = self.t_open + self.seconds
        self.t_end = self.t_open
        self.steps, self.window_steps = [], 0
        self.stats0 = self.stats_end = dict(eng.stats)
        while True:
            now = time.perf_counter()
            if now >= self.t_close:
                if self.gen.closed or not self._pending_first():
                    break
                if now >= self.t_close + self.drain_s:
                    break
            n = len(self.steps)
            self._turn(now, self.t_close + self.drain_s, in_window=True)
            if len(self.steps) > n and self.steps[-1].t0 < self.t_close:
                self.t_end = self.steps[-1].t1
                self.window_steps = len(self.steps)
                self.stats_end = dict(eng.stats)
        self.t_stop = time.perf_counter()
        return self

    # ------------------------------------------------------------ reading
    def stats(self) -> dict:
        """engine.stats accumulated over the window's steps."""
        return {k: self.stats_end[k] - self.stats0[k] for k in self.stats0
                if isinstance(self.stats0[k], (int, float))}

    def window_s(self) -> float:
        """From the window's opening to the return of the last step that
        began inside it."""
        return self.t_end - self.t_open

    def halves(self) -> tuple[float, float]:
        """Output tokens per second in the first and the second half of the
        window: alike where the window is in steady state."""
        mid, half = (self.t_open + self.t_end) / 2, (self.t_end - self.t_open) / 2
        stamps = [t for r in self.recs for t in r.stamps]
        first = sum(1 for t in stamps if self.t_open < t <= mid)
        second = sum(1 for t in stamps if mid < t <= self.t_end)
        return (first / half, second / half) if half > 0 else (0.0, 0.0)

    def turnover(self) -> tuple[int, int]:
        """Requests admitted, and requests finished, inside the window."""
        inside = lambda t: t is not None and self.t_open < t <= self.t_end  # noqa: E731
        admitted = sum(1 for r in self.recs if inside(r.t_admit))
        finished = sum(1 for r in self.recs if r.status == "ok" and r.stamps and inside(r.stamps[-1]))
        return admitted, finished
