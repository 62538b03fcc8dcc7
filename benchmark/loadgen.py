"""The load generator: drives ``BatchScheduler.submit`` from one thread and
takes every time at the ``on_token`` callback on the host's monotonic clock.

Open loop: a request is sent when it is due and timed from when it was due;
every arrival of the window is awaited (the run lasts the window and a drain);
one past the drain limit is cancelled and counts as failed. Closed loop: each
client sends its next request when its last one ended; at the window's end the
up-to-one request per client still running is cancelled and is in neither
count, and the tokens it emitted inside the window are in the token count.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

import numpy as np


@dataclass
class Sent:
    planned: object
    prompt: list[int]
    t_due: float
    t_submit: float = 0.0
    token_times: list[float] = field(default_factory=list)
    req: object = None          # the scheduler's Request
    error: str | None = None    # refused at submit (shed, unavailable, ...)
    cut: bool = False           # cancelled by the window's end (closed loop)
    late: bool = False          # past the drain limit (open loop)

    @property
    def finished(self) -> bool:
        return self.req is not None and self.req.done.is_set()

    @property
    def completed(self) -> bool:
        return (self.finished and not self.cut and not self.late and self.req.error is None
                and len(self.req.tokens) == self.planned.max_tokens)

    @property
    def failed(self) -> bool:
        return self.error is not None or self.late or (
            self.finished and not self.cut and not self.completed)


def _annotate(name: str):
    """A host span in the profiler's trace (a no-op context when no trace runs)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class LoadGenerator:
    def __init__(self, scheduler, plan, *, jitter_ms: float = 0.0, jitter_seed: int = 0):
        self.sched = scheduler
        self.plan = plan
        self.sent: list[Sent] = []
        self.lateness_s: list[float] = []
        self._wake = threading.Event()
        self._sessions: dict[int, list[int]] = {}
        self._jitter = np.random.default_rng(jitter_seed)
        self._jitter_s = jitter_ms / 1000.0
        self.t0 = 0.0
        self.t_end = 0.0

    def _submit(self, pr, t_due: float) -> Sent:
        prompt = self._sessions.get(pr.session, []) + pr.new_tokens if pr.turn else list(pr.new_tokens)
        s = Sent(planned=pr, prompt=prompt, t_due=t_due)
        times = s.token_times
        n_want = pr.max_tokens

        def on_token(_tok, _piece, times=times):
            with _annotate("bench.on_token"):
                times.append(time.monotonic())
                if len(times) >= n_want:
                    self._wake.set()

        with _annotate("bench.submit"):
            s.t_submit = time.monotonic()
            try:
                s.req = self.sched.submit(prompt, pr.max_tokens, temperature=pr.temperature,
                                          topp=pr.topp, seed=pr.seed, stop_on_eos=False,
                                          on_token=on_token)
            except Exception as e:  # noqa: BLE001 — a refused request is a failed one, not a crash
                s.error = f"{type(e).__name__}: {e}"
        self.lateness_s.append(s.t_submit - t_due)
        self.sent.append(s)
        return s

    def _sleep_until(self, t: float) -> None:
        while True:
            dt = t - time.monotonic()
            if dt <= 0:
                return
            with _annotate("bench.sleep"):
                self._wake.wait(min(dt, 0.05))
                self._wake.clear()

    def _note_session(self, s: Sent) -> None:
        if s.planned.session >= 0 and s.finished:
            self._sessions[s.planned.session] = s.prompt + list(s.req.tokens)

    def run(self, seconds: float) -> None:
        self.t0 = time.monotonic()
        self.t_end = self.t0 + seconds
        (self._run_open if self.plan.loop == "open" else self._run_closed)()

    # -- open loop ----------------------------------------------------------

    def _run_open(self) -> None:
        last_of: dict[int, Sent] = {}     # a session's latest turn: the next one waits for it
        for pr in self.plan.requests:
            t_due = self.t0 + pr.due_s + float(self._jitter.uniform(0, self._jitter_s))
            self._sleep_until(t_due)
            prev = last_of.get(pr.session) if pr.turn else None
            if prev is not None and not prev.finished:
                prev.req.done.wait(self.plan.drain_limit_s)
            if prev is not None:
                self._note_session(prev)
            s = self._submit(pr, t_due)
            if pr.session >= 0:
                last_of[pr.session] = s
        deadline = self.t_end + self.plan.drain_limit_s
        for s in self.sent:
            if s.req is None:
                continue
            if not s.req.done.wait(max(0.0, deadline - time.monotonic())):
                s.late = True
                s.req.cancel.set()
        for s in self.sent:
            if s.req is not None:
                s.req.done.wait(30.0)

    # -- closed loop --------------------------------------------------------

    def _run_closed(self) -> None:
        queues: dict[int, list] = {c: [] for c in range(self.plan.clients)}
        for pr in self.plan.requests:
            queues[pr.client].append(pr)
        current: dict[int, Sent | None] = {c: None for c in queues}
        next_at = {c: self.t0 + float(self._jitter.uniform(0, self._jitter_s)) for c in queues}
        while True:
            now = time.monotonic()
            if now >= self.t_end:
                break
            soonest = self.t_end
            for c, cur in current.items():
                if cur is not None and cur.finished:
                    self._note_session(cur)
                    current[c] = cur = None
                    next_at[c] = now + self.plan.think_s + float(self._jitter.uniform(0, self._jitter_s))
                if cur is None and queues[c]:
                    if now >= next_at[c]:
                        current[c] = self._submit(queues[c].pop(0), max(next_at[c], self.t0))
                    else:
                        soonest = min(soonest, next_at[c])
            with _annotate("bench.sleep"):
                self._wake.wait(max(0.0, min(soonest - time.monotonic(), 0.002)))
                self._wake.clear()
        for cur in current.values():
            if cur is not None and cur.req is not None and not cur.finished:
                cur.cut = True
                cur.req.cancel.set()
        for cur in current.values():
            if cur is not None and cur.req is not None:
                cur.req.done.wait(30.0)

    # -- what the run counted ------------------------------------------------

    def summary(self) -> dict:
        """Samples and counts. TTFT is from due (open) or submit (closed) to the
        first token; ITL is every gap between a request's consecutive tokens."""
        open_loop = self.plan.loop == "open"
        horizon = float("inf") if open_loop else self.t_end
        ttft, itl, tokens_in_window = [], [], 0
        for s in self.sent:
            times = [t for t in s.token_times if t <= horizon]
            tokens_in_window += sum(1 for t in s.token_times if self.t0 <= t <= self.t_end)
            if times:
                ttft.append((times[0] - (s.t_due if open_loop else s.t_submit)) * 1e3)
                itl.extend(np.diff(times) * 1e3)
        completed = [s for s in self.sent if s.completed]
        failed = [s for s in self.sent if s.failed]
        return {"ttft_ms": ttft, "itl_ms": itl, "tokens_in_window": tokens_in_window,
                "completed": completed, "failed": failed,
                "attempted": len(completed) + len(failed),
                "lateness_ms": [x * 1e3 for x in self.lateness_s],
                "window_s": self.t_end - self.t0}
