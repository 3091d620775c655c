"""Open-loop load from one producer thread, timed on the client's side.

The producer sends each request when it is due, into the program's
``ServingLoop`` on a ``WallClock`` at speedup 1; the calling thread pumps
the loop.  A request is timed from when it was due, so a stall of the
server or of the producer counts against every request it delays.  Its
completion is stamped on the wall clock by the benchmark's own ticket, as
the scheduler thread resolves it.  Requests due in the window are followed
until each has finished, or for ``follow_s`` past the window's end: one
that has not come by then never came.
"""
from __future__ import annotations

import threading
import time

from repro.serving import ingress


class ClientTicket(ingress.Ticket):
    """A ticket that stamps completion on the wall clock."""

    def __init__(self):
        super().__init__()
        self.done_at = None

    def resolve(self, status, request_id=None, finish_us=None, latency_us=None):
        self.done_at = time.perf_counter()
        super().resolve(status, request_id, finish_us, latency_us)


class _Stop(Exception):
    pass


class OpenLoop:
    def __init__(self, server, sched_items: list, seconds: float, limits: dict,
                 follow_s: float):
        self.server = server
        self.follow_s = follow_s
        self.items = sched_items  # (due s, workflow), window-relative
        self.seconds = seconds
        self.limits = limits
        self.loop = ingress.ServingLoop(server, clock=ingress.WallClock(speedup=1.0))
        self.sent: list = []  # (due s, workflow, sent at, ticket)
        self._halt = threading.Event()
        self.t0 = None  # wall time of the window's start
        self.stopped_at = None
        self.on_poll = None  # called from the pump with the wall time

    def _produce(self, start: float) -> None:
        for i, (due, wf) in enumerate(self.items):
            at = start + due
            while not self._halt.is_set():
                wait = at - time.perf_counter()
                if wait <= 0:
                    break
                self._halt.wait(min(wait, 0.05))
            if self._halt.is_set():
                return
            tk = ClientTicket()
            sent = time.perf_counter()
            self.sent.append((due, wf, sent, tk))
            if self.loop.queue.put(ingress.ARRIVAL, self.loop.clock.now_us(),
                                   workflow=wf, text=f"q{i}", ticket=tk) is None:
                return

    def _settled(self) -> bool:
        now = time.perf_counter()
        if self.on_poll is not None:
            self.on_poll(now)
        end = self.t0 + self.seconds
        if now < end:
            return False
        if now >= end + self.follow_s:
            return True
        if any(due < self.seconds for due, _ in self.items[len(self.sent):]):
            return False
        return all(tk.done_at is not None for due, _, _, tk in self.sent
                   if due < self.seconds)

    def _done(self) -> bool:
        if self._settled():
            raise _Stop
        return False

    def run(self, lead: float) -> None:
        """Send from ``-lead`` seconds; the window starts ``lead`` seconds
        after this call.  Returns once every window request is settled."""
        start = time.perf_counter() + lead
        self.t0 = start
        producer = threading.Thread(target=self._produce, args=(start,), daemon=True)
        producer.start()
        try:
            self.loop.pump(done=self._done, max_wall_s=float("inf"))
        except _Stop:
            pass
        finally:
            self.stopped_at = time.perf_counter()
            self._halt.set()
            self.loop.queue.close()
            producer.join(timeout=10.0)

    def window_requests(self) -> list:
        """The requests due in the window, as dicts for ``bench.stats``."""
        out = []
        for due, wf, sent, tk in self.sent:
            if not 0.0 <= due < self.seconds:
                continue
            done = tk.done_at if tk.status == "finished" else None
            out.append({"due": self.t0 + due, "sent": sent, "workflow": wf,
                        "done": done, "followed_to": self.stopped_at,
                        "limit": self.limits[wf], "status": tk.status})
        return out
