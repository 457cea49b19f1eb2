"""Offers a plan's load to the system under test and keeps a record per
request on the benchmark's own clock (`time.monotonic`).

A plan is what a generator kind returns: mode "open" (requests due on a
schedule, timed from their due time) or "closed" (callers that wait for a
reply). The system under test is anything with `submit(request, on_tokens)
-> Future`, `cancel(future)` and `held()` (a context in which what is
submitted waits to be admitted as one group). One thread offers the load;
the engine's thread calls `on_tokens`, which only appends a timestamp.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field


@dataclass
class Record:
    idx: int
    due: float  # when the request was due (open) or sent (closed)
    prompt_len: int
    max_tokens: int
    client: int = -1
    sent: float | None = None
    first_t: float | None = None  # first output token, benchmark's clock
    last_t: float | None = None
    n_tokens: int = 0
    end_t: float | None = None
    finish: str | None = None
    error: str | None = None
    censored: bool = False  # cancelled by the benchmark when the window closed
    blocks: list = field(default_factory=list)  # (t, tokens) per emission

    def on_tokens(self, tokens) -> None:
        now = time.monotonic()
        if self.first_t is None:
            self.first_t = now
        self.last_t = now
        self.n_tokens += len(tokens)
        self.blocks.append((now, len(tokens)))

    def done(self, future) -> None:
        self.end_t = time.monotonic()
        try:
            self.finish = future.result().finish_reason
        except Exception as e:  # refused, shed, expired, crashed: the request failed, the run goes on
            self.error = f"{type(e).__name__}: {e}"


class Marks(threading.Thread):
    """Calls each (time, fn) once its time has come: window edges, trace
    start and stop, counter snapshots."""

    def __init__(self, events):
        super().__init__(name="acpbench-marks", daemon=True)
        self.events = sorted(events, key=lambda e: e[0])
        self.errors: list[Exception] = []

    def run(self) -> None:
        for at, fn in self.events:
            delay = at - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            try:
                fn()
            except Exception as e:  # kept for the caller, which raises it after the window
                self.errors.append(e)


def _send(system, req: dict, rec: Record):
    rec.sent = time.monotonic()
    fut = system.submit(req, rec.on_tokens)
    fut.add_done_callback(rec.done)
    return fut


def drive_open(system, plan: dict, t0: float, stop_at: float, drain_limit_s: float) -> list[Record]:
    """Send each request when it is due, whatever the system does."""
    records, futures = [], []
    for i, req in enumerate(plan["requests"]):
        due = t0 + req["due_s"]
        if due >= stop_at:
            break
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        rec = Record(idx=i, due=due, prompt_len=len(req["prompt"]), max_tokens=req["max_tokens"])
        records.append(rec)
        futures.append(_send(system, req, rec))
    deadline = max(time.monotonic(), stop_at) + drain_limit_s
    for fut, rec in zip(futures, records):
        try:
            fut.result(timeout=max(0.0, deadline - time.monotonic()))
        except Exception:  # the record holds the failure; a timeout is marked below
            pass
    for fut, rec in zip(futures, records):
        if rec.end_t is None and rec.error is None:
            rec.error = "not ended when the drain limit passed"
            system.cancel(fut)
    return records


def drive_closed(system, plan: dict, t0: float, stop_at: float, drain_limit_s: float) -> list[Record]:
    """Each client sends its next request when its last one has ended.
    Requests still running when the window closes are cancelled and marked
    censored: they are neither counted as attempted nor as failed.

    The next request is sent from the completion callback itself (the
    engine's thread; `submit` only enqueues). Handing it to another thread
    left it to the interpreter's scheduler whether the request made the
    engine's next admission or waited a whole decode block, and runs of one
    trace then fell into two modes about 1% apart in tokens per second (my
    chip run, PR 25, calls 5 and 7). For the same reason the callers' first
    requests are all queued before the engine may admit any (`held`): sent
    as a plain burst, how many of them the first admission caught was a
    race, and one run in six took another course (call 9)."""
    records: list[Record] = []
    live: dict[int, tuple] = {}
    cursor = [0] * len(plan["clients"])
    lock = threading.Lock()
    closed = threading.Event()

    def send(k: int) -> None:
        with lock:
            if closed.is_set():
                return
            seq = plan["clients"][k]
            req = seq[cursor[k] % len(seq)]
            cursor[k] += 1
            rec = Record(idx=len(records), due=time.monotonic(), client=k,
                         prompt_len=len(req["prompt"]), max_tokens=req["max_tokens"])
            records.append(rec)
            fut = _send(system, req, rec)
            live[k] = (fut, rec)
        fut.add_done_callback(lambda _f, k=k: send(k))

    with system.held():
        for k in range(len(plan["clients"])):
            send(k)
    time.sleep(max(0.0, stop_at - time.monotonic()))
    with lock:
        closed.set()
        running = list(live.values())
    for fut, rec in running:
        if not fut.done():
            rec.censored = True
            system.cancel(fut)
    deadline = time.monotonic() + drain_limit_s
    for fut, rec in running:
        try:
            fut.result(timeout=max(0.0, deadline - time.monotonic()))
        except Exception:  # cancelled, as asked
            pass
    return records


DRIVERS = {"open": drive_open, "closed": drive_closed}


def lateness_ms(records: list[Record]) -> list[float]:
    """How late the generator sent each request after it was due."""
    return [(r.sent - r.due) * 1e3 for r in records if r.sent is not None]
