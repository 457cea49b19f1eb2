"""Compute efficiency observatory: per-dispatch program telemetry.

The flight recorder (observability/flight.py) made the *scheduler's*
decisions inspectable; this module is its compute-side twin. The engine
dispatches a zoo of compiled programs — prefill buckets, chunk
continuations, decode widths, spec verify, swap restores, prefix copies —
and until this module nobody could answer "where does device time go, how
much of each dispatch is padding, and how many computed tokens were thrown
away?". Three layers, all hanging off one :class:`DispatchProfiler` owned
by the engine (``engine.profiler``):

- **per-program dispatch telemetry** — every device-dispatch site wraps its
  jit call in ``t0 = profiler.start()`` / ``profiler.record(key, t0, ...)``
  where ``key`` names the compiled program the way the jit cache keys it
  (kind × bucket/width × batch × layout, plus a ``+tbl`` marker for
  programs whose trace shape changes once the grammar token table exists).
  ``record`` accumulates host dispatch wall time, real-vs-padded token and
  slot counts, and — SAMPLED, every ``sample_every``-th dispatch per
  program, to bound overhead — a ``jax.block_until_ready`` device-inclusive
  time. Each dispatch also lands one ``acp_engine_dispatch_seconds
  {program=}`` observation (dispatch granularity, never per token: the same
  always-on-cheap posture as the flight recorder; ``ACP_PROF=0`` reduces
  every hook to one bool branch for bench A/B).

- **cold-compile observatory** — the FIRST dispatch of a program key is
  where jit traces and compiles, so its wall time is recorded as that
  program's compile cost (the first dispatch always blocks, so the number
  is the real stall, not the async enqueue). Once the engine declares
  prewarm complete (:meth:`mark_prewarmed`), any further first-dispatch is
  a compile REAL TRAFFIC paid for — a serving-time latency bug. It records
  a ``cold_compile`` flight event and increments
  ``acp_engine_cold_compiles_total``, turning the silent "prewarm: batch
  never formed" log line into an alertable signal. That wall time is
  PARTITIONED by what jax itself reports (``jax.monitoring``, one
  process-wide listener set routed by thread): ``trace_ms`` (the Python of
  the program traced to a jaxpr, a jitted function traced inside another
  counted once), ``lower_ms`` (jaxpr to MLIR), ``compile_ms`` (the backend's
  compile where the persistent cache missed or is off), ``load_ms`` (the
  cache's retrieval where it hit) and the remainder ``run_ms`` (the first
  run, and what jit does around the four). ``compiles`` counts how often
  the key went through the backend: a key that compiles on a LATER dispatch
  holds less than jit's cache keys on, and is counted in
  ``cold_compiles.retraces`` with an event that names it.

- **set-up phases** — :meth:`setup` is a context manager for the CALLER's
  thread (the constructor and ``prewarm()`` do not run on the engine
  thread): an ``acp.setup.<name>`` trace annotation plus wall seconds into
  ``stats()["setup"]["phases"]``. What jax compiles on a thread with no
  dispatch open goes to the phase open there (``jax_s``, inside the phase's
  wall seconds), with no phase open to the profiler's ``outside`` row (the
  engine thread's helpers between programs), and on a thread that never
  opened either to the process-wide ``unattributed`` count, so that the
  compiles of all tables sum to what jax's backend was asked for.

- **goodput/waste accounting** — dispatch sites classify every computed
  token position into exactly one cause via :meth:`account`: ``goodput``
  (prompt rows prefilled into live KV + sampled tokens committed), or a
  waste cause (``pad_bucket`` prefill bucket padding, ``pad_width`` decode/
  verify lane+step padding, ``spec_rejected`` rejected draft positions,
  ``preempt_discard`` discarded-and-recomputed KV, ``swap_recompute``
  host-swap-error recompute, ``dedup_rewind`` follower rewinds,
  ``prewarm`` synthetic warm-up traffic, ``pad_fuse`` the pow2 padding
  rows the fused megastep adds over the split path's exact pow2
  decomposition — the fused-program waste row). :meth:`reclassify` moves already-
  counted goodput into a waste cause when the engine later discards it
  (zero-sum, clamped), so conservation — ``computed == goodput + Σ waste``
  — holds by construction and is audited by the armed invariant checker
  (engine/invariants.py ``_verify_profiler``). Exported as
  ``acp_engine_tokens_computed_total{cause=}`` plus the
  ``acp_engine_goodput_ratio`` gauge.

- **engine-cycle phases** — :meth:`phase` is a context manager the engine
  loop opens at its own boundaries (dispatch granularity, never per slot
  or per token): ``admit`` (scheduler self time: queue drain, group
  collection, page allocation, prefix lookup, park sweep, cancels,
  expiries, chunk planning), ``park`` (waiting on an empty queue),
  ``launch`` (first host work for one program to the return of its jitted
  call), ``fetch`` (``jax.device_get`` of a dispatch's results, and this
  profiler's own sampled ``block_until_ready``), ``commit`` (token
  consumption, ``on_tokens`` callbacks, finishes, result hand-over) and
  ``publish`` (watchdog, gauges, mirrors, ledger, armed audit). Each phase
  has two outputs: a ``jax.profiler.TraceAnnotation`` named ``acp.<name>``
  carrying ``cycle=<n>`` (``launch`` also ``program=<key>`` and
  ``call_us``, the offset of the jitted call into the span), which lands
  on the host plane of a profiler trace on the device trace's clock, and
  cumulative SELF seconds + count in ``stats()["phases"]``: a phase opened
  inside another suspends the outer one, so the phases of a cycle
  partition its wall time. :meth:`cycle` wraps one loop iteration in a
  ``StepTraceAnnotation`` (``acp.cycle``, ``step_num=<n>``) once it has
  work; its self time (loop glue no phase covers) is the ``cycle`` row.
  Beside them ``stats()`` counts ``cycles``, ``blocks`` (decode-block
  dispatches) and ``uploads`` (:meth:`count_upload`: every host-to-device
  upload ``Engine._put`` makes; a dirty decode block costs two at most).

Cross-thread contract: the write side (``record``/``account``/
``reclassify``/``phase``/``cycle``/``count_upload``) runs on the engine thread (``setup`` on
its caller's, and jax's listeners on whichever thread compiled: both add
their rows under the lock); the read side (``stats`` /
``ledger`` / ``publish``) runs on REST scrape threads and takes the same
lock — enforced by the acplint thread-ownership pass (read methods are
declared ``# acp: cross-thread``; server code must go through them, never
the profiler's privates).
"""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
from typing import Any, Optional

from .metrics import REGISTRY

# every computed token position lands in goodput or exactly one of these
WASTE_CAUSES = (
    "pad_bucket",       # prefill rows padded to the compiled bucket
    "pad_width",        # decode/verify lanes+steps beyond committed tokens
    "spec_rejected",    # draft positions the verify pass rejected
    "preempt_discard",  # KV discarded at preempt/expiry and recomputed
    "swap_recompute",   # host-tier restore failed; preserved KV recomputed
    "dedup_rewind",     # follower rewound past rows its dead leader wrote
    "prewarm",          # synthetic warm-up traffic (compute, no serving)
    "pad_fuse",         # pow2 padding rows the fused megastep adds (the
                        # split path's pow2 DECOMPOSITION has none): the
                        # compute price paid for one-dispatch cycles
)

COLD_EVENTS_KEPT = 32  # recent serving-time cold compiles kept for /perf

# jax.monitoring's names (jax/_src/dispatch.py, compiler.py): the three stages
# of a jitted call's first use, each a scalar at its start and a duration at its
# end, and the persistent cache's verdict on a backend compile, fired inside it
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_STAGES = {_TRACE: "trace_s", _LOWER: "lower_s", _BACKEND: "compile_s"}
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_SAVED = "/jax/compilation_cache/compile_time_saved_sec"


class _Split:
    """What jax reported of one dispatch, set-up phase or table row: seconds
    by stage (outermost stage only: what is traced, lowered or compiled
    inside another stage is that stage's time), backend compiles, and of
    those the persistent cache's hits and misses."""

    __slots__ = (
        "trace_s", "lower_s", "compile_s", "load_s", "saved_s", "compiles", "hits", "misses",
    )

    def __init__(self) -> None:
        self.trace_s = self.lower_s = self.compile_s = self.load_s = self.saved_s = 0.0
        self.compiles = self.hits = self.misses = 0

    def add(self, other: "_Split") -> None:
        for name in _Split.__slots__:
            setattr(self, name, getattr(self, name) + getattr(other, name))

    def jax_s(self) -> float:
        return self.trace_s + self.lower_s + self.compile_s + self.load_s

    def row(self) -> dict[str, Any]:
        return {
            "trace_ms": round(self.trace_s * 1e3, 3),
            "lower_ms": round(self.lower_s * 1e3, 3),
            "compile_ms": round(self.compile_s * 1e3, 3),
            "load_ms": round(self.load_s * 1e3, 3),
            # null: the persistent cache was not asked (it is off, or
            # nothing went through the backend)
            "cache_hit": (self.misses == 0) if (self.hits or self.misses) else None,
            "compiles": self.compiles,
        }


class _Thread:
    """One thread's routing state for jax's compile events: the profiler
    whose dispatch or set-up phase this thread opened last, whether that
    dispatch is still open and what arrived inside it, the open set-up
    phases, how many stages are open (events of a nested one are the
    outermost's), and the cache's verdict on the backend compile in flight."""

    __slots__ = ("owner", "open", "split", "phases", "depth", "asked", "hit", "load_s", "saved_s")

    def __init__(self) -> None:
        self.owner: Optional["DispatchProfiler"] = None
        self.open = False
        self.split: Optional[_Split] = None
        self.phases: list = []
        self.depth = 0
        self.asked = self.hit = False
        self.load_s = self.saved_s = 0.0


_TLS = threading.local()
# compiles on threads that never opened a dispatch or a phase (a benchmark's
# weights, another library): process-wide, in no engine's table
_UNATTRIBUTED = _Split()
_MODULE_LOCK = threading.Lock()  # the count above, and the one registration
_listening = False


def _thread() -> _Thread:
    st = getattr(_TLS, "st", None)
    if st is None:
        st = _TLS.st = _Thread()
    return st


def _sink(st: _Thread):
    """Where an event on this thread is counted, and the lock to hold."""
    if st.open:
        if st.split is None:
            st.split = _Split()
        return st.split, None  # the thread's own until record() takes it
    if st.phases:
        return st.phases[-1].split, None  # likewise until the phase closes
    if st.owner is not None:
        return st.owner._outside, st.owner._lock
    return _UNATTRIBUTED, _MODULE_LOCK


def _on_scalar(event: str, value, **kw) -> None:
    if event in _STAGES:  # a stage begins (log_elapsed_time.__enter__)
        _thread().depth += 1


def _on_event(event: str, **kw) -> None:
    if event == _CACHE_ASKED:
        import jax

        # jax asks its cache whenever caching is enabled, directory or none:
        # only with a directory can the answer be a miss
        _thread().asked = jax.config.jax_compilation_cache_dir is not None
    elif event == _CACHE_HIT:
        _thread().hit = True


def _on_duration(event: str, seconds: float, **kw) -> None:
    field = _STAGES.get(event)
    if field is None:
        if event == _CACHE_LOAD:
            _thread().load_s += seconds
        elif event == _CACHE_SAVED:
            _thread().saved_s += seconds
        return
    st = _thread()
    st.depth = depth = max(0, st.depth - 1)
    split, lock = _sink(st)
    with lock or _NULL_PHASE:
        if event == _BACKEND:
            split.compiles += 1
            if st.hit:
                split.hits += 1
                split.saved_s += st.saved_s
                field, seconds = "load_s", st.load_s
            elif st.asked:
                split.misses += 1
            st.asked = st.hit = False
            st.load_s = st.saved_s = 0.0
        if depth == 0:
            setattr(split, field, getattr(split, field) + seconds)


def _listen() -> None:
    """Register the three listeners, once a process: jax keeps them for good
    and calls each on every event, so one per engine would pile up."""
    global _listening
    with _MODULE_LOCK:
        if _listening:
            return
        import jax.monitoring as monitoring

        monitoring.register_scalar_listener(_on_scalar)
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


def unattributed() -> dict[str, Any]:
    """Backend compiles (and jax's seconds around them) on threads that
    never opened a dispatch or a set-up phase of any profiler."""
    with _MODULE_LOCK:
        return {"compiles": _UNATTRIBUTED.compiles, "s": round(_UNATTRIBUTED.jax_s(), 6)}


class _Program(_Split):
    """Mutable per-program aggregate (guarded by the profiler lock); the
    split is its first dispatch's, the compiles every dispatch's."""

    __slots__ = (
        "dispatches", "host_s", "blocked_s", "blocked_samples",
        "real_tokens", "padded_tokens", "real_slots", "padded_slots",
        "first_wall_s", "cold",
    )

    def __init__(self) -> None:
        super().__init__()
        self.dispatches = 0
        self.host_s = 0.0
        self.blocked_s = 0.0      # sampled dispatch-to-ready wall time
        self.blocked_samples = 0
        self.real_tokens = 0
        self.padded_tokens = 0
        self.real_slots = 0
        self.padded_slots = 0
        self.first_wall_s = 0.0   # first dispatch = trace + compile wall
        self.cold = False         # first dispatch landed AFTER prewarm


_NULL_PHASE = contextlib.nullcontext()  # what phase() returns while disabled


class _Phase:
    """One open phase of the engine loop: a trace annotation plus self-time
    accounting on the profiler's per-thread stack."""

    __slots__ = ("_prof", "name", "_ann", "_self_s", "_resumed", "_opened")

    def __init__(self, prof: "DispatchProfiler", name: str, ann):
        self._prof = prof
        self.name = name
        self._ann = ann
        self._self_s = 0.0
        self._resumed = 0.0
        self._opened = 0.0

    def __enter__(self):
        self._ann.__enter__()
        prof = self._prof
        stack = prof._stack()
        now = prof._stamp = time.monotonic()
        if stack:
            outer = stack[-1]
            outer._self_s += now - outer._resumed  # the outer phase is suspended
        self._resumed = self._opened = now
        stack.append(self)
        return self

    def __exit__(self, *exc) -> None:
        prof = self._prof
        stack = prof._stack()
        now = prof._stamp = time.monotonic()
        self._self_s += now - self._resumed
        stack.pop()
        if stack:
            stack[-1]._resumed = now
        with prof._lock:
            row = prof._phases.setdefault(self.name, [0.0, 0])
            row[0] += self._self_s
            row[1] += 1
        self._ann.__exit__(*exc)

    def program(self, key: str, called: float) -> None:
        """Name the compiled program a ``launch`` span dispatched (the key
        is built after the jitted call returns, as ``record`` has it), and
        say how many microseconds into the span its jitted call began
        (``start()``'s stamp): the device cannot have started the program
        before, which is what ties the device trace's clock to this one."""
        self._ann.set_metadata(
            program=key, call_us=int((called - self._opened) * 1e6)
        )


class _Setup:
    """One open set-up phase on its caller's thread: a trace annotation,
    wall seconds, what jax compiled on this thread while it was the
    innermost phase open there, and the first-dispatch wall time of the
    programs first dispatched (on any thread) while it was open."""

    __slots__ = ("_prof", "name", "_ann", "_t0", "split", "first_wall_s")

    def __init__(self, prof: "DispatchProfiler", name: str, ann):
        self._prof = prof
        self.name = name
        self._ann = ann
        self._t0 = 0.0
        self.split = _Split()
        self.first_wall_s = 0.0  # under the profiler's lock: record() adds to it

    def __enter__(self):
        self._ann.__enter__()
        prof = self._prof
        st = _thread()
        st.owner = prof
        st.phases.append(self)
        with prof._lock:
            prof._setup_open.append(self)
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        wall = time.monotonic() - self._t0
        prof = self._prof
        _thread().phases.remove(self)
        with prof._lock:
            prof._setup_open.remove(self)
            row = prof._setup.get(self.name)
            if row is None:
                row = prof._setup[self.name] = [0.0, 0, 0.0, _Split()]
            row[0] += wall
            row[1] += 1
            row[2] += self.first_wall_s
            row[3].add(self.split)
        self._ann.__exit__(*exc)


class DispatchProfiler:
    """Per-dispatch program telemetry + cold-compile tracking + goodput
    ledger. One per :class:`~agentcontrolplane_tpu.engine.engine.Engine`
    (``engine.profiler``); ``flight`` (optional) receives ``cold_compile``
    events so serving-time compiles appear inline with the scheduler
    decisions that caused them."""

    # A/B caveat: `enabled` is a plain mutable attribute (tests toggle it
    # on a live engine). A program whose FIRST dispatch lands inside a
    # disabled window is never registered, so it would read as a cold
    # compile when re-enabled after mark_prewarmed() — toggle only on
    # warmed engines whose program zoo is already registered, or
    # re-baseline with a fresh profiler.

    def __init__(
        self,
        flight=None,
        enabled: Optional[bool] = None,
        sample_every: Optional[int] = None,
    ):
        if enabled is None:
            enabled = os.environ.get("ACP_PROF", "1") not in ("", "0")
        if sample_every is None:
            sample_every = int(os.environ.get("ACP_PROF_SAMPLE", "32"))
        self.enabled = bool(enabled)
        self.sample_every = max(1, int(sample_every))
        self._flight = flight
        self._lock = threading.Lock()
        self._programs: dict[str, _Program] = {}
        self._warm = False
        self._cold_serving = 0
        self._retraces = 0  # later dispatches of a key that compiled again
        # set-up: per phase [wall s, count, first-dispatch wall s of the
        # programs first dispatched inside it, what jax compiled on its own
        # thread], the phases open now (on any thread), and jax's seconds on
        # this profiler's threads outside every dispatch and phase
        self._setup: dict[str, list] = {}
        self._setup_open: list[_Setup] = []
        self._outside = _Split()
        if self.enabled:
            _listen()
        self._cold_events: "collections.deque[dict]" = collections.deque(
            maxlen=COLD_EVENTS_KEPT
        )
        # the goodput/waste ledger: computed == goodput + sum(waste) holds
        # by construction (account() adds both sides; reclassify() is a
        # clamped zero-sum move) — the armed invariant checker audits it
        self._computed = 0
        self._goodput = 0
        self._waste: dict[str, int] = {c: 0 for c in WASTE_CAUSES}
        # registry values pushed so far, so publish() emits deltas and two
        # concurrent publishers can't double-count
        self._pub_tokens: dict[str, int] = {}
        self._pub_prog: dict[tuple[str, str], int] = {}
        # engine-cycle phases: cumulative self seconds + count per phase
        # name (under the lock: scrape threads read them), the open phases
        # of each thread (the engine thread's, in practice), the clock
        # reading of the latest phase boundary, and the cycle counter
        self._phases: dict[str, list] = {}
        self._blocks = 0
        self._uploads = 0
        self._local = threading.local()
        self._stamp = 0.0
        self._cycle_phase: Optional[_Phase] = None  # the open acp.cycle
        self.cycle_n = 0  # cycles begun: the number of the open (else the latest) one

    # -- write side (engine thread) ---------------------------------------

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def phase(self, name: str):
        """Open engine-loop phase ``name`` (see the module docstring for
        the vocabulary): an ``acp.<name>`` trace annotation tagged with the
        cycle number, and self time into ``stats()["phases"]``. Dispatch
        granularity only — never per slot or per token."""
        if not self.enabled:
            return _NULL_PHASE
        import jax

        return _Phase(self, name, jax.profiler.TraceAnnotation(
            "acp." + name, cycle=self.cycle_n if self._cycle_phase else 0,
        ))

    def setup(self, name: str):
        """Open set-up phase ``name`` on the caller's thread (the
        constructor's and ``prewarm()``'s, not the engine's: no self time,
        no stamp, nothing the cycle clock reads): an ``acp.setup.<name>``
        trace annotation, and wall seconds into
        ``stats()["setup"]["phases"]``. A phase opened inside another is
        inside its wall seconds too (``init.pool`` in ``init``)."""
        if not self.enabled:
            return _NULL_PHASE
        import jax

        _listen()  # a profiler made disabled and enabled since
        return _Setup(self, name, jax.profiler.TraceAnnotation("acp.setup." + name))

    def count_upload(self) -> None:
        """One host-to-device upload by the engine thread (``Engine._put``):
        beside ``blocks`` it says how many a decode block costs. Counted
        whether or not the profiler is enabled: one add, no clock."""
        self._uploads += 1

    def cycle(self, busy: bool) -> None:
        """A new iteration of the engine loop begins. The cycle of the
        iteration before, if it opened one, closes here; this one's opens
        at once when ``busy`` (slots to advance, requests to admit), else
        only if :meth:`begin_cycle` is called (a request arrived while the
        loop was parked). An iteration that never has work opens none."""
        self.end_cycle()
        if busy:
            self.begin_cycle()

    def begin_cycle(self) -> None:
        """The iteration in progress has work after all (idempotent):
        ``acp.cycle`` (a ``StepTraceAnnotation``, ``step_num=<n>``) opens,
        and ``<n>`` tags every phase and flight event until it closes."""
        if not self.enabled or self._cycle_phase is not None:
            return
        import jax

        self.cycle_n += 1
        self._cycle_phase = _Phase(self, "cycle", jax.profiler.StepTraceAnnotation(
            "acp.cycle", step_num=self.cycle_n,
        ))
        self._cycle_phase.__enter__()

    def end_cycle(self) -> None:
        """Close the open cycle (the next iteration's ``cycle()`` does it;
        the engine loop calls this itself only on its way out)."""
        if self._cycle_phase is not None:
            self._cycle_phase.__exit__(None, None, None)
            self._cycle_phase = None

    def stamp(self) -> float:
        """The clock reading of the latest phase boundary: for a consumer
        whose window coincides with phase boundaries (the planner's cycle
        clock), so that it does not read the clock again. With the
        profiler disabled, the clock itself."""
        return self._stamp if self.enabled else time.monotonic()

    def start(self) -> float:
        """Stamp a dispatch about to be issued (0.0 when disabled — the
        matching ``record`` is then skipped by its own guard). What jax
        traces, lowers, compiles or loads on this thread from here to
        ``record`` is that dispatch's."""
        if not self.enabled:
            return 0.0
        st = _thread()
        st.owner = self
        st.open = True
        return time.monotonic()

    def record(
        self,
        key: str,
        t0: float,
        out: Any = None,
        real_tokens: int = 0,
        padded_tokens: int = 0,
        real_slots: int = 0,
        padded_slots: int = 0,
        blocks: int = 0,
    ) -> None:
        """One dispatch of compiled program ``key``: host wall time since
        ``t0`` plus real/padded token+slot counts. ``out`` (any jax value
        the dispatch produced) lets the sampled legs — and always the FIRST
        dispatch of a key, whose wall time is the compile cost — block
        until device-ready for a device-inclusive time. Sampling bounds the
        overhead; blocking changes timing only, never values, so profiler
        on/off stays byte-identical. ``blocks`` is 1 for a dispatch that
        runs a decode block (split or fused): the denominator of the
        per-block host time. An open ``launch`` phase is named for
        ``key``."""
        if not self.enabled or not t0:
            # t0 == 0.0 means start() ran while the profiler was disabled
            # and `enabled` flipped mid-dispatch (bench A/B legs toggle it
            # from another thread) — a time-since-boot "duration" from the
            # zero stamp would corrupt the program's stats
            return
        host_s = time.monotonic() - t0
        st = _thread()
        st.open = False
        split, st.split = st.split, None  # None unless jax compiled in here
        stack = self._stack()
        if stack and stack[-1].name == "launch":
            stack[-1].program(key, t0)
        with self._lock:
            p = self._programs.get(key)
            first = p is None
            if first:
                p = self._programs[key] = _Program()
            sample = first or (p.dispatches % self.sample_every == 0)
            if blocks:
                self._blocks += blocks
        blocked_s = None
        if sample and out is not None:
            import jax

            # waiting on the device is `fetch`, whoever waits: the sampled
            # leg must not read as host time of the launch it sits in
            with self.phase("fetch"):
                jax.block_until_ready(out)
            blocked_s = time.monotonic() - t0
        cold = False
        wall = blocked_s if blocked_s is not None else host_s
        with self._lock:
            if split is not None:
                self._record_split(key, p, split, first, wall, t0)
            p.dispatches += 1
            p.host_s += host_s
            p.real_tokens += int(real_tokens)
            p.padded_tokens += int(padded_tokens)
            p.real_slots += int(real_slots)
            p.padded_slots += int(padded_slots)
            if blocked_s is not None:
                p.blocked_s += blocked_s
                p.blocked_samples += 1
            if first:
                p.first_wall_s = wall
                for ph in self._setup_open:
                    ph.first_wall_s += wall
                if self._warm:
                    p.cold = True
                    self._cold_serving += 1
                    self._cold_events.append(
                        {"program": key, "wall_s": round(wall, 6),
                         "t": round(t0, 6), **p.row()}
                    )
                    cold = True
        REGISTRY.observe(
            "acp_engine_dispatch_seconds", host_s, labels={"program": key},
            help="host wall time per device dispatch, by compiled program "
            "(kind x bucket/width x batch x layout); sampled legs include "
            "block_until_ready device time in the per-program stats",
        )
        if cold:
            REGISTRY.counter_add(
                "acp_engine_cold_compiles_total", 1.0,
                help="first-dispatch-of-shape events AFTER prewarm declared "
                "completion — compiles real traffic paid for at serving "
                "time (each is a latency bug: widen prewarm coverage)",
            )
            if self._flight is not None:
                self._flight.record(
                    "cold_compile", program=key, wall_s=round(wall, 6)
                )

    def _record_split(self, key: str, p: _Program, split: _Split, first: bool,
                      wall: float, t0: float) -> None:
        """Under the lock: what jax did inside one dispatch of ``key``. A
        first dispatch's split partitions its wall time. A later one's
        seconds go to ``outside`` (the row's four parts stay under its
        ``first_wall_ms``); its compiles stay with the key, and each such
        dispatch that went through the backend is a retrace."""
        if first:
            p.add(split)
            return
        if split.compiles:
            self._retraces += 1
            self._cold_events.append(
                {"program": key, "wall_s": round(wall, 6), "t": round(t0, 6),
                 "retrace": True, **split.row()}
            )
        p.compiles += split.compiles
        p.hits += split.hits
        p.misses += split.misses
        split.compiles = split.hits = split.misses = 0
        self._outside.add(split)

    def account(self, goodput: int = 0, **waste: int) -> None:
        """Classify one dispatch's computed token positions: ``goodput``
        plus any :data:`WASTE_CAUSES` keywords. The computed total is the
        sum of what the caller passes, so ledger conservation holds by
        construction; an unknown cause raises (programming error)."""
        if not self.enabled:
            return
        with self._lock:
            total = int(goodput)
            self._goodput += int(goodput)
            for cause, n in waste.items():
                if cause not in self._waste:
                    raise KeyError(f"unknown waste cause {cause!r}")
                if n:
                    self._waste[cause] += int(n)
                    total += int(n)
            self._computed += total

    def reclassify(self, cause: str, n: int) -> None:
        """Move ``n`` already-goodput token positions into ``cause`` — the
        engine discarded compute it had counted useful (preemption without
        a host swap, a failed restore, a dedup follower rewind). Zero-sum
        and clamped at the available goodput, so conservation survives
        over-estimates (e.g. prefix-cache rows that were never computed in
        this admission)."""
        if not self.enabled or n <= 0:
            return
        if cause not in self._waste:
            raise KeyError(f"unknown waste cause {cause!r}")
        with self._lock:
            n = min(int(n), self._goodput)
            if n <= 0:
                return
            self._goodput -= n
            self._waste[cause] += n

    def mark_prewarmed(self) -> None:
        """Prewarm coverage is complete: every LATER first-dispatch of a
        program key is a serving-time cold compile (flight event +
        ``acp_engine_cold_compiles_total``)."""
        with self._lock:
            self._warm = True

    # -- read side (engine loop per cycle + REST scrape threads) ----------

    def publish(self) -> None:  # acp: cross-thread
        """Push ledger counters (as deltas) and the goodput-ratio gauge to
        the registry. Called per scheduler cycle by the engine loop and at
        scrape time; safe from any thread (delta bookkeeping happens under
        the profiler lock, so concurrent publishers never double-count)."""
        if not self.enabled:
            return
        with self._lock:
            token_deltas: list[tuple[str, int]] = []
            for cause, n in [("goodput", self._goodput), *self._waste.items()]:
                d = n - self._pub_tokens.get(cause, 0)
                if d:
                    token_deltas.append((cause, d))
                    self._pub_tokens[cause] = n
            prog_deltas: list[tuple[str, str, int]] = []
            for key, p in self._programs.items():
                for kind, n in (("real", p.real_tokens), ("padded", p.padded_tokens)):
                    d = n - self._pub_prog.get((key, kind), 0)
                    if d:
                        prog_deltas.append((key, kind, d))
                        self._pub_prog[(key, kind)] = n
            computed, goodput = self._computed, self._goodput
        for cause, d in token_deltas:
            REGISTRY.counter_add(
                "acp_engine_tokens_computed_total", float(d),
                labels={"cause": cause},
                help="computed token positions by outcome: goodput (live KV "
                "+ committed tokens) vs the waste causes (bucket/width "
                "padding, rejected drafts, preempt-discarded KV, host-swap "
                "recompute, dedup rewinds, prewarm)",
            )
        for key, kind, d in prog_deltas:
            REGISTRY.counter_add(
                "acp_engine_dispatch_tokens_total", float(d),
                labels={"program": key, "kind": kind},
                help="token positions dispatched per compiled program, "
                "split real vs padding (the per-program padding-waste "
                "series behind the goodput accounting)",
            )
        REGISTRY.gauge_set(
            "acp_engine_goodput_ratio",
            (goodput / computed) if computed else 1.0,
            help="goodput token positions / all computed token positions "
            "(1.0 = no padding or discarded compute); see "
            "acp_engine_tokens_computed_total for the waste attribution",
        )

    def ledger(self) -> dict[str, Any]:  # acp: cross-thread
        """Snapshot of the goodput/waste ledger (the invariant checker's
        conservation input): ``computed == goodput + sum(waste.values())``."""
        with self._lock:
            return {
                "computed": self._computed,
                "goodput": self._goodput,
                "waste": dict(self._waste),
            }

    def stats(self) -> dict[str, Any]:  # acp: cross-thread
        """The /v1/engine/perf payload: per-program dispatch stats, the
        cold-compile observatory, and the goodput/waste ledger."""
        self.publish()
        with self._lock:
            programs: dict[str, dict[str, Any]] = {}
            for key, p in sorted(
                self._programs.items(), key=lambda kv: -kv[1].host_s
            ):
                if not p.dispatches:
                    # record() creates the entry, drops the lock for the
                    # sampled block_until_ready, then increments — a scrape
                    # landing in that window skips the half-born program
                    continue
                padded_pct = (
                    round(100.0 * p.padded_tokens / (p.real_tokens + p.padded_tokens), 2)
                    if (p.real_tokens + p.padded_tokens) else 0.0
                )
                programs[key] = {
                    "dispatches": p.dispatches,
                    "host_ms_total": round(p.host_s * 1e3, 3),
                    "host_ms_mean": round(p.host_s / p.dispatches * 1e3, 4),
                    "device_ms_mean": (
                        round(p.blocked_s / p.blocked_samples * 1e3, 4)
                        if p.blocked_samples else None
                    ),
                    "device_samples": p.blocked_samples,
                    "real_tokens": p.real_tokens,
                    "padded_tokens": p.padded_tokens,
                    "padding_pct": padded_pct,
                    "real_slots": p.real_slots,
                    "padded_slots": p.padded_slots,
                    "first_wall_ms": round(p.first_wall_s * 1e3, 3),
                    "cold": p.cold,
                    # the first dispatch's wall time, partitioned: jax's four
                    # stages, then what is left (the first run itself)
                    **p.row(),
                    "run_ms": round(max(0.0, p.first_wall_s - p.jax_s()) * 1e3, 3),
                }
            waste = dict(self._waste)
            computed, goodput = self._computed, self._goodput
            phases = {
                name: {"s": round(row[0], 6), "n": row[1]}
                for name, row in self._phases.items()
            }
            doc = {
                "enabled": self.enabled,
                "sample_every": self.sample_every,
                "prewarmed": self._warm,
                "programs": programs,
                "cold_compiles": {
                    "serving": self._cold_serving,
                    "retraces": self._retraces,
                    "events": list(self._cold_events),
                },
                "goodput": {
                    "computed": computed,
                    "goodput": goodput,
                    "ratio": round(goodput / computed, 4) if computed else 1.0,
                    "waste": waste,
                },
                # engine-cycle phases: self seconds + count per phase, the
                # busy cycles they partition, and decode-block dispatches
                "phases": phases,
                "cycles": self.cycle_n,
                "blocks": self._blocks,
                "uploads": self._uploads,
            }
            if self.enabled:
                doc["setup"] = self._setup_stats(programs)
        return doc

    def _setup_stats(self, programs: dict[str, dict]) -> dict[str, Any]:
        """Under the lock: the engine's start. ``prewarm_rest_s`` is the
        ``prewarm`` phase's wall seconds less the first dispatches inside
        it: the bursting, the waiting for batches to form and the freeze."""
        phases = {}
        total = _Split()  # programs' + outside's + phases'
        total.add(self._outside)
        for name, (wall, n, first_wall_s, split) in self._setup.items():
            phases[name] = {
                "s": round(wall, 6), "n": n, "jax_s": round(split.jax_s(), 6),
                "compiles": split.compiles, "first_wall_s": round(first_wall_s, 6),
            }
            total.add(split)
        for p in self._programs.values():
            total.add(p)
        prewarm = phases.get("prewarm")
        return {
            "phases": phases,
            "programs": len(programs),
            "compiles": total.compiles,  # with unattributed's, all the backend saw
            "cache_misses": sum(1 for p in self._programs.values() if p.misses),
            "after_prewarm": self._cold_serving,
            "retraces": self._retraces,
            "prewarm_rest_s": (
                round(max(0.0, prewarm["s"] - prewarm["first_wall_s"]), 6) if prewarm else 0.0
            ),
            "saved_s": round(total.saved_s, 6),  # backend seconds the cache's hits stood for
            "outside": self._outside.row(),
            "unattributed": unattributed(),
        }


__all__ = ["DispatchProfiler", "WASTE_CAUSES"]
