"""Engine flight recorder, compile/cold-start journal, postmortem black box.

The metrics registry answers AGGREGATE questions and the span journal
answers PER-REQUEST ones; neither answers *what did the engine loop decide
on iteration N* — which is exactly the question when goodput sags or the
decode-stall watchdog trips.  This module is that third leg (ISSUE 12):

- :class:`FlightRecorder` — a bounded, host-only, ALWAYS-ON ring holding
  one record per engine-loop iteration (mux budget inputs/outputs, decode
  burst width, prefill rows dispatched, slot/tenant occupancy, and where
  the iteration's wall went: :class:`IterationSplit`).  Cheap enough to
  never be off: one dict + deque append per iteration, no device traffic,
  no syscalls.  Exported as Chrome-trace
  slices (the whole record in each slice's args) through the existing
  ``/healthz?trace=1`` journal
  (so PR 9's fleet stitching yields per-peer engine lanes for free) and
  summarized by ``scripts/traceview.py --flight``.
- :class:`CompileWatch` — the compile/cold-start journal: every compiled
  program emits one ``(program, key, shape, seconds, phase, aot_hit,
  cold)`` event.  A compile event AFTER warmup completed is a hole in the
  warmup bucket grid (the ``test_warmup_aot`` bug class) surfaced at
  runtime as ``engine_cold_compiles_total`` + a timeline event instead of
  only in tests.  Since ISSUE 40 it is also the START-UP journal: the
  phases of a serve process from the kernel's process start to ``ready``
  (``startup.*`` spans) and one ``startup.program`` record for each warmed
  program with Python's part (tracing, lowering) and XLA's (the compile or
  the persistent cache's load) apart.  Always on, a few dozen records a
  process, in a bounded list of its own that no request traffic can evict;
  exported on a ``startup`` lane of ``/healthz?trace=1`` and summed in the
  ``/healthz`` ``startup`` section.
- :class:`GcWatch` — the collector's pauses, timed where they happen (one
  ``gc.callbacks`` hook a process, ISSUE 57): what the loop's records, the
  ``process_gc_*`` counters and the ``process.gc_pause`` spans are fed from.
- :class:`BlackBox` — postmortem capture: on a watchdog trip, SLO breach,
  drain timeout, or fatal engine error, atomically snapshot {flight tail,
  scheduler/slot/tenant state, recent spans, metrics, EngineConfig} into
  ONE schema-versioned JSON bundle, kept in a bounded in-memory ring
  (served at ``GET /healthz?postmortem=1``) and written under
  ``artifacts/`` when a directory is configured.

Every field name written into a flight record or a postmortem bundle must
be declared in :data:`FLIGHT_SCHEMA` / :data:`POSTMORTEM_SCHEMA` — the
TC06/TC09 catalog pattern, enforced statically by tunnelcheck rule TC16
and at runtime by :meth:`FlightRecorder.record_iteration` /
:meth:`BlackBox.capture`, so a typo'd field can never silently split the
black-box vocabulary between writers and the tools that read bundles.

Determinism contract: bundles captured at the same logical point of two
seeded chaos runs are identical after :func:`postmortem_canonical` strips
the explicitly-waived wall-clock fields (``WALLCLOCK_WAIVED`` + the
``_ms``/``_s`` suffix families) — pinned by tests/test_flight.py and the
``make chaos`` matrix row.
"""

from __future__ import annotations

import contextlib
import gc
import json
import os
import threading
import time
from collections import deque
from typing import Callable, Deque, Dict, Iterator, List, Optional, Tuple

from p2p_llm_tunnel_tpu.utils.logging import get_logger
from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

log = get_logger(__name__)

#: The one catalogue of legal flight-record field names (tunnelcheck TC16).
#: One record per NON-IDLE engine-loop iteration; wall-clock fields are
#: waived from the postmortem determinism contract (see WALLCLOCK_WAIVED).
FLIGHT_SCHEMA: Dict[str, str] = {
    "iter": "engine-loop iteration sequence number (monotone per recorder)",
    "t": "monotonic instant the iteration started (s; wall-clock, waived)",
    "dur_ms": "host wall time of the whole iteration (waived)",
    "queue_depth": "requests in the scheduler waiting queue at admit time",
    "backlog_rows": (
        "prefill backlog in dispatch rows: remaining chunk segments + "
        "pending whole-prompt rows + parked prefix waiters"
    ),
    "min_slack_s": (
        "tightest deadline slack across queued/backlogged requests fed to "
        "the mux controller (None = no deadlines; wall-clock, waived)"
    ),
    "budget_tokens": (
        "the mux controller's chosen prefill token budget this iteration "
        "(0 when mux is off or nothing waited)"
    ),
    "admitted": "requests bound to decode slots this iteration",
    "prefill_rows": (
        "prefill rows actually dispatched this iteration (chunk segment "
        "rows + budgeted whole-prompt rows)"
    ),
    "decode_steps": "decode burst width dispatched (0 = no burst)",
    "decode_rows": "active rows in the dispatched decode burst",
    "active_slots": "occupied decode slots after admission",
    "tenants": "distinct tenants holding decode slots",
    "waiters": "requests parked behind an in-flight shared-prefix owner",
    "prefix_blocks_used": "prefix-pool blocks in use (0 when the pool is off)",
    "prefix_pages_reserved": (
        "pool pages reserved by admissions whose prompt insert has not "
        "landed yet (ISSUE 14; a nonzero steady state in a postmortem "
        "tail is a reservation leak)"
    ),
    "conv_inserted": (
        "finished conversations whose KV the end-of-iteration drain saved "
        "into the pool this iteration (ISSUE 14)"
    ),
    "spill_pages": (
        "host-tier pages resident at iteration end (ISSUE 16; 0 when the "
        "spill tier is off)"
    ),
    "spill_pageouts": (
        "pool pages the spill drain committed to the host tier this "
        "iteration (ISSUE 16)"
    ),
    "spill_pageins": (
        "host-tier pages spliced back into the pool ahead of admission "
        "this iteration (ISSUE 16; the thrash detector's context — "
        "page-ins racing pageouts over a small window is the signature)"
    ),
    "pages_shipped": (
        "prefix-pool pages exported over the tunnel for KV_PAGES "
        "transfers since the last row (ISSUE 20; exports run off the "
        "iteration rhythm, drained into the next row)"
    ),
    "pages_spliced": (
        "wire-delivered KV pages spliced into the pool since the last "
        "row (ISSUE 20; the decode role's disagg hit signal)"
    ),
    "spec_proposed": (
        "draft tokens proposed to the verify burst this iteration "
        "(ISSUE 17; greedy rows only, 0 when speculation is off/idle)"
    ),
    "spec_accepted": (
        "proposed draft tokens the verify burst accepted this iteration "
        "(ISSUE 17; excludes the always-emitted bonus token)"
    ),
    "spec_k": (
        "burst width K the dispatched spec-verify program used this "
        "iteration (ISSUE 17; 0 when no spec burst ran)"
    ),
    "cold_compiles": "mid-serve cold compiles detected during this iteration",
    "streams_detached": (
        "streams parked in the detached-stream registry's grace window "
        "at iteration end (ISSUE 13; nonzero while the engine is "
        "generating into replay journals with no channel attached)"
    ),
    # -- where the iteration's wall went (ISSUE 57; all waived) ------------
    # Seven parts tile the iteration: their sum is dur_ms to the rounding.
    "admit_ms": (
        "part: expiry, admission, the pool's reservation and eviction, "
        "page-ins, the mux wave's plan"
    ),
    "prefill_ms": "part: prefill dispatch (whole-prompt waves, segments)",
    "dispatch_ms": "part: decode-burst dispatch",
    "fetch_ms": "part: the previous burst's device->host fetch",
    "process_ms": (
        "part: token accounting of the fetched block (_process_burst, "
        "_trace_burst)"
    ),
    "segments_ms": (
        "part: _finish_segments over the iteration's prefill segments "
        "(their fetch, first tokens, pool inserts)"
    ),
    "drain_ms": (
        "part: the end-of-iteration drains (_drain_conv_inserts, "
        "_drain_spill_outs)"
    ),
    "at_ms": (
        "loop phase (the phase marker's names) -> offset of its FIRST start "
        "from t, ms: lays the parts on the clock whatever their order"
    ),
    "wait_ms": (
        "wall inside the iteration's blocking device->host fetches (the "
        "burst's, each segment's, each whole-prompt wave's), measured "
        "around the calls in the executor thread: the host waited for the "
        "chip.  dur_ms - wait_ms is the iteration's HOST time"
    ),
    "waits_ms": "[offset from t, length] of each such fetch, ms",
    "exec_ms": (
        "the iteration's executor calls' own wall, in the executor thread"
    ),
    "lag_ms": (
        "sum over those calls of (the await's wall - the call's own): how "
        "long the loop's coroutine waited for the event loop (or a busy "
        "executor) around work that was done"
    ),
    "evict_ms": (
        "wall of the pool's reservations that evicted, inside admission "
        "(_reserve_pages -> PrefixIndex.reserve); 0 where none did"
    ),
    "evicted_pages": "pages those reservations evicted",
    "gc_ms": "the collector's pause time that fell inside the iteration",
    "gc_full": (
        "how many of those collections were of the oldest generation "
        "(follows the allocator, waived)"
    ),
}

#: The one catalogue of legal start-up journal field names (tunnelcheck
#: TC16): what a compile event (``CompileWatch.note``) and the attrs of a
#: ``startup.*`` span may carry.  Span NAMES are tracing.SPAN_CATALOG's.
STARTUP_SCHEMA: Dict[str, str] = {
    "seq": "compile-event sequence number (monotone per journal)",
    "program": "program kind: decode|spec|prefill|chunk|ragged|copy|...",
    "key": "program key, kind[shape] (copy_in / copy_out for the copies)",
    "shape": "the program's bucket shape",
    "seconds": (
        "wall seconds of the record: lower + compile in the AOT phase, the "
        "first dispatch in the serial pass or on the serving path (waived)"
    ),
    "phase": "aot | warmup (the serial pass) | serve (a cold compile)",
    "aot_hit": (
        "the AOT phase of THIS process already compiled the key, so the "
        "serial pass only loaded it (was cache_hit before ISSUE 40)"
    ),
    "cold": "compiled on the serving path after warm-up declared done",
    "thread": "name of the thread that compiled (process-scoped, waived)",
    "trace_lower_s": (
        "Python's part: tracing and lowering to StableHLO, as a WALL inside "
        "its thread, so with what the thread waited for the interpreter "
        "lock the phase's threads share (AOT: the record's wall less "
        "compile_s; serial pass: JAX's own trace + MLIR duration events in "
        "the dispatching thread)"
    ),
    "compile_s": (
        "XLA's part: the backend compile or the persistent cache's load "
        "(JAX's backend-compile duration event in the compiling thread)"
    ),
    "persistent_hit": (
        "true: the executable came from the compile cache on disk (JAX's "
        "cache_hits event in the compiling thread); false: it was compiled "
        "and written there (cache_misses); null: neither event, so no "
        "cache, a JAX without the events, or a program that compiles "
        "faster than JAX's threshold for caching and is compiled again at "
        "every start (a fact of the machine's disk, waived)"
    ),
    "clock": (
        "where startup.process got its start: proc (/proc/self/stat "
        "starttime) or cli.main (its first line; /proc absent or unusable)"
    ),
    "entries": "tokenizer vocabulary entries",
    "loader": (
        "what loaded the tokenizer: tokenizers (tokenizer.json read with "
        "that library alone) or transformers (AutoTokenizer: the 18-25 s "
        "import, for what the plain loader refuses)"
    ),
    "platform": "JAX's default backend",
    "device_kind": "device kind of the first local device",
    "devices": "local device count",
    "source": "where the parameters came from: random|checkpoint|injected",
    "quant": "weight quantisation mode the engine was built with",
    "bytes": "bytes of the arrays the phase made, from shapes (no fetch)",
    "threads": "AOT compile threads (TUNNEL_WARMUP_PAR)",
}

#: The ``startup.*`` spans that tile ``startup.process``, in order.
STARTUP_PHASES = ("startup.imports", "startup.tokenizer", "startup.backend",
                  "startup.engine_build", "startup.warmup")
#: Start-up records kept: a few dozen a process; beyond the cap (many
#: engines in one test process) later ones are dropped, never the first.
STARTUP_CAPACITY = 2048

#: JAX's monitoring events the journal attributes to the compiling thread.
_EV_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_EV_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_EV_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_EV_MLIR = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_EV_COMPILE = "/jax/core/compile/backend_compile_duration"
_EV_WATCHED = frozenset(
    {_EV_CACHE_HIT, _EV_CACHE_MISS, _EV_TRACE, _EV_MLIR, _EV_COMPILE}
)


#: What JAX reported while a warm-up listened, by the name of the thread
#: that compiled: (instant, event, seconds or 1).  ``CompileWatch.note``
#: takes a thread's entries when it records that thread's program.
_HEARD: Dict[str, List[Tuple[float, str, float]]] = {}
_listeners_lock = threading.Lock()
_listeners_on = False
#: Warm-ups running (replicas may overlap): above 0 the listeners keep
#: what each thread compiles.  At 0 (before and after warm-up) they
#: return at once, so the serving path keeps nothing.
_listening = 0


def _on_jax_event(event: str, *args: float, **_kw: object) -> None:
    """Both of jax.monitoring's listener shapes: ``(event)`` counts one,
    ``(event, seconds)`` gives the seconds.  JAX calls listeners in the
    thread that compiles, so entries kept by thread attribute them: no
    scope is opened around a compile and nothing is added to the code that
    times one."""
    if _listening and event in _EV_WATCHED:
        _HEARD.setdefault(threading.current_thread().name, []).append(
            (time.monotonic(), event, args[0] if args else 1.0))


def _heard(thread: str, t0: float) -> Dict[str, object]:
    """The STARTUP_SCHEMA fields of what ``thread`` compiled since ``t0``
    (its entries are taken: the next program starts empty); nothing where
    nothing was heard."""
    entries = [e for e in _HEARD.pop(thread, ()) if e[0] >= t0]
    if not entries:
        return {}
    sums: Dict[str, float] = {}
    for _t, event, value in entries:
        sums[event] = sums.get(event, 0.0) + value
    # JAX's own two events: a hit loaded the executable from the cache on
    # disk; a miss compiled it and WROTE it there.  Neither: no cache, or a
    # program under JAX's thresholds (a compile shorter than
    # jax_persistent_cache_min_compile_time_secs, 1 s, is never written, so
    # it is compiled again at every start and is no miss).  The program's
    # own compile is the window's last: the helpers that make its arguments
    # (a jnp.zeros) compile before it.
    asked = [event for _t, event, _v in entries
             if event in (_EV_CACHE_HIT, _EV_CACHE_MISS)]
    out: Dict[str, object] = {
        "persistent_hit": asked[-1] == _EV_CACHE_HIT if asked else None,
    }
    if _EV_TRACE in sums or _EV_MLIR in sums:
        out["trace_lower_s"] = round(
            sums.get(_EV_TRACE, 0.0) + sums.get(_EV_MLIR, 0.0), 4)
    if _EV_COMPILE in sums:
        out["compile_s"] = round(sums[_EV_COMPILE], 4)
    return out


def _install_listeners() -> None:
    global _listeners_on
    with _listeners_lock:
        if _listeners_on:
            return
        _listeners_on = True
        try:
            from jax import monitoring

            monitoring.register_event_listener(_on_jax_event)
            monitoring.register_event_duration_secs_listener(_on_jax_event)
        except Exception as e:  # no such API: every record says null
            log.info("jax.monitoring listeners unavailable: %s", e)


def program_rollup(programs: List[Dict[str, object]],
                   slowest: int = 5) -> Dict[str, object]:
    """What /healthz's ``startup`` section and ``traceview.py --startup``
    both say of a start's ``startup.program`` records (each its attrs plus
    ``seconds``, the record's duration).  The AOT phase's records carry
    the compile; a start without one (no TUNNEL_WARMUP_PAR) compiled in
    its serial pass, and those are read instead."""
    # (the copy programs' records are the serial pass's in every start)
    compiled = [p for p in programs if p.get("phase") == "aot"] or programs
    lower = [p["trace_lower_s"] for p in compiled
             if p.get("trace_lower_s") is not None]
    return {
        "programs": len({p.get("key") for p in programs}),
        "trace_lower_s_per_program": (
            sum(lower) / len(lower) if lower else None),
        "persistent_hits": sum(
            p.get("persistent_hit") is True for p in compiled),
        "persistent_misses": sum(
            p.get("persistent_hit") is False for p in compiled),
        "slowest": sorted(
            compiled, key=lambda p: -p["seconds"])[:slowest],
    }


def process_start(fallback: float) -> Tuple[float, str]:
    """The kernel's start of this process on ``time.monotonic()``'s clock,
    and where it came from.  ``/proc/self/stat`` field 22 counts clock
    ticks since boot; CLOCK_BOOTTIME read beside CLOCK_MONOTONIC converts
    it (they differ by time spent suspended).  ``fallback`` (the first
    line of ``cli.main``) where /proc is absent or the answer is not a
    past instant of this boot."""
    try:
        with open("/proc/self/stat", "rb") as f:
            # the command may hold spaces and parentheses: split after it
            fields = f.read().rsplit(b")", 1)[1].split()
        ticks = int(fields[19])  # field 22; fields[0] is field 3
        since_boot = ticks / os.sysconf("SC_CLK_TCK")
        now_mono = time.monotonic()
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - since_boot
        if 0.0 <= age < 86400.0 and now_mono - age <= fallback:
            return now_mono - age, "proc"
    except (OSError, ValueError, IndexError, AttributeError):
        pass
    return fallback, "cli.main"


#: The one catalogue of legal postmortem-bundle top-level fields
#: (tunnelcheck TC16).  ``BlackBox.capture`` builds EXACTLY this key set —
#: a runtime lockstep guard backs the static rule.
POSTMORTEM_SCHEMA: Dict[str, str] = {
    "schema_version": "bundle schema version (int; bump on shape changes)",
    "trigger": (
        "what fired the capture: watchdog|slo|drain|crash|manual|memory"
    ),
    "attribution": (
        "where the engine was when the trigger fired — the flight "
        "recorder's current loop phase for watchdog/crash, the objective "
        "for slo, free text otherwise"
    ),
    "captured_unix_s": "wall-clock capture instant (waived)",
    "degraded": "the engine_degraded gauge at capture time (0/1)",
    "flight": "the last N flight records (FLIGHT_SCHEMA rows)",
    "compile_events": "the compile/cold-start journal (CompileWatch rows)",
    "spans": "recent span-journal records (empty when tracing is off)",
    "metrics": "full metrics snapshot (counters, gauges, histogram tails)",
    "slo": "per-objective SLO verdicts at capture time",
    "engine": (
        "the engine provider's state: EngineConfig, scheduler/slot/tenant "
        "snapshot, backlog registries, warmed-program set (null when no "
        "engine registered)"
    ),
}

POSTMORTEM_SCHEMA_VERSION = 1

#: Legal capture triggers.
POSTMORTEM_TRIGGERS = ("watchdog", "slo", "drain", "crash", "manual",
                       "memory")

#: Field NAMES excluded from the bundle-determinism contract: wall-clock
#: instants/durations and process-scoped ids.  Together with the
#: WALLCLOCK_SUFFIXES families, these are the ONLY fields two seeded chaos
#: runs may disagree on (tests/test_flight.py pins the rest byte-for-byte).
WALLCLOCK_WAIVED = frozenset({
    "captured_unix_s", "t", "ts", "dur", "seconds", "min_slack_s",
    "span_id", "parent_id", "trace_id",
    # a compile event's thread name and whether the machine's disk held
    # the executable (STARTUP_SCHEMA): facts of the process, not the run
    "thread", "persistent_hit",
    # what the collector did inside an iteration (FLIGHT_SCHEMA), and the
    # counters that sum the clock's and the collector's doings (ISSUE 57)
    "gc_full",
    "engine_loop_host_seconds_total", "engine_loop_wait_seconds_total",
    "engine_loop_lag_seconds_total", "process_gc_pause_seconds_total",
    "process_gc_collections_total", "process_gc_full_collections_total",
})
#: Field-name suffixes waived as wall-clock derived (``engine_ttft_ms``,
#: ``engine_warmup_compile_s``, ``tenant_tokens_per_s``, ...); the
#: ``_ms_`` infix covers the registry's derived histogram keys
#: (``engine_ttft_ms_p50``...).
WALLCLOCK_SUFFIXES = ("_ms", "_s", "_per_s")


def _waived(key: str) -> bool:
    return (key in WALLCLOCK_WAIVED or key.endswith(WALLCLOCK_SUFFIXES)
            or "_ms_" in key or "_s_" in key)


def postmortem_canonical(obj: object) -> object:
    """The deterministic projection of a bundle: every waived wall-clock
    field removed, recursively.  Two seeded chaos runs' bundles must be
    EQUAL under this projection — the explicit waiver list is the whole
    escape hatch, so any new nondeterminism fails the identity test
    instead of quietly widening it."""
    if isinstance(obj, dict):
        return {
            k: postmortem_canonical(v)
            for k, v in obj.items()
            if not _waived(str(k))
        }
    if isinstance(obj, (list, tuple)):
        return [postmortem_canonical(v) for v in obj]
    return obj


#: The loop's phases (its phase marker's names) and the record's part each
#: one's wall is summed into.  The parts tile the iteration.
LOOP_PARTS: Dict[str, str] = {
    "admit": "admit_ms",
    "prefill_dispatch": "prefill_ms",
    "decode_dispatch": "dispatch_ms",
    "decode_fetch": "fetch_ms",
    "process": "process_ms",
    "segments": "segments_ms",
    "drain": "drain_ms",
}
#: An iteration whose HOST time (dur_ms - wait_ms) reaches this logs its
#: split, so an untraced run's serve.log names its own holes ...
LONG_HOLD_MS = 50.0
#: ... at most one line in this many seconds.
LONG_HOLD_EVERY_S = 1.0
#: With the span journal on, a collection of the oldest generation or one
#: this long writes a ``process.gc_pause`` span.
GC_SPAN_MIN_S = 0.001


def _ms(seconds: float) -> float:
    return round(seconds * 1000.0, 3)


class GcWatch:
    """The collector's pauses, timed where they happen (ISSUE 57).

    One ``gc.callbacks`` hook a process, installed when the first engine
    starts.  The hook runs INSIDE a collection, which any allocation may
    start, also one made under the metrics registry's or the span journal's
    lock: so it takes no lock and calls nothing that does.  It adds to plain
    totals (collections never overlap: the interpreter runs one at a time,
    under its lock) and keeps the pauses worth a span; :meth:`publish`,
    called from outside the collector (the engine loop at every record and
    park, the ``/metrics`` and ``/healthz?trace=1`` handlers), moves the
    growth into the ``process_gc_*`` counters and the kept pauses into the
    journal.  It changes no threshold and freezes nothing."""

    def __init__(self) -> None:
        self.pause_s = 0.0
        self.collections = 0
        self.full = 0
        self._began = 0.0
        self._published = (0.0, 0, 0)
        self._pauses: Deque[Tuple[float, float, int, int]] = deque(maxlen=4096)
        self._tracer = None
        self._lock = threading.Lock()  # publish()'s alone

    def install(self) -> None:
        """Hook the collector (once a process; later calls do nothing)."""
        from p2p_llm_tunnel_tpu.utils.tracing import global_tracer

        with self._lock:
            if self._tracer is None:
                self._tracer = global_tracer
                gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        now = time.monotonic()
        if phase == "start":
            self._began = now
            return
        pause = now - self._began
        full = info.get("generation") == 2
        self.pause_s += pause
        self.collections += 1
        self.full += full
        if self._tracer.enabled and (full or pause >= GC_SPAN_MIN_S):
            self._pauses.append((self._began, pause, info.get("generation"),
                                 info.get("collected")))

    def totals(self) -> Tuple[float, int]:
        """(pause seconds, collections of the oldest generation) so far:
        the loop reads it at an iteration's two ends."""
        return self.pause_s, self.full

    def publish(self) -> None:
        with self._lock:
            now = (self.pause_s, self.collections, self.full)
            grown = [a - b for a, b in zip(now, self._published)]
            self._published = now
            for name, amount in zip(
                    ("process_gc_pause_seconds_total",
                     "process_gc_collections_total",
                     "process_gc_full_collections_total"), grown):
                if amount:
                    global_metrics.inc(name, amount)
            while self._pauses:
                began, pause, generation, collected = self._pauses.popleft()
                self._tracer.add_span(
                    "process.gc_pause", trace_id=None, track="process",
                    t0=began, t1=began + pause,
                    attrs={"generation": generation, "collected": collected},
                )


class IterationSplit:
    """Where one engine-loop iteration's wall went (ISSUE 57): made at the
    iteration's start, fed by the loop as it goes, read once into the
    iteration's flight record.  A dozen ``time.monotonic()`` calls and a
    few small containers an iteration; always on, as the ring is.

    - :meth:`enter` moves the loop's phase marker and closes the part that
      ran: the parts (``LOOP_PARTS``) tile the iteration;
    - :meth:`call` is the loop's ``run_in_executor``: the call's own wall
      in the executor thread, and how much longer the loop's coroutine
      waited for it (the event loop's lag);
    - :meth:`fetched` (executor thread) notes a blocking device->host
      fetch: the host waited for the chip;
    - :meth:`evicted` notes a reservation that evicted pool pages."""

    def __init__(self, recorder: "FlightRecorder") -> None:
        self.t0 = self._since = time.monotonic()
        self._recorder = recorder
        self._phase: Optional[str] = None
        self._parts = dict.fromkeys(LOOP_PARTS, 0.0)
        self._at: Dict[str, float] = {}
        self._waits: List[Tuple[float, float]] = []
        self._exec_s = self._lag_s = self._evict_s = 0.0
        self._evicted = 0
        self._gc0 = global_gc.totals()

    def enter(self, phase: str) -> None:
        if self._phase is not None:  # (the first phase opens at t0)
            now = time.monotonic()
            self._parts[self._phase] += now - self._since
            self._since = now
        self._phase = phase
        self._at.setdefault(phase, self._since - self.t0)
        self._recorder.set_phase(phase)

    async def call(self, loop, executor, fn: Callable, *args: object):
        """``await loop.run_in_executor(executor, fn, *args)``, timed."""
        asked = time.monotonic()
        own = [0.0]

        def run():
            began = time.monotonic()
            try:
                return fn(*args)
            finally:
                own[0] = time.monotonic() - began

        try:
            return await loop.run_in_executor(executor, run)
        finally:
            self._exec_s += own[0]
            self._lag_s += max(0.0, time.monotonic() - asked - own[0])

    def fetched(self, began: float) -> None:
        self._waits.append((began, time.monotonic() - began))

    def evicted(self, began: float, pages: int) -> None:
        self._evict_s += time.monotonic() - began
        self._evicted += pages

    def fields(self) -> Dict[str, object]:
        """The record's FLIGHT_SCHEMA fields of the split, the iteration
        ending now."""
        now = time.monotonic()
        if self._phase is not None:
            self._parts[self._phase] += now - self._since
            self._since = now
        gc_s, gc_full = global_gc.totals()
        out: Dict[str, object] = {
            "t": self.t0,
            "dur_ms": _ms(now - self.t0),
            "at_ms": {phase: _ms(at) for phase, at in self._at.items()},
            "wait_ms": _ms(sum(length for _t, length in self._waits)),
            "waits_ms": [[_ms(began - self.t0), _ms(length)]
                         for began, length in self._waits],
            "exec_ms": _ms(self._exec_s),
            "lag_ms": _ms(self._lag_s),
            "evict_ms": _ms(self._evict_s),
            "evicted_pages": self._evicted,
            "gc_ms": _ms(gc_s - self._gc0[0]),
            "gc_full": gc_full - self._gc0[1],
        }
        for phase, field in LOOP_PARTS.items():
            out[field] = _ms(self._parts[phase])
        return out


class FlightRecorder:
    """Bounded, thread-safe, always-on ring of engine-loop iteration
    records, plus the loop's current-phase marker (what the watchdog
    reports as stall attribution)."""

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            capacity = int(
                os.environ.get("TUNNEL_FLIGHT_RECORDS", "") or 1024
            )
        self._lock = threading.Lock()
        self.capacity = max(1, capacity)
        self._records: Deque[Dict[str, object]] = deque(maxlen=self.capacity)
        self._iter = 0
        self._phase = "idle"
        self._hold_said = float("-inf")
        self._holds_unsaid = 0

    def configure(self, *, capacity: Optional[int] = None) -> None:
        with self._lock:
            if capacity is not None and capacity != self.capacity:
                self.capacity = max(1, capacity)
                self._records = deque(self._records, maxlen=self.capacity)

    def reset(self) -> None:
        with self._lock:
            self._records.clear()
            self._iter = 0
            self._phase = "idle"
            self._hold_said = float("-inf")
            self._holds_unsaid = 0

    # -- phase marker ------------------------------------------------------

    def set_phase(self, phase: str) -> None:
        """Mark which loop phase is executing.  A wedged XLA dispatch
        leaves this at the stalled phase — the watchdog's attribution."""
        self._phase = phase

    def current_phase(self) -> str:
        return self._phase

    # -- recording ---------------------------------------------------------

    def record_iteration(self, **fields: object) -> None:
        """Append one iteration record.  Field names must come from
        FLIGHT_SCHEMA (the runtime twin of tunnelcheck TC16 — a typo'd
        field would otherwise silently split the black-box vocabulary);
        ``iter`` is assigned here."""
        unknown = set(fields) - set(FLIGHT_SCHEMA)
        if unknown:
            raise ValueError(
                f"flight-record field(s) not in FLIGHT_SCHEMA: "
                f"{sorted(unknown)}"
            )
        with self._lock:
            self._iter += 1
            rec = {"iter": self._iter}
            rec.update(fields)
            self._records.append(rec)
        global_metrics.inc("engine_flight_iterations_total")
        if "wait_ms" in fields:
            self._note_host_time(rec)

    def _note_host_time(self, rec: Dict[str, object]) -> None:
        """What an UNTRACED run leaves behind of the split (ISSUE 57): the
        three sums on ``/metrics``, and one INFO line for an iteration that
        held the loop for LONG_HOLD_MS of host time, at most one in
        LONG_HOLD_EVERY_S on the records' own clock."""
        dur, wait = float(rec["dur_ms"]), float(rec["wait_ms"])
        host = max(0.0, dur - wait)
        global_metrics.inc("engine_loop_host_seconds_total", host / 1000.0)
        global_metrics.inc("engine_loop_wait_seconds_total", wait / 1000.0)
        global_metrics.inc("engine_loop_lag_seconds_total",
                           float(rec.get("lag_ms", 0.0)) / 1000.0)
        if host < LONG_HOLD_MS:
            return
        end = float(rec["t"]) + dur / 1000.0
        if end - self._hold_said < LONG_HOLD_EVERY_S:
            self._holds_unsaid += 1
            return
        log.info(
            "engine loop: iteration %d held the loop %.1f ms of host time "
            "(dur %.1f, wait %.1f; %s; exec %.1f lag %.1f evict %.1f/%d "
            "pages gc %.1f/%d full; %d more such since the last line)",
            rec["iter"], host, dur, wait,
            " ".join(f"{phase} {rec.get(part, 0.0):.1f}"
                     for phase, part in LOOP_PARTS.items()),
            rec.get("exec_ms", 0.0), rec.get("lag_ms", 0.0),
            rec.get("evict_ms", 0.0), rec.get("evicted_pages", 0),
            rec.get("gc_ms", 0.0), rec.get("gc_full", 0),
            self._holds_unsaid,
        )
        self._hold_said, self._holds_unsaid = end, 0

    # -- reading -----------------------------------------------------------

    def records(self, last_n: Optional[int] = None) -> List[Dict[str, object]]:
        with self._lock:
            out = list(self._records)
        if last_n is not None:
            out = out[-last_n:]
        return [dict(r) for r in out]

    @property
    def iterations(self) -> int:
        return self._iter

    def chrome_events(self) -> List[Dict[str, object]]:
        """The ring as Chrome trace events: one ``ph:"X"`` slice per
        iteration on an ``engine-flight`` lane (args = the full record:
        what ``scripts/traceview.py --flight`` and the benchmark's readers
        ``layer_metrics/loop_host.py`` and ``idle_by_phase.py`` read).
        Merged into the ``/healthz?trace=1`` export by the serve loop, so
        the fleet stitcher gives every peer its own engine-flight lane."""
        recs = self.records()
        events: List[Dict[str, object]] = []
        if not recs:
            return events
        tid = 1001  # clear of the recorder's small per-track tid space
        events.append({
            "ph": "M", "name": "thread_name", "pid": 1, "tid": tid,
            "args": {"name": "engine-flight"},
        })
        for rec in recs:
            t = float(rec.get("t", 0.0) or 0.0)
            dur_ms = float(rec.get("dur_ms", 0.0) or 0.0)
            ts = int(t * 1e6)
            events.append({
                "name": "engine.flight", "cat": "engine-flight",
                "ph": "X", "pid": 1, "tid": tid, "ts": ts,
                "dur": max(1, int(dur_ms * 1000)),
                "args": dict(rec),
            })
        return events


class CompileWatch:
    """Bounded, thread-safe journal of program compiles and of start-up.

    **Compile events**: one per (program kind, bucket shape) the FIRST time
    a process compiles/loads it: warmup's AOT phase, warmup's serial
    execute pass (``aot_hit`` when the AOT phase already compiled the
    key), and — the alarm case — ``cold=True`` mid-serve compiles after
    warmup declared the grid complete.

    **Start-up records** (ISSUE 40): ``startup.*`` spans and instants on
    ``time.monotonic()``'s clock, one ``startup.program`` span for every
    compile event that is not cold, in a list of their own that is filled
    once and never evicted (request traffic writes elsewhere; past
    ``STARTUP_CAPACITY`` later records are dropped, never the first).
    Recorded whether request tracing is on or not."""

    def __init__(self, capacity: int = 512):
        self._lock = threading.Lock()
        self._events: Deque[Dict[str, object]] = deque(maxlen=max(1, capacity))
        self._seq = 0
        self._cold = 0
        self._startup: List[Dict[str, object]] = []
        self._process: Optional[Tuple[float, str]] = None

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
            self._seq = 0
            self._cold = 0
            self._startup.clear()
            self._process = None

    @staticmethod
    def _check(fields: Dict[str, object]) -> None:
        unknown = set(fields) - set(STARTUP_SCHEMA)
        if unknown:
            raise ValueError(
                f"start-up journal field(s) not in STARTUP_SCHEMA: "
                f"{sorted(unknown)}"
            )

    # -- compile events ----------------------------------------------------

    def note(self, *, program: str, key: str, shape: List[int],
             seconds: float, phase: str, aot_hit: bool = False,
             cold: bool = False, **fields: object) -> None:
        """One compile event of the calling thread, ended now.  While a
        warm-up listens, ``trace_lower_s``, ``compile_s`` and
        ``persistent_hit`` come from what JAX reported in this thread over
        the record's ``seconds``; ``fields`` (STARTUP_SCHEMA) override."""
        self._check(fields)
        t1 = time.monotonic()
        thread = threading.current_thread().name
        event = {
            "program": program, "key": key, "shape": list(shape),
            "seconds": round(seconds, 4), "phase": phase,
            "aot_hit": bool(aot_hit), "cold": bool(cold), "thread": thread,
            "trace_lower_s": None, "compile_s": None,
            "persistent_hit": None,
        }
        if _listening:
            event.update(_heard(thread, t1 - seconds - 0.05))
            if phase == "aot" and event["compile_s"] is not None:
                # the record is a whole lowering and compile: XLA's part
                # is what JAX timed, Python's the rest of the wall
                xla = event["compile_s"] = min(event["compile_s"], seconds)
                event["trace_lower_s"] = round(seconds - xla, 4)
        event.update(fields)
        with self._lock:
            self._seq += 1
            event["seq"] = self._seq
            self._events.append(event)
            if cold:
                self._cold += 1
        if not cold:
            # the event itself, not a copy: one dict, in the ring a bundle
            # reads and in the list no later event can push it out of
            self._keep("startup.program", t1 - seconds, seconds, event)

    def mark(self) -> int:
        """Current sequence number — pass to :meth:`since` to read only
        events recorded after this point (one engine's warmup)."""
        with self._lock:
            return self._seq

    def since(self, mark: int) -> List[Dict[str, object]]:
        with self._lock:
            return [dict(e) for e in self._events if e["seq"] > mark]

    def events(self) -> List[Dict[str, object]]:
        with self._lock:
            return [dict(e) for e in self._events]

    @property
    def cold_total(self) -> int:
        return self._cold

    # -- JAX's own compile events ------------------------------------------

    @staticmethod
    def listen(on: bool) -> None:
        """Warm-up switches JAX's compile events on for its length (the
        listeners are installed once, on the first call); :meth:`note`
        then finds each program's in what its thread reported."""
        global _listening
        if on:
            _install_listeners()
        with _listeners_lock:
            _listening = max(0, _listening + (1 if on else -1))
            if not _listening:
                _HEARD.clear()

    # -- start-up spans ----------------------------------------------------

    def process_began(self, fallback: Optional[float] = None,
                      ) -> Tuple[float, str]:
        """(start of this process on the monotonic clock, ``proc`` or
        ``cli.main``): settled on the first call, which ``cli.main`` makes
        on its first line."""
        with self._lock:
            if self._process is None:
                self._process = process_start(
                    time.monotonic() if fallback is None else fallback)
            return self._process

    def _keep(self, name: str, ts: float, dur: Optional[float],
              attrs: Dict[str, object]) -> None:
        with self._lock:
            if len(self._startup) < STARTUP_CAPACITY:
                self._startup.append(
                    {"name": name, "ts": ts, "dur": dur, "attrs": attrs})

    def add_span(self, name: str, *, t0: float, t1: Optional[float] = None,
                 **attrs: object) -> None:
        """One completed start-up span (``name`` from SPAN_CATALOG, attrs
        from STARTUP_SCHEMA); ``t1`` defaults to now."""
        self._check(attrs)
        end = time.monotonic() if t1 is None else t1
        self._keep(name, t0, max(0.0, end - t0), attrs)

    def add_event(self, name: str, *, t: Optional[float] = None,
                  **attrs: object) -> None:
        """One start-up instant."""
        self._check(attrs)
        self._keep(name, time.monotonic() if t is None else t, None, attrs)

    @contextlib.contextmanager
    def startup_phase(self, name: str,
                      **attrs: object) -> Iterator[Dict[str, object]]:
        """Time the block as the span ``name``; the yielded dict takes
        attrs learned inside it.  A block that raises records nothing."""
        t0 = time.monotonic()
        yield attrs
        self.add_span(name, t0=t0, **attrs)

    def mark_ready(self) -> None:
        """The engine is warm and its backend installed: the instant
        ``startup.ready``, and the root span ``startup.process`` from the
        kernel's start of the process to it."""
        t0, clock = self.process_began()
        now = time.monotonic()
        self.add_span("startup.process", t0=t0, t1=now, clock=clock)
        self.add_event("startup.ready", t=now)

    def startup_records(self) -> List[Dict[str, object]]:
        with self._lock:
            return [dict(r, attrs=dict(r["attrs"])) for r in self._startup]

    def chrome_events(self) -> List[Dict[str, object]]:
        """The start-up records as Chrome trace events: the phases on a
        ``startup`` lane, each compiling thread's ``startup.program``
        slices on a lane of its own beside it (they overlap in the AOT
        phase).  ``ts`` / ``dur`` in µs of ``time.monotonic()``, attrs
        under ``args``: the form the span journal exports."""
        recs = self.startup_records()
        if not recs:
            return []
        lanes: Dict[str, int] = {"startup": 1002}
        events: List[Dict[str, object]] = []
        for rec in recs:
            lane = "startup"
            if rec["name"] == "startup.program":
                lane = f"startup {rec['attrs'].get('thread', '')}"
            tid = lanes.setdefault(lane, 1002 + len(lanes))
            ev: Dict[str, object] = {
                "name": rec["name"], "cat": "startup", "pid": 1, "tid": tid,
                "ts": int(rec["ts"] * 1e6), "args": rec["attrs"],
            }
            if rec["dur"] is None:
                ev.update(ph="i", s="t")
            else:
                ev.update(ph="X", dur=max(1, int(rec["dur"] * 1e6)))
            events.append(ev)
        return [
            {"ph": "M", "name": "thread_name", "pid": 1, "tid": tid,
             "args": {"name": lane}}
            for lane, tid in lanes.items()
        ] + events

    def startup_section(self) -> Dict[str, object]:
        """The ``/healthz`` ``startup`` payload: seconds by phase (summed
        over engines where a process built several), the warmed programs
        and how many of them the compile cache on disk held, the slowest
        one.  An operator sizes a rolling restart's drain window from
        ``to_ready_s`` and tells a cold cache (``persistent_misses``) from
        a slow runtime start (``phases_s['startup.backend']``)."""
        recs = self.startup_records()
        phases: Dict[str, float] = {}
        for rec in recs:
            if rec["dur"] is not None and rec["name"] != "startup.program":
                phases[rec["name"]] = round(
                    phases.get(rec["name"], 0.0) + rec["dur"], 3)
        rollup = program_rollup(
            [dict(r["attrs"], seconds=r["dur"]) for r in recs
             if r["name"] == "startup.program"], slowest=1)
        slowest = rollup.pop("slowest")
        del rollup["trace_lower_s_per_program"]
        return {
            "ready": any(r["name"] == "startup.ready" for r in recs),
            "to_ready_s": phases.get("startup.process"),
            "phases_s": phases,
            **rollup,
            "slowest_program": (
                {"key": slowest[0]["key"],
                 "seconds": round(slowest[0]["seconds"], 3)}
                if slowest else None),
        }


class BlackBox:
    """Postmortem bundle capture + bounded in-memory store + archive dir.

    ``capture`` assembles EXACTLY the POSTMORTEM_SCHEMA key set from the
    process-global observability state (flight ring, compile journal,
    span journal, metrics registry, SLO verdicts) plus the registered
    engine provider, stores the bundle in a small ring (served at
    ``GET /healthz?postmortem=1``), and — when a directory is configured
    (``TUNNEL_POSTMORTEM_DIR`` / serve ``--postmortem-dir``) — writes it
    atomically (tmp + rename) as one JSON file."""

    #: Bundles kept in memory; flight tail length embedded per bundle.
    STORE_CAP = 8
    FLIGHT_TAIL = 256

    def __init__(self, directory: Optional[str] = None):
        if directory is None:
            directory = os.environ.get("TUNNEL_POSTMORTEM_DIR", "")
        self._lock = threading.Lock()
        self.directory = directory or ""
        self._bundles: Deque[Dict[str, object]] = deque(maxlen=self.STORE_CAP)
        self._paths: List[str] = []
        self._seq = 0
        self._capturing = False
        self._engine_provider: Optional[Callable[[], Optional[dict]]] = None
        #: Outstanding archive-writer threads (non-daemon, bounded work).
        self._writers: List[threading.Thread] = []

    def configure(self, *, directory: Optional[str] = None) -> None:
        with self._lock:
            if directory is not None:
                self.directory = directory

    def reset(self) -> None:
        with self._lock:
            self._bundles.clear()
            self._paths.clear()
            self._seq = 0
            self._engine_provider = None

    def set_engine_provider(
        self, fn: Optional[Callable[[], Optional[dict]]]
    ) -> None:
        """Register the engine-state contributor (latest engine wins —
        one serving engine per process is the deployed shape)."""
        with self._lock:
            self._engine_provider = fn

    # -- capture -----------------------------------------------------------

    def capture(self, trigger: str, attribution: Optional[str] = None,
                slo: Optional[dict] = None,
                extra: Optional[dict] = None) -> Optional[dict]:
        """Snapshot the black box.  Returns the bundle, or None when a
        capture is already in progress (re-entrancy guard: an SLO publish
        inside a capture must not recurse into a second capture) or the
        assembly itself failed.

        ``extra`` merges declared POSTMORTEM_SCHEMA fields over the
        assembled defaults (tunnelcheck TC16 checks literal keys; the
        drift guard below rejects undeclared ones at runtime).

        NEVER raises past the unknown-trigger precondition: every caller
        sits on an incident path (a crash handler, the watchdog, a drain
        that already blew its budget) where a diagnostics failure
        preempting the actual failure handling would be strictly worse
        than a missing bundle — assembly errors log loudly and return
        None instead."""
        if trigger not in POSTMORTEM_TRIGGERS:
            raise ValueError(f"unknown postmortem trigger {trigger!r}")
        with self._lock:
            if self._capturing:
                return None
            self._capturing = True
            provider = self._engine_provider
        try:
            return self._capture_inner(
                trigger, attribution, slo, extra, provider
            )
        except Exception:
            log.exception(
                "postmortem capture failed (trigger=%s); the incident "
                "path continues without a bundle", trigger,
            )
            return None
        finally:
            with self._lock:
                self._capturing = False

    def _capture_inner(self, trigger, attribution, slo, extra,
                       provider) -> dict:
        if slo is None:
            from p2p_llm_tunnel_tpu.utils.slo import global_slo

            slo = global_slo.section()
        engine_state = None
        if provider is not None:
            try:
                engine_state = provider()
            except Exception as e:  # a torn engine must not block capture
                engine_state = {"provider_error": str(e)}
        from p2p_llm_tunnel_tpu.utils.tracing import global_tracer

        bundle: Dict[str, object] = {
            "schema_version": POSTMORTEM_SCHEMA_VERSION,
            "trigger": trigger,
            "attribution": attribution,
            "captured_unix_s": round(time.time(), 3),
            "degraded": global_metrics.gauge("engine_degraded"),
            "flight": global_flight.records(last_n=self.FLIGHT_TAIL),
            "compile_events": global_compile_watch.events(),
            "spans": [
                {
                    "name": r.name, "trace_id": r.trace_id,
                    "span_id": r.span_id, "parent_id": r.parent_id,
                    "track": r.track, "ts": r.ts, "dur": r.dur,
                    "attrs": dict(r.attrs),
                }
                for r in global_tracer.records()
            ],
            "metrics": global_metrics.snapshot(),
            "slo": slo,
            "engine": engine_state,
        }
        bundle.update(extra or {})
        # Runtime lockstep with the declared schema (the static half is
        # tunnelcheck TC16): the builder above — and any extra= keys —
        # must match POSTMORTEM_SCHEMA exactly, loudly (the raise is
        # absorbed by capture()'s never-break-serving guard but lands in
        # the log and fails the schema tests).
        drift = set(bundle).symmetric_difference(POSTMORTEM_SCHEMA)
        if drift:
            raise RuntimeError(
                f"postmortem bundle schema drift: {sorted(drift)}"
            )
        with self._lock:
            self._seq += 1
            seq = self._seq
            self._bundles.append(bundle)
            directory = self.directory
        global_metrics.inc("engine_postmortems_total")
        log.error(
            "postmortem captured: trigger=%s attribution=%s "
            "(%d flight records, %d compile events)",
            trigger, attribution, len(bundle["flight"]),
            len(bundle["compile_events"]),
        )
        if directory:
            # Archive off the caller's thread: the SLO-edge capture runs
            # on the serving event loop, and a multi-MB json.dump to disk
            # there would stall every tunnel stream at exactly the moment
            # the SLO is burning.  NON-daemon so a process exiting right
            # after an incident (the chaos gate, a crashing serve) still
            # finishes the one bounded write; flush() joins explicitly.
            t = threading.Thread(
                target=self._write, args=(bundle, directory, seq),
                name="postmortem-write",
            )
            with self._lock:
                self._writers = [w for w in self._writers if w.is_alive()]
                self._writers.append(t)
            t.start()
        return bundle

    def flush(self, timeout: float = 10.0) -> None:
        """Join outstanding archive writes (tests, pre-exit hooks)."""
        with self._lock:
            writers = list(self._writers)
        for t in writers:
            t.join(timeout)

    def _write(self, bundle: dict, directory: str, seq: int) -> None:
        """Atomic archive write: a reader (the chaos summary, an operator
        tailing artifacts/) never sees a torn bundle."""
        try:
            os.makedirs(directory, exist_ok=True)
            name = f"postmortem-{bundle['trigger']}-{os.getpid()}-{seq:03d}.json"
            path = os.path.join(directory, name)
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(bundle, f, indent=1, default=str)
            os.replace(tmp, path)
            with self._lock:
                self._paths.append(path)
            log.error("postmortem bundle written to %s", path)
        except OSError as e:
            log.warning("postmortem bundle write failed: %s", e)

    def section(self) -> Dict[str, object]:
        """The ``/healthz?postmortem=1`` payload — ONE builder shared by
        the serve loop and the proxy's fleet federation, so the federated
        ``proxy`` entry can never drift from the per-peer entries."""
        return {
            "postmortem": self.last(),
            "captured": self.captured,
            "paths": self.paths(),
        }

    # -- reading -----------------------------------------------------------

    def last(self) -> Optional[dict]:
        with self._lock:
            return dict(self._bundles[-1]) if self._bundles else None

    def bundles(self) -> List[dict]:
        with self._lock:
            return [dict(b) for b in self._bundles]

    def paths(self) -> List[str]:
        with self._lock:
            return list(self._paths)

    @property
    def captured(self) -> int:
        with self._lock:
            return self._seq


#: Process-wide singletons (the global_metrics/global_tracer convention).
global_gc = GcWatch()
global_flight = FlightRecorder()
global_compile_watch = CompileWatch()
global_blackbox = BlackBox()


def _slo_alert(objective: str, state: str, verdicts: dict) -> None:
    """SLO transition hook: an objective entering burning/breached is a
    black-box trigger — the bundle's attribution names the objective."""
    global_blackbox.capture(
        "slo", attribution=f"{objective}:{state}", slo=verdicts,
    )


# Wire the SLO engine's worsening-transition hook once per process: any
# module importing flight (the engine, the serve loop) arms postmortem
# capture on SLO breach without its own wiring.  capture() is re-entrancy
# guarded, so a publish inside a capture cannot recurse.
from p2p_llm_tunnel_tpu.utils.slo import global_slo as _global_slo  # noqa: E402

_global_slo.on_alert = _slo_alert
