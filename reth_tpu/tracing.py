"""Tracing/logging: layered init with per-target filters, span timing,
block-lifecycle trace propagation, a bounded flight recorder, and
Chrome-trace/OTLP span export.

Reference analogue: crates/tracing — stdout/file layers with per-layer
env filters (src/lib.rs:1-35) and the `target:` discipline (e.g.
``trie::state_root``). Built on stdlib logging; `span()` provides the
timing-span idiom used across the reference's hot paths.

Block-lifecycle layer (this repo's observability tentpole):

- **Trace context** (:class:`TraceContext`): ``trace_id`` (the block hash
  for block lifecycles) + a process-unique span id. The context lives in
  thread-local state inside ``span()`` blocks and is carried EXPLICITLY
  across queue/pool handoffs: a producer captures
  :func:`current_context`, the consumer adopts it with
  :func:`use_context` (worker threads) or attributes completed work with
  :func:`record_span` (batch dispatchers that serve many contexts at
  once, e.g. the hash service).
- **Per-block timelines**: every span/event recorded under a trace id
  lands in a bounded per-trace timeline (:func:`block_timeline`), and
  closing a :func:`trace_block` root computes the wall-budget summary
  (:func:`block_summary` / :func:`last_block_summary`) the events
  dashboard prints: ``block N total=Xms = prewarm a + exec b + root c
  (wait d, dispatch e, encode f)``.
- **Flight recorder** (:class:`FlightRecorder`): a bounded in-memory
  ring of recent spans, events, breaker/fault transitions. Snapshots to
  JSONL on circuit-breaker open, watchdog timeout, any
  ``RETH_TPU_FAULT_*`` drill firing (:func:`fault_event`), or on demand
  (:func:`flight_dump` / the ``debug_flightRecorder`` RPC) — the wedge
  postmortem a bare error line never had.
- **Exporters**: the OTLP/JSON file exporter (below) now carries
  trace/span/parent ids; :class:`ChromeTraceExporter` writes the same
  spans as Chrome trace-event JSON that Perfetto / chrome://tracing load
  directly (``--trace-blocks``).

Enablement: span *recording* is off unless ``RETH_TPU_TRACE`` is set
truthy or :func:`set_trace_enabled` ran (the ``--trace-blocks`` path);
when off, ``span()`` costs one DEBUG log call and, where JAX is loaded,
one inert profiler annotation: every span is also a
``jax.profiler.TraceAnnotation`` ``target:name``, so any profiler trace of
the process carries the program's spans on the device ops' clock.
Events (:func:`event` / :func:`fault_event`) record into the flight
recorder regardless — breaker trips and fault drills are rare and are
exactly what a postmortem needs.

Fleet layer (the cross-PROCESS half of the same machinery):

- **Wire form** (:func:`context_to_wire` / :func:`context_from_wire`):
  a compact dict ``{"t": trace_id, "s": span_id, "r": role, "p": pid}``
  carried on witness-feed frames and as a ``traceparent`` member of
  fleet-routed JSON-RPC requests. Span ids embed the originating pid in
  their high bits (:func:`span_id_pid_bits`), so ids stay globally
  unique across a fleet and a remote ``parent`` id resolves when traces
  from several processes are merged.
- **Process role** (:func:`set_process_role`): ``full`` / ``replica`` /
  ``node`` — stamped as a resource attribute on every exported span and
  as Chrome ``process_name`` metadata, so merged multi-process traces
  stay attributable.
- **Correlated dumps**: :func:`fault_event` stamps every dump with a
  :func:`new_correlation_id` + time window and notifies registered
  fault observers (:func:`add_fault_observer`) — the fleet coordinators
  (feed server / replica) fan the dump request to their peers, every
  process dumps under the SAME correlation id, and
  :func:`merge_correlated` returns the time-aligned multi-process view
  (``debug_flightRecorder`` ``action="correlated"``).
- **Stitching** (:func:`stitch_chrome_traces`): merge exported Chrome
  traces from several processes and report distinct pids + any
  unresolved cross-process parent ids — the bench/chaos acceptance
  check that one user read really is ONE trace.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import logging
import os
import sys
import tempfile
import threading
import time
from collections import OrderedDict, deque
from pathlib import Path


def init_tracing(
    stdout_level: str | None = None,
    file_path: str | Path | None = None,
    file_level: str = "DEBUG",
    filters: str | None = None,
) -> None:
    """Install stdout (+ optional file) handlers.

    ``filters``: comma-separated ``target=LEVEL`` pairs (the RUST_LOG
    analogue), e.g. ``"reth_tpu.trie=DEBUG,reth_tpu.engine=INFO"``; also
    read from the RETH_TPU_LOG env var.
    """
    root = logging.getLogger("reth_tpu")
    root.setLevel(logging.DEBUG)
    root.handlers.clear()
    fmt = logging.Formatter(
        "%(asctime)s %(levelname)-5s %(name)s: %(message)s", "%H:%M:%S"
    )
    out = logging.StreamHandler(sys.stdout)
    out.setLevel((stdout_level or "INFO").upper())
    out.setFormatter(fmt)
    root.addHandler(out)
    if file_path:
        fh = logging.FileHandler(file_path)
        fh.setLevel(file_level.upper())
        fh.setFormatter(fmt)
        root.addHandler(fh)
    spec = filters if filters is not None else os.environ.get("RETH_TPU_LOG", "")
    for pair in filter(None, spec.split(",")):
        target, _, level = pair.partition("=")
        logging.getLogger(target.strip()).setLevel((level or "DEBUG").upper())


def tracer(target: str) -> logging.Logger:
    """Logger for a target (``trie.state_root`` style)."""
    return logging.getLogger(f"reth_tpu.{target}")


# -- trace context ------------------------------------------------------------

_FALSY = ("", "0", "false", "off", "no")


def _env_enabled() -> bool:
    return os.environ.get("RETH_TPU_TRACE", "").lower() not in _FALSY


_TRACE_ON = _env_enabled()
_tls = threading.local()
_span_ids = itertools.count(1)

# span ids are globally unique across a FLEET: the low 40 bits count,
# the high bits carry this process's pid — a remote parent id exported
# from another process can never collide with a local span id, so
# cross-process parent references resolve in merged Chrome/OTLP traces
_SPAN_PID_SHIFT = 40
_SPAN_PID_BITS = os.getpid() & 0x3FFFFF


def _new_span_id() -> int:
    return (_SPAN_PID_BITS << _SPAN_PID_SHIFT) | next(_span_ids)


def span_id_pid_bits(span_id: int) -> int:
    """The pid bits embedded in a span id (which process minted it) —
    how stitch checks tell a cross-process parent from a local one."""
    return span_id >> _SPAN_PID_SHIFT


# process role for multi-process attribution (full | replica | node):
# rides the wire form, OTLP resource attributes, and Chrome process
# metadata so merged fleet traces stay tellable-apart after export
_ROLE = os.environ.get("RETH_TPU_ROLE", "") or "node"


def set_process_role(role: str) -> None:
    global _ROLE
    _ROLE = role


def process_role() -> str:
    return _ROLE


class TraceContext:
    """A propagated trace position: ``trace_id`` (block hash hex for
    block lifecycles) + the current span id (None at the trace root)."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str | None, span_id: int | None = None):
        self.trace_id = trace_id
        self.span_id = span_id

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"TraceContext({self.trace_id!r}, span={self.span_id})"


def context_to_wire(ctx: TraceContext | None = None) -> dict | None:
    """Compact wire form of a trace position for cross-process handoffs
    (witness-feed frames, fleet-routed JSON-RPC ``traceparent``):
    ``{"t": trace_id, "s": span_id, "r": role, "p": pid}``. ``ctx``
    defaults to the calling thread's current context; None (no trace)
    encodes to None so untraced traffic carries zero extra bytes. A
    span-only context (a routed READ has no block trace id) still
    encodes — the remote spans stitch by parent span id even when no
    named trace exists."""
    if ctx is None:
        ctx = current_context()
    if ctx is None or (ctx.trace_id is None and ctx.span_id is None):
        return None
    return {"t": ctx.trace_id, "s": ctx.span_id, "r": _ROLE,
            "p": os.getpid()}


def context_from_wire(wire) -> TraceContext | None:
    """Decode a wire-form dict back into an adoptable context (the
    consumer half: ``use_context(context_from_wire(frame["tp"]))``).
    Tolerates None/garbage — a malformed traceparent must never fail
    the request it rode in on."""
    if not isinstance(wire, dict):
        return None
    trace = wire.get("t")
    if trace is not None and not (isinstance(trace, str) and trace):
        return None
    span = wire.get("s")
    if span is not None and not isinstance(span, int):
        return None
    if trace is None and span is None:
        return None
    return TraceContext(trace, span)


def set_trace_enabled(on: bool) -> None:
    """Master switch for span recording (``--trace-blocks`` /
    ``RETH_TPU_TRACE``). Off = ``span()`` reverts to its log-only cost."""
    global _TRACE_ON
    _TRACE_ON = bool(on)


def trace_enabled() -> bool:
    return _TRACE_ON


def current_context() -> TraceContext | None:
    """The calling thread's trace position (None outside any span, or
    with tracing disabled). Capture this BEFORE handing work to a queue
    or pool; the consumer adopts it with :func:`use_context`."""
    return getattr(_tls, "ctx", None)


@contextlib.contextmanager
def use_context(ctx: TraceContext | None):
    """Adopt a propagated context in a worker thread for the duration of
    the block — the consumer half of every queue/pool handoff."""
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = ctx
    try:
        yield ctx
    finally:
        _tls.ctx = prev


@contextlib.contextmanager
def span(target: str, name: str, level: int = logging.DEBUG, **fields):
    """Timed span: logs entry fields + exit duration (tracing-span idiom).

    With tracing enabled the span joins the current thread's trace
    (parent/child ids), records into the flight recorder + per-trace
    timeline, and exports to the installed OTLP/Chrome exporters.

    Enabled or not, the span is also a ``jax.profiler.TraceAnnotation``
    named ``target:name``: inside a profiler session it lands in the
    ``.xplane.pb`` on the device ops' clock, so an idle device can be
    attributed to what the host was doing."""
    log = tracer(target)
    # looked up, not imported: this module stays importable without jax,
    # and a process that never loaded it has no device trace to share a
    # clock with
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)  # None while jax half-imported
    annotation = None
    if profiler is not None:
        annotation = profiler.TraceAnnotation(f"{target}:{name}")
        annotation.__enter__()
    t0 = time.time()
    parent = None
    ctx = None
    if _TRACE_ON:
        parent = getattr(_tls, "ctx", None)
        ctx = TraceContext(parent.trace_id if parent is not None else None,
                           _new_span_id())
        _tls.ctx = ctx
    err = None
    try:
        yield ctx
    except BaseException as e:
        err = type(e).__name__
        raise
    finally:
        dt = time.time() - t0
        if annotation is not None:
            annotation.__exit__(None, None, None)
        if ctx is not None:
            _tls.ctx = parent
        extra = " ".join(f"{k}={v}" for k, v in fields.items())
        log.log(level, "%s %s took %.3fms", name, extra, dt * 1e3)
        if _otlp is not None:
            _otlp.export(target, name, t0, dt, fields, err,
                         ctx=ctx, parent=parent)
        if ctx is not None:
            _record({
                "kind": "span", "target": target, "name": name,
                "ts": t0, "dur_ms": round(dt * 1e3, 3),
                "trace": ctx.trace_id, "span": ctx.span_id,
                "parent": parent.span_id if parent is not None else None,
                "thread": threading.current_thread().name,
                "fields": fields, "error": err,
            })


def record_span(target: str, name: str, start: float, duration: float, *,
                ctx: TraceContext | None = None, fields: dict | None = None,
                error: str | None = None) -> None:
    """Record an already-timed span under ``ctx`` — the attribution path
    for batch dispatchers that complete work for MANY contexts at once
    (hash-service requests, proof shards): the producer captured the
    context at submit time, the completion attributes the wall to it."""
    if not _TRACE_ON:
        return
    rec = {
        "kind": "span", "target": target, "name": name,
        "ts": start, "dur_ms": round(duration * 1e3, 3),
        "trace": ctx.trace_id if ctx is not None else None,
        "span": _new_span_id(),
        "parent": ctx.span_id if ctx is not None else None,
        "thread": threading.current_thread().name,
        "fields": fields or {}, "error": error,
    }
    _record(rec)


def event(target: str, name: str, **fields) -> None:
    """Instant event (breaker transition, probe outcome, fault firing).
    Always lands in the flight recorder — these are the rare records a
    postmortem is made of — and in the current trace's timeline when
    span recording is on."""
    ctx = getattr(_tls, "ctx", None) if _TRACE_ON else None
    _record({
        "kind": "event", "target": target, "name": name,
        "ts": time.time(), "dur_ms": 0.0,
        "trace": ctx.trace_id if ctx is not None else None,
        "span": None,
        "parent": ctx.span_id if ctx is not None else None,
        "thread": threading.current_thread().name,
        "fields": fields, "error": None,
    }, always=True)


# -- per-block timelines ------------------------------------------------------

_TL_LOCK = threading.Lock()
_TIMELINES: OrderedDict[str, list] = OrderedDict()
_SUMMARIES: OrderedDict[str, dict] = OrderedDict()
_MAX_TRACES = 64
_MAX_TIMELINE_RECORDS = 8192
_last_summary: dict | None = None


@contextlib.contextmanager
def trace_block(trace_id: str, name: str = "block",
                target: str = "engine::block", **fields):
    """Root span of one block lifecycle: ``trace_id`` (the block hash
    hex) seeds every child span on this thread and every explicitly
    propagated context; closing computes the wall-budget summary."""
    if not _TRACE_ON:
        yield None
        return
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = TraceContext(trace_id, None)  # trace seed: root has no parent
    with _TL_LOCK:
        _TIMELINES.setdefault(trace_id, [])
        _TIMELINES.move_to_end(trace_id)
        while len(_TIMELINES) > _MAX_TRACES:
            dead, _ = _TIMELINES.popitem(last=False)
            _SUMMARIES.pop(dead, None)
    try:
        with span(target, name, **fields) as ctx:
            yield ctx
    finally:
        _tls.ctx = prev
        _finalize_block(trace_id)


def _record(rec: dict, always: bool = False) -> None:
    if _TRACE_ON or always:
        _RECORDER.record(rec)
    if _chrome is not None and (_TRACE_ON or always):
        _chrome.export(rec)
    trace = rec.get("trace")
    if trace is None:
        return
    with _TL_LOCK:
        tl = _TIMELINES.get(trace)
        if tl is not None and len(tl) < _MAX_TIMELINE_RECORDS:
            tl.append(rec)


def ensure_timeline(trace_id: str) -> None:
    """Pre-register a trace timeline so spans recorded BEFORE the block's
    root ``trace_block`` opens still land in it — cross-block speculation
    executes N+1 while N commits, ahead of N+1's own lifecycle."""
    if not _TRACE_ON:
        return
    with _TL_LOCK:
        _TIMELINES.setdefault(trace_id, [])
        _TIMELINES.move_to_end(trace_id)
        while len(_TIMELINES) > _MAX_TRACES:
            dead, _ = _TIMELINES.popitem(last=False)
            _SUMMARIES.pop(dead, None)


def block_timeline(trace_id: str) -> list[dict] | None:
    """All records of one trace (block), oldest first; None if unknown."""
    with _TL_LOCK:
        tl = _TIMELINES.get(trace_id)
        return list(tl) if tl is not None else None


def recent_traces() -> list[str]:
    """Known trace ids, oldest first."""
    with _TL_LOCK:
        return list(_TIMELINES)


def _sum_field(records, names, field) -> float:
    return sum(float(r["fields"].get(field, 0.0)) for r in records
               if r["name"] in names)


def _summarize(trace_id: str, records: list[dict]) -> dict | None:
    root = next((r for r in records
                 if r["kind"] == "span" and r["parent"] is None), None)
    if root is None:
        return None

    def dur_of(name: str) -> float:
        return sum(r["dur_ms"] for r in records
                   if r["kind"] == "span" and r["name"] == name)

    spans = [r for r in records if r["kind"] == "span"]
    # accounted wall: union of direct-child intervals over the root span
    children = sorted(((r["ts"], r["ts"] + r["dur_ms"] / 1e3)
                       for r in spans if r["parent"] == root["span"]))
    covered, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in children:
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                covered += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        covered += cur_hi - cur_lo
    total_ms = root["dur_ms"]
    summary = {
        "trace": trace_id,
        "number": root["fields"].get("number"),
        # closing wall-clock time: the health engine's block-wall SLO rule
        # windows summaries by when the block finished
        "ts": root["ts"] + total_ms / 1e3,
        "total_ms": total_ms,
        "prewarm_ms": round(dur_of("prewarm"), 3),
        # an adopted speculation ran its execute leg as speculate.exec
        # inside the parent's commit window; count it as the exec wall
        "exec_ms": round(dur_of("execute") or dur_of("speculate.exec"), 3),
        "root_ms": round(dur_of("state_root"), 3),
        # hash-service attribution: queue-wait vs device dispatch (with no
        # service the direct hash.dispatch spans carry the dispatch wall)
        "wait_ms": round(_sum_field(records, ("hashsvc.request",), "wait_ms"), 3),
        "dispatch_ms": round(
            _sum_field(records, ("hashsvc.request",), "service_ms")
            if any(r["name"] == "hashsvc.request" for r in records)
            else dur_of("hash.dispatch"), 3),
        "encode_ms": round(dur_of("sparse.encode"), 3),
        "spans": len(spans),
        "coverage": round(covered * 1e3 / total_ms, 4) if total_ms else 1.0,
    }
    return summary


def _finalize_block(trace_id: str) -> None:
    global _last_summary
    records = block_timeline(trace_id)
    if not records:
        return
    summary = _summarize(trace_id, records)
    if summary is None:
        return
    with _TL_LOCK:
        _SUMMARIES[trace_id] = summary
        while len(_SUMMARIES) > _MAX_TRACES:
            _SUMMARIES.popitem(last=False)
    _last_summary = summary


def block_summary(trace_id: str) -> dict | None:
    """Wall-budget summary of one closed block trace."""
    with _TL_LOCK:
        s = _SUMMARIES.get(trace_id)
    if s is not None:
        return s
    records = block_timeline(trace_id)
    return _summarize(trace_id, records) if records else None


def last_block_summary() -> dict | None:
    """The most recently closed block's wall budget (events dashboard)."""
    return _last_summary


def recent_block_summaries(n: int | None = None) -> list[dict]:
    """Closed-block wall budgets, oldest first (bounded by the timeline
    ring) — the health engine's block-import SLO rule averages these over
    its evaluation window."""
    with _TL_LOCK:
        out = list(_SUMMARIES.values())
    return out[-n:] if n else out


def format_wall_budget(s: dict) -> str:
    """The one-line per-block budget operators read:
    ``block N total=Xms = prewarm a + exec b + root c (wait d, dispatch
    e, encode f)``."""
    return (f"block {s.get('number', '?')} total={s['total_ms']:.1f}ms = "
            f"prewarm {s['prewarm_ms']:.1f} + exec {s['exec_ms']:.1f} + "
            f"root {s['root_ms']:.1f} (wait {s['wait_ms']:.1f}, "
            f"dispatch {s['dispatch_ms']:.1f}, encode {s['encode_ms']:.1f})")


# -- flight recorder ----------------------------------------------------------


class FlightRecorder:
    """Bounded ring of recent spans/events/fault transitions, snapshotted
    to JSONL when something goes wrong (breaker open, watchdog timeout,
    a RETH_TPU_FAULT_* drill firing) or on demand."""

    def __init__(self, capacity: int = 4096, directory: str | Path | None = None):
        self._lock = threading.Lock()
        self._buf: deque = deque(maxlen=capacity)
        self.directory = directory
        self.dumps: list[str] = []  # paths written, oldest first
        self.recorded = 0
        self.last_correlation_id: str | None = None

    def record(self, rec: dict) -> None:
        with self._lock:
            self._buf.append(rec)
            self.recorded += 1

    def snapshot(self, n: int | None = None) -> list[dict]:
        with self._lock:
            out = list(self._buf)
        return out[-n:] if n else out

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()

    def _dir(self) -> Path:
        d = (self.directory or os.environ.get("RETH_TPU_FLIGHT_DIR")
             or Path(tempfile.gettempdir()) / "reth_tpu_flight")
        d = Path(d)
        d.mkdir(parents=True, exist_ok=True)
        return d

    def dump(self, reason: str, path: str | Path | None = None, *,
             correlation_id: str | None = None,
             window: tuple | list | None = None) -> str | None:
        """Write the ring (oldest first) as JSONL: one header line
        ``{"kind": "flight_snapshot", "reason", "ts", "records", "pid",
        "role", "correlation_id", "window"}`` then one line per record.
        ``correlation_id`` ties this dump to the fleet-wide set written
        for one incident; ``window`` (``[t0, t1]`` wall-clock seconds)
        filters the ring to the incident's period so a peer's dump is
        time-aligned with the initiator's. Returns the path, or None on
        an empty ring. Never raises — a diagnostics failure must not
        fail the caller."""
        try:
            records = self.snapshot()
            if window:
                t0, t1 = float(window[0]), float(window[1])
                records = [r for r in records
                           if t0 - 1.0 <= r.get("ts", 0.0) <= t1 + 1.0]
            if not records:
                return None
            if path is None:
                safe = "".join(c if c.isalnum() or c in "-_" else "_"
                               for c in reason)[:60]
                path = self._dir() / (
                    f"flight-{safe}-{int(time.time() * 1e3)}-"
                    f"{os.getpid()}.jsonl")
            path = Path(path)
            with open(path, "w") as f:
                f.write(json.dumps({
                    "kind": "flight_snapshot", "reason": reason,
                    "ts": time.time(), "records": len(records),
                    "pid": os.getpid(), "role": _ROLE,
                    "correlation_id": correlation_id,
                    "window": list(window) if window else None}) + "\n")
                for rec in records:
                    f.write(json.dumps(rec, default=str) + "\n")
            self.dumps.append(str(path))
            if correlation_id:
                self.last_correlation_id = correlation_id
            return str(path)
        except Exception:  # noqa: BLE001 — diagnostics only
            return None


_RECORDER = FlightRecorder()


def flight_recorder() -> FlightRecorder:
    return _RECORDER


def flight_snapshot(n: int | None = None) -> list[dict]:
    return _RECORDER.snapshot(n)


def flight_dump(reason: str, path: str | Path | None = None, *,
                correlation_id: str | None = None,
                window: tuple | list | None = None) -> str | None:
    """Snapshot the flight recorder to JSONL now (see the triggers in the
    module docstring)."""
    return _RECORDER.dump(reason, path, correlation_id=correlation_id,
                          window=window)


def load_flight_dump(path: str | Path) -> tuple[dict, list[dict]]:
    """Parse a flight-recorder JSONL dump -> (header, records). Torn
    trailing lines (a killed process mid-write) are discarded."""
    lines = Path(path).read_text().splitlines()
    header = json.loads(lines[0])
    records = []
    for line in lines[1:]:
        try:
            records.append(json.loads(line))
        except json.JSONDecodeError:  # torn tail: the process died here
            break
    return header, records


# -- correlated dumps ---------------------------------------------------------

_corr_counter = itertools.count(1)
# the incident window a correlated dump covers: the initiator stamps
# [now - CORRELATION_WINDOW_S, now + slack] so every peer's dump is
# filtered to the same period
CORRELATION_WINDOW_S = 30.0


def new_correlation_id() -> str:
    """Fleet-unique incident id stamped on every dump of one correlated
    set: wall-ms + pid + a per-process counter."""
    return (f"{int(time.time() * 1e3):x}-{os.getpid():x}-"
            f"{next(_corr_counter):x}")


def correlated_dumps(correlation_id: str,
                     directory: str | Path | None = None) -> list[tuple]:
    """Every flight dump under ``directory`` (default: this process's
    flight dir, which a fleet shares via RETH_TPU_FLIGHT_DIR) whose
    header carries ``correlation_id`` -> [(header, records), ...]."""
    d = Path(directory) if directory is not None else _RECORDER._dir()
    out = []
    for path in sorted(d.glob("flight-*.jsonl")):
        try:
            header, records = load_flight_dump(path)
        except (OSError, json.JSONDecodeError, IndexError):
            continue
        if header.get("correlation_id") == correlation_id:
            header = dict(header, path=str(path))
            out.append((header, records))
    return out


def merge_correlated(correlation_id: str | None = None,
                     directory: str | Path | None = None) -> dict:
    """The merged multi-process view of one correlated incident: every
    dump sharing the correlation id, records annotated with their
    originating pid/role and time-ordered — what ``debug_flightRecorder``
    ``action="correlated"`` returns. ``correlation_id`` defaults to the
    most recent one this process stamped."""
    cid = correlation_id or _RECORDER.last_correlation_id
    if cid is None:
        return {"correlation_id": None, "dumps": [], "pids": [],
                "records": []}
    dumps = correlated_dumps(cid, directory)
    records = []
    for header, recs in dumps:
        pid, role = header.get("pid"), header.get("role")
        for r in recs:
            records.append(dict(r, pid=pid, role=role))
    records.sort(key=lambda r: r.get("ts", 0.0))
    return {
        "correlation_id": cid,
        "dumps": [h["path"] for h, _ in dumps],
        "pids": sorted({h.get("pid") for h, _ in dumps
                        if h.get("pid") is not None}),
        "roles": sorted({str(h.get("role")) for h, _ in dumps}),
        "records": records,
    }


# fault observers: the fleet coordinators hang here — the feed server
# (full node) fans a dump request to every replica, a replica notifies
# the full node upstream over its feed socket. Called AFTER the local
# dump with (reason, correlation_id, window); observers must never
# raise into the faulting path.
_observer_lock = threading.Lock()
_fault_observers: list = []


def add_fault_observer(fn) -> None:
    with _observer_lock:
        if fn not in _fault_observers:
            _fault_observers.append(fn)


def remove_fault_observer(fn) -> None:
    with _observer_lock:
        if fn in _fault_observers:
            _fault_observers.remove(fn)


_fault_lock = threading.Lock()
_fault_last_dump: dict[str, float] = {}
FAULT_DUMP_INTERVAL_S = 5.0


def reset_fault_dump_limits() -> None:
    """Forget per-drill dump rate limits (tests / operator reset)."""
    with _fault_lock:
        _fault_last_dump.clear()


def fault_event(drill: str, target: str = "fault", **fields) -> str | None:
    """A RETH_TPU_FAULT_* drill (or real failure trigger) fired: record
    the event and snapshot the flight recorder, rate-limited per drill
    name so wedge-every-dispatch drills don't spray the disk. The dump
    is stamped with a fresh correlation id + incident window and every
    registered fault observer is notified so fleet peers dump under the
    SAME id. Returns the dump path when one was written."""
    event(target, drill, **fields)
    now = time.monotonic()
    with _fault_lock:
        last = _fault_last_dump.get(drill, 0.0)
        if now - last < FAULT_DUMP_INTERVAL_S:
            return None
        _fault_last_dump[drill] = now
    cid = new_correlation_id()
    wall = time.time()
    window = (wall - CORRELATION_WINDOW_S, wall + 5.0)
    path = flight_dump(drill, correlation_id=cid, window=window)
    with _observer_lock:
        observers = list(_fault_observers)
    for obs in observers:
        try:
            obs(drill, cid, window)
        except Exception:  # noqa: BLE001 — diagnostics only
            pass
    return path


# -- OTLP export (reference crates/tracing-otlp) ------------------------------
# The reference ships spans to an OTLP collector endpoint; this environment
# has no egress, so the exporter writes the SAME span model (resource +
# scope + span with name/attributes/start/end/status) as OTLP/JSON lines to
# a file a collector can tail — the transport is the only difference.

_otlp = None


def process_resource_attributes(replica_id: str | None = None) -> dict:
    """Resource attributes identifying THIS process in a merged fleet
    trace: role, pid, and the node's build identity
    (``reth_tpu_build_info`` fields) — stamped on every exported span so
    multi-process traces stay distinguishable after export."""
    attrs = {"service.role": _ROLE, "process.pid": os.getpid()}
    if replica_id:
        attrs["service.replica_id"] = replica_id
    try:
        from .metrics import build_info

        for k, v in build_info().items():
            attrs[f"build.{k}"] = v
    except Exception:  # noqa: BLE001 — identity is best-effort
        pass
    return attrs


class OtlpFileExporter:
    def __init__(self, path: str | Path, service_name: str = "reth-tpu"):
        self._lock = threading.Lock()
        self._f = open(path, "a", buffering=1)
        self.service_name = service_name
        self.exported = 0
        self._resource: list | None = None  # built lazily: role may be
        # set after init but before the first span exports

    def _resource_attrs(self) -> list:
        if self._resource is None:
            attrs = {"service.name": self.service_name}
            attrs.update(process_resource_attributes())
            self._resource = [
                {"key": k, "value": {"stringValue": str(v)}}
                for k, v in attrs.items()
            ]
        return self._resource

    def export(self, target: str, name: str, start: float, duration: float,
               fields: dict, error: str | None,
               ctx: TraceContext | None = None,
               parent: TraceContext | None = None) -> None:
        sp = {
            "name": name,
            "startTimeUnixNano": str(int(start * 1e9)),
            "endTimeUnixNano": str(int((start + duration) * 1e9)),
            "attributes": [
                {"key": k, "value": {"stringValue": str(v)}}
                for k, v in fields.items()
            ],
            "status": ({"code": 2, "message": error} if error
                       else {"code": 1}),
        }
        if ctx is not None:
            if ctx.trace_id is not None:
                sp["traceId"] = str(ctx.trace_id)
            sp["spanId"] = format(ctx.span_id or 0, "016x")
            if parent is not None and parent.span_id is not None:
                sp["parentSpanId"] = format(parent.span_id, "016x")
        span_rec = {
            "resource": {"attributes": self._resource_attrs()},
            "scopeSpans": [{
                "scope": {"name": f"reth_tpu.{target}"},
                "spans": [sp],
            }],
        }
        with self._lock:
            self._f.write(json.dumps(span_rec) + "\n")
            self.exported += 1

    def close(self) -> None:
        with self._lock:
            self._f.close()


def init_otlp(path: str | Path, service_name: str = "reth-tpu") -> OtlpFileExporter:
    """Install the OTLP/JSON file exporter for every span()."""
    global _otlp
    _otlp = OtlpFileExporter(path, service_name)
    return _otlp


def shutdown_otlp() -> None:
    global _otlp
    if _otlp is not None:
        _otlp.close()
        _otlp = None


# -- Chrome trace-event export ------------------------------------------------
# The format chrome://tracing and Perfetto's JSON importer load directly:
# one "X" (complete) event per span, instant events as "i". Written one
# event per line so the file doubles as JSON-lines for tooling; close()
# terminates it into a fully valid JSON array.

_chrome = None


class ChromeTraceExporter:
    """Spans/events as Chrome trace-event JSON (``--trace-blocks``)."""

    def __init__(self, path: str | Path):
        self._lock = threading.Lock()
        self.path = str(path)
        self._f = open(path, "w", buffering=1)
        self._f.write("[\n")
        self._tids: dict[str, int] = {}
        self.exported = 0
        self._named = False  # process metadata emitted?

    def _tid(self, thread_name: str) -> int:
        # caller holds the lock. Distinct pid/tid metadata events per
        # process so MERGED multi-process traces show named, separate
        # process/thread tracks instead of anonymous numeric ids.
        if not self._named:
            self._named = True
            self._f.write(json.dumps(
                {"name": "process_name", "ph": "M", "pid": os.getpid(),
                 "tid": 0, "args": {"name": f"{_ROLE}-{os.getpid()}"}})
                + ",\n")
        tid = self._tids.get(thread_name)
        if tid is None:
            tid = self._tids[thread_name] = len(self._tids) + 1
            self._f.write(json.dumps(
                {"name": "thread_name", "ph": "M", "pid": os.getpid(),
                 "tid": tid, "args": {"name": thread_name}}) + ",\n")
        return tid

    def export(self, rec: dict) -> None:
        args = {k: str(v) for k, v in rec.get("fields", {}).items()}
        if rec.get("trace"):
            args["trace_id"] = rec["trace"]
        if rec.get("span") is not None:
            args["span_id"] = rec["span"]
        if rec.get("parent") is not None:
            args["parent_id"] = rec["parent"]
        if rec.get("error"):
            args["error"] = rec["error"]
        ev = {
            "name": rec["name"],
            "cat": rec["target"],
            "ph": "X" if rec["kind"] == "span" else "i",
            "ts": round(rec["ts"] * 1e6, 1),
            "pid": os.getpid(),
            "args": args,
        }
        if rec["kind"] == "span":
            ev["dur"] = round(rec["dur_ms"] * 1e3, 1)
        else:
            ev["s"] = "p"  # process-scoped instant
        with self._lock:
            ev["tid"] = self._tid(rec.get("thread", "main"))
            self._f.write(json.dumps(ev) + ",\n")
            self.exported += 1

    def close(self) -> None:
        with self._lock:
            if not self._f.closed:
                # terminate the array so the file is strictly valid JSON
                self._f.write(json.dumps(
                    {"name": "trace_end", "ph": "i", "ts": time.time() * 1e6,
                     "pid": os.getpid(), "tid": 0, "s": "g", "args": {}})
                    + "\n]\n")
                self._f.close()


def init_chrome_trace(path: str | Path) -> ChromeTraceExporter:
    """Install the Chrome trace-event exporter for every recorded span."""
    global _chrome
    _chrome = ChromeTraceExporter(path)
    return _chrome


def shutdown_chrome_trace() -> None:
    global _chrome
    if _chrome is not None:
        _chrome.close()
        _chrome = None


def read_chrome_trace(path: str | Path) -> list[dict]:
    """Tolerant loader for a (possibly still-open) Chrome trace file:
    each line holds one event object (JSON-lines view of the array).
    Undecodable lines (a SIGKILLed process torn mid-write) are skipped —
    postmortem tooling must read what the dead process DID flush."""
    out = []
    for line in Path(path).read_text().splitlines():
        line = line.strip().rstrip(",")
        if line in ("", "[", "]"):
            continue
        try:
            out.append(json.loads(line))
        except json.JSONDecodeError:
            continue
    return out


def stitch_chrome_traces(paths) -> dict:
    """Merge Chrome trace files exported by SEVERAL processes and check
    the cross-process stitching contract: every ``parent_id`` minted by
    another process (its pid bits differ from the referencing event's)
    must resolve to an exported span somewhere in the merged set.

    Returns ``{"events", "pids", "span_ids", "unresolved",
    "unresolved_cross", "stitched"}`` — ``stitched`` is True when at
    least one cross-process parent reference exists AND all of them
    resolve (a fleet whose traces never cross a process boundary is NOT
    stitched, it is merely concatenated)."""
    events: list[dict] = []
    for p in paths:
        try:
            events.extend(read_chrome_trace(p))
        except OSError:
            continue
    span_ids = set()
    for e in events:
        sid = (e.get("args") or {}).get("span_id")
        if isinstance(sid, int):
            span_ids.add(sid)
    # pids that contributed SPANS — a process whose file holds only
    # metadata events did not span the trace
    pids = {e["pid"] for e in events
            if "pid" in e and e.get("ph") == "X"}
    unresolved, unresolved_cross, cross_refs = [], [], 0
    for e in events:
        parent = (e.get("args") or {}).get("parent_id")
        if not isinstance(parent, int):
            continue
        cross = span_id_pid_bits(parent) != (e.get("pid", 0) & 0x3FFFFF)
        if cross:
            cross_refs += 1
        if parent not in span_ids:
            unresolved.append(parent)
            if cross:
                unresolved_cross.append(parent)
    return {
        "events": events,
        "pids": sorted(pids),
        "span_ids": span_ids,
        "unresolved": unresolved,
        "unresolved_cross": unresolved_cross,
        "cross_refs": cross_refs,
        "stitched": cross_refs > 0 and not unresolved_cross,
    }


def init_block_tracing(chrome_path: str | Path | None = None,
                       otlp_path: str | Path | None = None,
                       flight_dir: str | Path | None = None,
                       capacity: int | None = None) -> None:
    """The ``--trace-blocks`` bundle: install the requested exporters,
    point flight-recorder dumps at a directory, and THEN enable span
    recording — exporters must exist before the first span can close,
    or a busy worker thread (the feed's witness generator on a 1-core
    host) slips whole spans into the gap: recorded in the ring and
    adopted by replicas, but missing from the exported trace."""
    if chrome_path is not None:
        init_chrome_trace(chrome_path)
    if otlp_path is not None:
        init_otlp(otlp_path)
    if flight_dir is not None:
        _RECORDER.directory = flight_dir
    if capacity is not None and capacity != _RECORDER._buf.maxlen:
        with _RECORDER._lock:
            _RECORDER._buf = deque(_RECORDER._buf, maxlen=capacity)
    set_trace_enabled(True)


def shutdown_block_tracing() -> None:
    shutdown_chrome_trace()
    shutdown_otlp()
    set_trace_enabled(_env_enabled())
