"""Distributed request tracing: sampled span context as a wire citizen.

TPU-native counterpart of the reference tracing layer (ref:
python/ray/util/tracing/tracing_helper.py:36-60 — there OTel span context
is injected into task specs by decorator wrappers and child spans open
around execution), grown the Dapper way (Sigelman et al., 2010): the
context ``(trace_id_128, span_id_64, sampled)`` rides the wire ITSELF —
packed fast-lane records and node-tunnel frames carry an optional
25-byte trace leg (core/fastpath.py, flag ``TRACED``) — so causality is
cheap enough to leave on in production. Spans use OTel-shaped ids
(128-bit trace, 64-bit span), ride the task-event pipeline into the GCS
trace assembler (``state.get_trace`` / ``state.list_traces``) and the
chrome timeline.

Beside request spans stands :class:`phase`: a timed *host phase of a
loop* (the LLM engine's admit / dispatch / sync / emit steps), always
summed into one registry histogram and, while a ``jax.profiler`` trace
is on, mirrored as a ``TraceAnnotation`` so that it lands in the same
``.xplane.pb`` — and on the same clock — as the device's own lines.

Before a loop's first step stands :class:`stage`: a ``phase`` of bring-up
(``STAGES``: the backend's start, weights, pools, a train group's placement
and set-up), summed into ``rt_bringup_seconds``; :func:`build_duration`
adds what jax reports of every program it builds, and says of each whether
it was compiled or read from the persistent compile cache.

Inside the jitted programs stands :func:`part`: the one vocabulary of named
scopes (``PARTS``) by which a serve program says which part of a layer each
of its instructions belongs to. The profiler drops a scope from the device's
events, but the compiled program keeps it (``op_name`` in its text), so
:func:`instruction_parts` reduces that text to a table a reader joins with
the trace's events by instruction name and result shape.

Enable with ``Config.tracing_enabled`` (env ``RT_TRACING_ENABLED=1``).
Sampling is HEAD-BASED (``Config.trace_sample_rate``): the decision is
made once where a trace starts (the serve router's root, a driver
``.remote()`` with no active context) and carried in the wire leg;
an unsampled request pays one contextvar read and one branch — the
chaos-gate cost model — and ships NO trace bytes.

Propagation model: a contextvar holds the active (trace_id, span_id).
Submitting a task captures it into the spec (``trace_ctx``) or the
packed record's trace leg; executing a task opens a child span and
activates it for the duration of the user function, so nested
``.remote()`` calls chain parent -> child across any number of
processes and transports (shm ring, node tunnel, RPC).

Span ids come from a per-process random prefix + counter — one urandom
syscall per process, not per span (the per-call ``os.urandom`` measured
~288µs under the syscall-intercepting sandbox, the same hot-path cost
PR 8 and PR 11 evicted from task and promise ids).
"""

from __future__ import annotations

import asyncio
import contextvars
import itertools
import os
import re
import struct
import sys
import threading as _threading
import time

from ray_tpu.config import get_config
from ray_tpu.utils import metrics

_ctx: contextvars.ContextVar[tuple[str, str] | None] = contextvars.ContextVar(
    "rt_trace_ctx", default=None)

# Span clock: durations come from perf_counter_ns (monotonic, ns
# resolution — time.time() collapses sub-ms spans to zero on coarse
# clocks and a wall-clock step mid-span would yield a NEGATIVE
# duration); one wall anchor captured at import reconstructs absolute
# start/end times for the timeline.
_ANCHOR_PERF_NS = time.perf_counter_ns()
_ANCHOR_WALL_NS = time.time_ns()


def _wall_s(t_perf_ns: int) -> float:
    return (_ANCHOR_WALL_NS + (t_perf_ns - _ANCHOR_PERF_NS)) / 1e9


def enabled() -> bool:
    return get_config().tracing_enabled


# ------------------------------------------------------------------ id gen
# Prefix + counter, the TaskID.generate scheme (utils/ids.py): ONE
# urandom per process; the counter's next() is a single GIL-atomic C
# step so user threads and the loop thread can mint ids concurrently.
# 128/64-bit OTel shapes are kept: trace ids are 9 random bytes + a
# 7-byte counter, span ids 4 random bytes + 4-byte counter.
_gen_lock = _threading.Lock()
_trace_prefix: bytes = b""
_trace_counter = None
_span_prefix: bytes = b""
_span_counter = None


def _gen_trace_id() -> str:
    global _trace_prefix, _trace_counter
    if _trace_counter is None:
        with _gen_lock:
            if _trace_counter is None:
                _trace_prefix = os.urandom(9)  # raylint: disable=RT021 -- one-time prefix init, counter per call
                _trace_counter = itertools.count()
    n = next(_trace_counter) % (1 << 56)
    return (_trace_prefix + n.to_bytes(7, "little")).hex()


def _gen_span_id() -> str:
    global _span_prefix, _span_counter
    if _span_counter is None:
        with _gen_lock:
            if _span_counter is None:
                _span_prefix = os.urandom(4)  # raylint: disable=RT021 -- one-time prefix init, counter per call
                _span_counter = itertools.count()
    n = next(_span_counter) % (1 << 32)
    return (_span_prefix + n.to_bytes(4, "little")).hex()


def _reset_prefixes() -> None:
    global _trace_prefix, _trace_counter, _span_prefix, _span_counter
    with _gen_lock:
        _trace_prefix = b""
        _trace_counter = None
        _span_prefix = b""
        _span_counter = None


if hasattr(os, "register_at_fork"):  # a fork child must mint fresh ids
    os.register_at_fork(after_in_child=_reset_prefixes)


# ---------------------------------------------------------------- sampling
# Head-based, deterministic: every Nth root is sampled (N derived from
# trace_sample_rate), so the unsampled path is one counter bump + one
# compare — no RNG, no syscall. The decision is carried in the wire
# leg's sampled bit; children never re-decide.
_sample_counter = itertools.count()
_stride_cache: tuple[float, int] | None = None


def sample() -> bool:
    """One head-sampling decision (call only where a trace would START)."""
    global _stride_cache
    rate = get_config().trace_sample_rate
    if rate >= 1.0:
        return True
    if rate <= 0.0:
        return False
    cached = _stride_cache
    if cached is None or cached[0] != rate:
        cached = _stride_cache = (rate, max(1, round(1.0 / rate)))
    return next(_sample_counter) % cached[1] == 0


# ------------------------------------------------------------- wire format
# The 25-byte trace leg packed records carry (core/fastpath.py, wire
# 2.1): <16s trace_id><8s span_id><B flags> — flags bit0 = sampled.
# Unsampled requests ship NO leg at all; the leg's presence is flagged
# by the record's TRACE_CTX bit / the reply's TRACED status flag.
_WIRE = struct.Struct("<16s8sB")
WIRE_LEN = _WIRE.size  # 25


def pack_ctx(trace_id: str, span_id: str, sampled: bool = True) -> bytes:
    return _WIRE.pack(bytes.fromhex(trace_id), bytes.fromhex(span_id),
                      1 if sampled else 0)


def unpack_ctx(leg: bytes) -> dict:
    tid, sid, flags = _WIRE.unpack_from(leg)
    return {"trace_id": tid.hex(), "parent_span_id": sid.hex(),
            "sampled": bool(flags & 1)}


# Sentinel an UNSAMPLED root installs in the contextvar: the head
# decision is per REQUEST, so downstream submits inside an unsampled
# request must not re-draw (each stray draw would mint an orphan
# partial trace AND consume a stride tick, skewing the configured rate).
UNSAMPLED = ("", "")


def current() -> tuple[str, str] | None:
    """(trace_id, span_id) of the active span, if any."""
    ctx = _ctx.get()
    return None if ctx is UNSAMPLED else ctx


def suppress():
    """Mark the current context UNSAMPLED (a root that lost the head
    draw): downstream :func:`submit_context` calls inherit the decision
    instead of re-drawing. Returns a token for :func:`deactivate`."""
    return _ctx.set(UNSAMPLED)


def is_suppressed() -> bool:
    return _ctx.get() is UNSAMPLED


def inject() -> dict:
    """Capture the caller's span context for a task spec; starts a fresh
    trace when the caller has none (every traced task belongs to some
    trace — the reference behaves the same for root calls). Does NOT
    apply sampling: use :func:`submit_context` on request paths."""
    ctx = _ctx.get()
    if ctx is None or ctx is UNSAMPLED:
        return {"trace_id": _gen_trace_id(), "parent_span_id": None}
    return {"trace_id": ctx[0], "parent_span_id": ctx[1]}


def submit_context() -> dict | None:
    """Sampling-aware :func:`inject`: inherit the active (already
    decided) context, or head-sample a fresh root. None = this request
    is unsampled — ship nothing, record nothing."""
    ctx = _ctx.get()
    if ctx is not None:
        if ctx is UNSAMPLED:
            return None  # decided at the request's root: no re-draw
        return {"trace_id": ctx[0], "parent_span_id": ctx[1]}
    if not sample():
        return None
    return {"trace_id": _gen_trace_id(), "parent_span_id": None}


class span:
    """Context manager recording one span into ``sink`` (a callable
    taking the span dict — typically the task-event buffer's emit).
    Extra ``attributes`` land in the span dict verbatim; ``stage``
    (queue | exec | wire | pull) and ``transport`` (ring | tunnel |
    rpc) are the ones TraceCriticalPath understands."""

    def __init__(self, name: str, trace_ctx: dict | None, sink,
                 **attributes):
        self.name = name
        self.sink = sink
        self.attributes = attributes
        ctx = trace_ctx or inject()
        self.trace_id = ctx["trace_id"]
        self.parent_span_id = ctx.get("parent_span_id")
        self.span_id = _gen_span_id()
        self._token = None

    def __enter__(self):
        self._t0_ns = time.perf_counter_ns()
        self.start = _wall_s(self._t0_ns)
        self._token = _ctx.set((self.trace_id, self.span_id))
        return self

    def __exit__(self, exc_type, exc, tb):
        _ctx.reset(self._token)
        # same monotonic clock as __enter__: end >= start ALWAYS, and a
        # 2µs span reports 2µs instead of 0.0
        end = self.start + (time.perf_counter_ns() - self._t0_ns) / 1e9
        self.sink({
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_span_id": self.parent_span_id,
            "name": self.name,
            "start_ts": self.start,
            "end_ts": end,
            "error": repr(exc) if exc is not None else None,
            **self.attributes,
        })
        return False


def emit_point(name: str, trace_ctx: dict, sink, **attributes) -> str:
    """Record a zero-duration span (the submit-side marker) and return
    its span id — the parent the executing side's child span links to."""
    span_id = _gen_span_id()
    now = _wall_s(time.perf_counter_ns())
    sink({
        "trace_id": trace_ctx["trace_id"], "span_id": span_id,
        "parent_span_id": trace_ctx.get("parent_span_id"),
        "name": name, "start_ts": now, "end_ts": now,
        **attributes,
    })
    return span_id


def emit_retro(name: str, trace_ctx: dict, sink, dur_s: float,
               end_ns: int | None = None, **attributes) -> str:
    """Record a span for an operation that already FINISHED (duration
    known after the fact — the disagg telemetry shape, where stage
    durations are measured first and reported once). It ended at
    ``end_ns`` (a ``perf_counter_ns`` stamp), or just now."""
    span_id = _gen_span_id()
    end = _wall_s(time.perf_counter_ns() if end_ns is None else end_ns)
    sink({
        "trace_id": trace_ctx["trace_id"], "span_id": span_id,
        "parent_span_id": trace_ctx.get("parent_span_id"),
        "name": name, "start_ts": end - max(0.0, dur_s), "end_ts": end,
        **attributes,
    })
    return span_id


def activate(trace_ctx: dict | None):
    """Set the ambient context from a spec's trace_ctx WITHOUT opening a
    span (thread-side helper); returns a reset token or None."""
    if not trace_ctx:
        return None
    return _ctx.set((trace_ctx["trace_id"],
                     trace_ctx.get("parent_span_id") or _gen_span_id()))


def deactivate(token) -> None:
    if token is not None:
        _ctx.reset(token)


# ------------------------------------------------------------ loop phases
_annotation = None  # (TraceMe.is_enabled, TraceAnnotation) once jax is here


def _profiler():
    """jax's profiler hooks, only if the process imported jax already:
    this module is imported by the driver, the router and every worker,
    which stay off jax. Resolved once per process that has it."""
    global _annotation
    if _annotation is None:
        prof = sys.modules.get("jax.profiler")
        if prof is None:
            return None
        _annotation = (prof.TraceAnnotation.is_enabled, prof.TraceAnnotation)
    return _annotation


def profiling() -> bool:
    """Whether a ``jax.profiler`` trace is on in this process."""
    prof = _profiler()
    return prof is not None and prof[0]()


class phase:
    """Context manager around one host phase of a loop (not a request).

    Always: two ``perf_counter_ns`` reads and one observe into
    ``rt_llm_engine_phase_seconds{phase=<name>}`` (its ``sum`` and bucket
    counts are the seconds and the count). While a ``jax.profiler`` trace
    is on: the same interval as a ``TraceAnnotation(name, **args)`` on
    this thread's line of the trace's ``/host:CPU`` plane — one file and
    one clock with the device's ``XLA Modules``. :meth:`set` adds what is
    only known inside the phase (a block's step count, tokens emitted).
    ``seconds`` is the last interval, once it has closed.

    An annotation belongs to its thread: a phase must not span an
    ``await`` that suspends while another phase could open."""

    __slots__ = ("name", "args", "seconds", "_ann", "_t0")
    # where the seconds go: a subclass names another family and tag, and
    # may know seconds of the interval that are counted under another name
    family, tag = metrics.llm_engine_phase_seconds, "phase"
    _elsewhere = 0.0

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args
        self._ann = None

    def set(self, **args) -> None:
        if self._ann is not None:
            self.args.update(args)  # a phase opened again keeps them
            self._ann.set_metadata(**args)

    def __enter__(self):
        if profiling():
            self._ann = _annotation[1](self.name, **self.args)
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        dt = time.perf_counter_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        self.seconds = max(0.0, dt * 1e-9 - self._elsewhere)
        self.family.observe(self.seconds, {self.tag: self.name})
        return False


# ----------------------------------------------------------------- bring-up
# Every stage between a process's start and its first step, each named where
# the work happens. The five ``program_*`` a jax.monitoring listener feeds
# (``build_duration``); the others are ``stage`` blocks.
STAGES = (
    "backend_start",      # jax's import and jax.devices(): libtpu on the leased chip
    "weights",            # a replica's parameter tree made (params_fn / the family's init)
    "weights_prepare",    # ServePrograms.prepare: the tree laid out for serving
    "pools",              # the engine's cache allocated (ServePrograms.make_cache)
    "program_trace",      # a program traced to a jaxpr (Python)
    "program_lower",      # the jaxpr lowered to its module (Python)
    "program_cache_read",  # an executable read from the persistent compile cache
    "program_compile",    # an executable compiled that the cache could hold
    "program_compile_small",  # one compiled faster than the cache's minimum: never written
    "parts_table",        # compiled_parts, in its own thread beside the program's first run
    "group_placement",    # JaxTrainer: the placement group, a try
    "group_setup",        # JaxTrainer: the workers created and set up, a try
    "train_jax_import",   # TrainWorker.setup: jax imported and configured
    "train_session",      # TrainWorker.setup: the checkpoint and the session
    "train_collective",   # TrainWorker.setup: the collective group's rendezvous
)


class stage(phase):
    """A :class:`phase` of bring-up: the same two clock reads, one observe
    (into ``rt_bringup_seconds{stage=<name>}``) and annotation while a
    profiler trace is on. A name outside ``STAGES`` raises where it is
    written. Its seconds are its OWN: what jax built inside it in this
    thread (making weights compiles a hundred small programs) is counted
    under the ``program_*`` stages and taken off here, so that the stages of
    one thread add up to its wall time."""

    __slots__ = ("_built", "_elsewhere")
    family, tag = metrics.bringup_seconds, "stage"

    def __init__(self, name: str, **args):
        if name not in STAGES:
            raise ValueError(f"{name!r} is not a stage of bring-up: {STAGES}")
        super().__init__(name, **args)

    def __enter__(self):
        self._built = _built_here()
        return super().__enter__()

    def __exit__(self, exc_type, exc, tb):
        self._elsewhere = _built_here() - self._built
        return super().__exit__(exc_type, exc, tb)


# what jax reports of a program it builds, in the thread that builds it and
# in this order (jax/_src/compiler.py compile_or_get_cached): trace, lower,
# then on a cache hit the event, the retrieval time and a "backend compile"
# duration that IS the retrieval; on a miss the backend compile alone
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_BUILD_EVENTS = {
    _TRACE: "program_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "program_lower",
    "/jax/compilation_cache/cache_retrieval_time_sec": "program_cache_read",
    "/jax/core/compile/backend_compile_duration": "program_compile",
}
# per thread: .hit, a cache hit awaits its duration; .tracing, how deep in
# traces of jitted functions inside jitted functions (jax times each, the
# outer one's seconds hold the inner ones'); .built, the seconds counted;
# .record, the record of the ONE program ``build_in_executor`` builds here
_build_thread = _threading.local()


def _built_here() -> float:
    """Seconds of program builds counted in this thread so far."""
    return getattr(_build_thread, "built", 0.0)


def build_event(event: str, **kw) -> None:
    """jax.monitoring event listener (``utils/device.py`` registers the
    three of them)."""
    if event == _CACHE_HIT:
        _build_thread.hit = True


def build_begin(event: str, value: float, **kw) -> None:
    """jax.monitoring scalar listener: jax says when a timed step starts."""
    if event == _TRACE:
        _build_thread.tracing = getattr(_build_thread, "tracing", 0) + 1


def build_duration(event: str, seconds: float, **kw) -> None:
    """jax.monitoring duration listener. A trace counts where it is the
    outermost of its thread. Every executable obtained counts once — under
    ``program_cache_read``, ``program_compile`` or, compiled faster than the
    persistent cache's minimum as jax holds it now (jax never writes such a
    program: eager operations make hundreds), ``program_compile_small``."""
    name = _BUILD_EVENTS.get(event)
    if name is None:
        return
    if name == "program_trace":
        _build_thread.tracing = deep = getattr(_build_thread, "tracing", 1) - 1
        if deep > 0:
            return
    elif name == "program_compile":
        if getattr(_build_thread, "hit", False):
            _build_thread.hit = False
            return
        config = sys.modules["jax"].config
        if seconds < config.jax_persistent_cache_min_compile_time_secs:
            name = "program_compile_small"
    metrics.bringup_seconds.observe(seconds, {"stage": name})
    _build_thread.built = _built_here() + seconds
    built = getattr(_build_thread, "record", None)
    if built is not None:
        field = name.removeprefix("program_").removesuffix("_small") + "_s"
        built[field] += seconds


async def build_in_executor(executor, thunk):
    """``loop.run_in_executor(executor, thunk)`` for a thunk that builds ONE
    program -> ``(its result, {"source": "cache" | "compiled", "trace_s",
    "lower_s", "cache_read_s", "compile_s"})``: what the listeners above saw
    in the thread while it ran. ``"memory"`` where they saw no executable
    obtained: jit's own caches had it (a second engine in one process)."""
    built = dict.fromkeys(("trace_s", "lower_s", "cache_read_s", "compile_s"),
                          0.0)

    def build():
        _build_thread.record = built
        try:
            return thunk()
        finally:
            _build_thread.record = None

    result = await asyncio.get_running_loop().run_in_executor(executor, build)
    source = ("cache" if built["cache_read_s"] else
              "compiled" if built["compile_s"] else "memory")
    return result, {"source": source, **built}


# ------------------------------------------------ parts of a jitted program
# The layer parts a serve program's instructions are summed by. Scopes nest
# and a reader takes the innermost: ``router`` and ``experts`` stand inside an
# expert layer. ``weights_concat`` is a guard that stands nowhere (below).
PARTS = (
    "embed",           # the token rows of the embedding
    "project",         # input norm, q/k/v or latent projections, rotary
    "kv_write",        # the new rows into the pools
    "attention",       # scores, softmax, values, or the paged/prefill kernel
    "attn_out",        # the output projection onto the residual
    "ffn",             # dense feed-forward and shared experts, their norm
    "router",          # scores, top-k, sort, the load counters
    "experts",         # the routed product and its weighted sum
    "indexer",         # the indexer's projections and scores
    "select",          # the top-k mask over the indexer's scores
    "conv",            # a state-space block's depthwise convolution, its saved inputs
    "ssm",             # dt, the recurrence in either form, the D skip, the gated norm
    "delta",           # a delta-rule layer's gate, q/k norms, recurrence in either form, head norm and gate
    "summary",         # a chunk's keys and values pooled into its pair, and its write
    "mix",             # CCA between projections and attention: mean, both convolutions, norm, temperature, rotation, value shift, the row
    "weights_concat",  # a weight laid out again by a program: none may hold it
    "head",            # final norm and logits
    "sample",          # the sampling tail, rng
)
# what a table holds besides: a scan's own instructions that no scope names
# (slices of stacked weights and pools, the carry), and a key that two
# shape variants of one program give different parts
SCAN, AMBIGUOUS = "scan", "?"


def part(name: str):
    """``jax.named_scope(name)`` for a name of ``PARTS``, as a context
    manager or a decorator; any other name raises where it is written, so a
    typo cannot ship. jax is taken as ``_profiler`` takes it: from the
    modules the process has, for only code that traces a program comes
    here."""
    if name not in PARTS:
        raise ValueError(f"{name!r} is not a part of a program: {PARTS}")
    return sys.modules["jax"].named_scope(name)


# one instruction of a compiled program's text: name, result, the rest
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")
_SHAPE = re.compile(r"(pred|[a-z]+\d+)\[[\d,]*\]")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_TO_APPLY = re.compile(r"\bto_apply=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
# opcodes that cost the device nothing and never are an event of a trace
_FREE = frozenset(("parameter", "constant", "get-tuple-element", "tuple",
                   "bitcast", "after-all", "partition-id", "replica-id"))
# a transform's wrapper around the scopes under it: ``transpose(jvp(ffn))``
_WRAPPED = re.compile(r"^(?:jvp|transpose|vmap|checkpoint|remat|custom_jvp|"
                      r"custom_vjp|shard_map)\((.*)\)$")


def instruction_key(name: str, result: str) -> str:
    """``<instruction name>|<first array shape of its result>``: what a
    device event of a trace and an instruction of the program's text have
    in common (``fusion.16|bf16[16,128]``)."""
    m = _SHAPE.search(result)
    return f"{name}|{m.group(0) if m else ''}"


def op_part(op_name: str) -> str | None:
    """The innermost ``PARTS`` component of an ``op_name`` path
    (``jit(f)/while/body/ffn/weights_concat/concatenate``), ``SCAN`` for a
    scan's own unnamed instruction, else None. The last component is the
    primitive and names nothing."""
    path = op_name.split("/")[:-1]
    for comp in reversed(path):
        m = _WRAPPED.match(comp)
        while m:
            comp = m.group(1)
            m = _WRAPPED.match(comp)
        if comp in PARTS:
            return comp
    if "while" in path and "body" in path:
        return SCAN
    return None


def program_instructions(hlo_text: str) -> tuple[str, list]:
    """(module name, computations) of a compiled program's text
    (``Compiled.as_text()``): per computation whose instructions can be
    device events of their own — those of fused computations and of the
    scalar bodies of reductions are inside an event, never one — its
    instructions in the text's (the schedule's) order, each ``(name,
    instruction_key, opcode, op_name or None, names in its operands)``."""
    module = hlo_text[:200].split(",", 1)[0].removeprefix("HloModule ").strip()
    lines = hlo_text.splitlines()
    # computations that are inside an instruction: most of the text, and
    # known only once their callers, which stand after them, are read
    inside: set[str] = set()
    for line in lines:
        if "calls=" in line:
            inside.update(_CALLS.findall(line))
        elif "to_apply=" in line:
            m = _INSTRUCTION.match(line)
            if m and m.group(3) != "call":
                inside.update(_TO_APPLY.findall(m.group(4)))
    computations, rows = [], None
    for line in lines:
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            rows = [] if m and m.group(1) not in inside else None
            if rows is not None:
                computations.append(rows)
            continue
        m = _INSTRUCTION.match(line) if rows is not None else None
        if m is None:
            continue
        name, result, opcode, rest = m.groups()
        op = _OP_NAME.search(rest)
        rows.append((name, instruction_key(name, result), opcode,
                     op.group(1) if op else None,
                     _OPERAND.findall(rest.split(", metadata=", 1)[0])))
    return module, computations


def _agreed(parts: dict, names) -> str | None:
    """The one part that those of ``names`` which have a part all have."""
    found = {parts.get(n) for n in names} - {None}
    return found.pop() if len(found) == 1 else None


def instruction_parts(hlo_text: str) -> tuple[str, dict[str, str]]:
    """(module name, ``{instruction_key: part}``) of a compiled program's
    text, over the instructions that can be device events. An instruction's
    part is its ``op_name``'s (``op_part``). One that has none — the
    compiler's own: a weight fetched in slices ahead of its matmul, a copy
    into fast memory, a layout change — takes the part that all of its
    users have, else the part that all of its operands have: a fetch belongs
    to what it feeds. What is still without a part is absent."""
    module, computations = program_instructions(hlo_text)
    out: dict[str, str] = {}
    for rows in computations:
        named = {name: p for name, _, _, op, _ in rows
                 if op and (p := op_part(op)) is not None}
        users: dict[str, list] = {}
        for name, _, _, _, operands in rows:
            for o in operands:
                users.setdefault(o, []).append(name)
        part = dict(named)
        for name, *_ in reversed(rows):   # the schedule's order: users later
            if name not in part:
                part[name] = _agreed(part, users.get(name, ()))
        down = dict(named)                # what flows on from the producers
        for name, _, _, _, operands in rows:
            if part[name] is None:
                part[name] = down[name] = _agreed(down, operands)
        out.update((key, part[name]) for name, key, opcode, _, _ in rows
                   if opcode not in _FREE and part[name] is not None)
    return module, out


def compiled_parts(compiled) -> dict:
    """What a reader needs of one compiled program (a ``jax.stages.
    Compiled``): its module's name as a trace prints it, its
    ``instruction_parts`` and the seconds making them took. ``stale``: what
    the benchmark's readers ask of a table, and never true — the compile
    cache keys on the scopes (``utils/device.py``). A program with no part
    of its own (``merge_carry``) has an empty table."""
    with stage("parts_table") as reading:
        module, parts = instruction_parts(compiled.as_text())
    return {"module": module, "parts": parts, "stale": False,
            "seconds": reading.seconds}


def merged_parts(variants) -> dict:
    """``compiled_parts`` of a process's programs -> ``{module: {"parts",
    "stale", "variants", "seconds"}}``: the shape variants of one program
    (pads, waves, step counts) as one table, in which a key that two of them
    give different parts names neither."""
    out: dict[str, dict] = {}
    for v in variants:
        p = out.setdefault(v["module"], {"parts": {}, "stale": False,
                                         "variants": 0, "seconds": 0.0})
        p["variants"] += 1
        p["seconds"] += v["seconds"]
        for key, name in v["parts"].items():
            if p["parts"].setdefault(key, name) != name:
                p["parts"][key] = AMBIGUOUS
    return out


# -------------------------------------------------------- critical path
class TraceCriticalPath:
    """Attribute one assembled trace's latency to stages.

    Walks the span tree of one request and splits the root span's wall
    time into ``queue`` (admission/batch queues), ``exec`` (user code),
    ``wire`` (submit/reply hops, routing), ``pull`` (object/KV-page
    movement) and ``other`` — each span's SELF time (its duration minus
    the union of its children's overlap) is charged to its stage, so
    concurrent children never double-bill the parent. The result feeds
    the ``request_critical_path_us`` metrics and the ``/api/trace/<id>``
    waterfall's stage strip.
    """

    STAGES = ("queue", "exec", "wire", "pull", "other")

    @staticmethod
    def classify(s: dict) -> str:
        stage = s.get("stage")
        if stage in TraceCriticalPath.STAGES:
            return stage
        name = s.get("name", "")
        if name.endswith("::run") or name.endswith("::exec"):
            return "exec"
        if name.endswith(".remote") or name.endswith("::call"):
            return "wire"
        if "queue" in name or "admission" in name:
            return "queue"
        if ("adopt" in name or "ship" in name or "pull" in name
                or "kv_" in name):
            return "pull"
        return "other"

    @staticmethod
    def compute(spans: list[dict]) -> dict | None:
        """-> {total_us, stages: {stage: us}, root_span_id, path: [span
        ids root->leaf along the latest-finishing chain]} or None for an
        empty/parentless span set."""
        if not spans:
            return None
        by_id = {s["span_id"]: s for s in spans if s.get("span_id")}
        children: dict[str | None, list[dict]] = {}
        for s in spans:
            children.setdefault(s.get("parent_span_id"), []).append(s)
        roots = [s for s in spans
                 if s.get("parent_span_id") not in by_id]
        if not roots:
            return None
        root = min(roots, key=lambda s: s.get("start_ts", 0.0))
        stages = {st: 0.0 for st in TraceCriticalPath.STAGES}

        def self_time(s: dict) -> float:
            dur = max(0.0, s.get("end_ts", 0.0) - s.get("start_ts", 0.0))
            kids = children.get(s.get("span_id"), ())
            if not kids:
                return dur
            # union of child intervals clipped to this span
            ivs = sorted(
                (max(k["start_ts"], s["start_ts"]),
                 min(k["end_ts"], s["end_ts"])) for k in kids)
            covered = 0.0
            cur_a = cur_b = None
            for a, b in ivs:
                if b <= a:
                    continue
                if cur_b is None or a > cur_b:
                    if cur_b is not None:
                        covered += cur_b - cur_a
                    cur_a, cur_b = a, b
                else:
                    cur_b = max(cur_b, b)
            if cur_b is not None:
                covered += cur_b - cur_a
            return max(0.0, dur - covered)

        # attribute self time over the whole tree under the chosen root
        seen = set()
        stack = [root]
        tree_end = root.get("end_ts", 0.0)
        while stack:
            s = stack.pop()
            sid = s.get("span_id")
            if sid in seen:
                continue
            seen.add(sid)
            tree_end = max(tree_end, s.get("end_ts", 0.0))
            stages[TraceCriticalPath.classify(s)] += self_time(s)
            stack.extend(children.get(sid, ()))
        # critical chain: from the root, follow the latest-finishing child
        path = [root["span_id"]]
        cur = root
        while True:
            kids = [k for k in children.get(cur.get("span_id"), ())
                    if k.get("span_id") not in path]
            if not kids:
                break
            cur = max(kids, key=lambda k: k.get("end_ts", 0.0))
            path.append(cur["span_id"])
        # total spans the whole tree, not just the root's own interval —
        # a driver-rooted trace's root is a zero-duration submit POINT
        # whose children carry all the time
        total = max(0.0, tree_end - root.get("start_ts", 0.0))
        return {
            "total_us": total * 1e6,
            "stages": {st: v * 1e6 for st, v in stages.items()},
            "root_span_id": root["span_id"],
            "root_name": root.get("name"),
            "path": path,
        }
