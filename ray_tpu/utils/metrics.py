"""In-process metrics registry.

TPU-native equivalent of the reference stats layer (ref:
src/ray/stats/metric_defs.cc metric definitions, python/ray/util/metrics.py
user-facing Counter/Gauge/Histogram). Each process keeps one registry;
component code records locally (lock-free dict bumps on the hot path) and
the core client piggybacks periodic snapshots to the GCS KV
(ns="metrics", key=worker hex) on the task-event flush timer, where the
state API aggregates them cluster-wide.

Snapshot format: each metric exports structured ``samples`` —
``{"tags": {...}, "value": v}`` (counters/gauges) or
``{"tags": {...}, "counts": [...], "sum": s}`` (histograms) — so
``state.prometheus_metrics()`` can emit real labels without reparsing
stringified tag tuples, and the GCS rollup plane
(``core/metrics_store.py``) can window counter deltas and merge
histogram buckets across sources. Counters are monotonic cumulatives on
the wire; rates live GCS-side (``state.metric_window``), never here.
"""
from __future__ import annotations

import threading
import time
from typing import Sequence


class Metric:
    def __init__(self, name: str, description: str = "", tag_keys: Sequence[str] = ()):
        self.name = name
        self.description = description
        self.tag_keys = tuple(tag_keys)
        _registry.register(self)

    def _key(self, tags: dict | None) -> tuple:
        if not tags:
            return ()
        return tuple(sorted(tags.items()))


class Counter(Metric):
    def __init__(self, name, description="", tag_keys=()):
        self._values: dict[tuple, float] = {}
        super().__init__(name, description, tag_keys)

    def inc(self, value: float = 1.0, tags: dict | None = None):
        k = self._key(tags)
        self._values[k] = self._values.get(k, 0.0) + value

    def snapshot(self):
        return {"type": "counter",
                "samples": [{"tags": dict(k), "value": v}
                            for k, v in self._values.items()]}


class Gauge(Metric):
    def __init__(self, name, description="", tag_keys=()):
        self._values: dict[tuple, float] = {}
        super().__init__(name, description, tag_keys)

    def set(self, value: float, tags: dict | None = None):
        self._values[self._key(tags)] = value

    def snapshot(self):
        return {"type": "gauge",
                "samples": [{"tags": dict(k), "value": v}
                            for k, v in self._values.items()]}


class Histogram(Metric):
    """Fixed-boundary histogram (ref: metrics.py Histogram)."""

    def __init__(self, name, description="", boundaries: Sequence[float] = (), tag_keys=()):
        self.boundaries = tuple(boundaries) or (
            0.001, 0.01, 0.1, 1.0, 10.0, 100.0, 1000.0
        )
        self._counts: dict[tuple, list[int]] = {}
        self._sums: dict[tuple, float] = {}
        super().__init__(name, description, tag_keys)

    def observe(self, value: float, tags: dict | None = None):
        k = self._key(tags)
        counts = self._counts.setdefault(k, [0] * (len(self.boundaries) + 1))
        i = 0
        while i < len(self.boundaries) and value > self.boundaries[i]:
            i += 1
        counts[i] += 1
        self._sums[k] = self._sums.get(k, 0.0) + value

    def observe_many(self, values, tags: dict | None = None):
        """Bulk feed (flush-time batches, e.g. the flight recorder's
        sampled stage latencies): one key lookup + bisect per value
        instead of a linear boundary scan per observe."""
        from bisect import bisect_left

        k = self._key(tags)
        counts = self._counts.setdefault(k, [0] * (len(self.boundaries) + 1))
        b = self.boundaries
        total = 0.0
        for v in values:
            counts[bisect_left(b, v)] += 1
            total += v
        self._sums[k] = self._sums.get(k, 0.0) + total

    def snapshot(self):
        return {
            "type": "histogram",
            "boundaries": list(self.boundaries),
            "samples": [{"tags": dict(k), "counts": list(c),
                         "sum": self._sums.get(k, 0.0)}
                        for k, c in self._counts.items()],
        }


class _Registry:
    def __init__(self):
        self._metrics: dict[str, Metric] = {}
        self._lock = threading.Lock()

    def register(self, metric: Metric):
        with self._lock:
            self._metrics[metric.name] = metric

    def get(self, name: str) -> Metric | None:
        return self._metrics.get(name)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "ts": time.time(),
                "metrics": {name: m.snapshot() for name, m in self._metrics.items()},
            }


_registry = _Registry()


def registry() -> _Registry:
    return _registry


# --- core runtime metrics (ref: metric_defs.cc tasks/objects families) ------
tasks_submitted = Counter("rt_tasks_submitted", "tasks submitted by this process")
tasks_finished = Counter("rt_tasks_finished", "task replies applied, by outcome",
                         tag_keys=("outcome",))
actor_calls = Counter("rt_actor_calls", "actor method calls submitted")
objects_put = Counter("rt_objects_put", "objects created via put")
object_bytes_put = Counter("rt_object_bytes_put", "bytes written via put")
objects_spilled = Counter("rt_objects_spilled", "objects spilled to disk")
objects_restored = Counter("rt_objects_restored", "spilled objects restored")
# memory tiering (PR 18): byte-granular spill/restore traffic plus the
# prefix cache's tier-1 effectiveness (set from cache stats)
spill_bytes_total = Counter("rt_spill_bytes_total",
                            "bytes written to tier-1 spill files")
restore_bytes_total = Counter("rt_restore_bytes_total",
                              "bytes restored from tier-1 into shm arenas")
tier1_hit_rate = Gauge("rt_tier1_hit_rate",
                       "fraction of prefix-cache hits served from tier-1")
# arena watermarks (rollup plane): live/peak/capacity bytes per arena the
# tiering registry knows (core/tiering.py stats providers — prefix cache,
# shard plane, KV staging; the raylet hand-rolls the object_store cells
# into its own snapshot). Set at flush time from sample_arenas().
arena_bytes = Gauge("rt_arena_bytes", "live bytes in a tiering arena",
                    tag_keys=("arena",))
arena_peak_bytes = Gauge("rt_arena_peak_bytes",
                         "high-water bytes a tiering arena has held",
                         tag_keys=("arena",))
arena_capacity_bytes = Gauge("rt_arena_capacity_bytes",
                             "configured capacity of a tiering arena",
                             tag_keys=("arena",))
task_exec_seconds = Histogram("rt_task_exec_seconds", "worker-side task execution time")

# --- flight-recorder families (PR 4; see utils/recorder.py) -----------------
# Per-stage fast-lane latency. Fed at flush time from the recorder's
# retained sample window (bounded batch per flush — Dapper-style
# sampling under load), NOT per task: the hot path pays one ring store.
task_stage_seconds = Histogram(
    "rt_task_stage_seconds",
    "fast-lane per-stage task latency (sampled by the flight recorder)",
    boundaries=(1e-6, 1e-5, 1e-4, 1e-3, 1e-2, 0.1, 1.0, 10.0),
    tag_keys=("stage",))
task_stage_us = Gauge(
    "rt_task_stage_us",
    "fast-lane per-stage latency percentiles over the recorder window (µs)",
    tag_keys=("stage", "q"))
# --- LLM decode-plane signals (llm/disagg/telemetry.py) ---------------------
# Published per decode-worker process. (Tokens in flight, which the disagg
# scheduler and the serve router admit on, travel in the workers' own
# ``headroom()`` / ``signals()`` replies, not through the registry.)
# monotonic spec-decode cumulatives: the rollup plane's derived
# llm_spec_accept_rate series is accepted/proposed per window slot —
# restart-safe and windowable (tokens per step and the acceptance rate
# of recent blocks ride the ns="latency" stage windows)
llm_spec_proposed_total = Counter(
    "rt_llm_spec_proposed_total",
    "draft tokens proposed to the fused spec-decode verify")
llm_spec_accepted_total = Counter(
    "rt_llm_spec_accepted_total",
    "draft tokens the fused spec-decode verify accepted")
# --- LLM engine and serve-lane stages (PR 25) -------------------------------
# What a request waits for and what the engine's loop thread does, taken
# inside the program: utils/tracing.phase feeds the phase histogram,
# llm/engine.py the request waits and prefill counters, core/worker.py
# the lane's two legs. LLMEngineServer.engine_stats()["stages"] hands
# the cumulative sums and counts to whoever asks (the benchmark's
# per-layer readers take deltas of them).
_WAIT_BOUNDS = (0.001, 0.002, 0.005, 0.01, 0.02, 0.05, 0.1, 0.2, 0.5,
                1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0)
llm_engine_phase_seconds = Histogram(
    "rt_llm_engine_phase_seconds",
    "host phases of the LLM engine loop (engine.admit, engine.block_sync, ...)",
    boundaries=(1e-5, 1e-4, 1e-3, 1e-2, 0.1, 1.0, 10.0), tag_keys=("phase",))
llm_queue_wait_seconds = Histogram(
    "rt_llm_queue_wait_seconds",
    "engine submit to the dispatch of the request's prefill",
    boundaries=_WAIT_BOUNDS)
llm_prefill_wait_seconds = Histogram(
    "rt_llm_prefill_wait_seconds",
    "prefill dispatch to the request's first token on the host",
    boundaries=_WAIT_BOUNDS)
llm_decode_seconds = Histogram(
    "rt_llm_decode_seconds",
    "first token to last token of a finished request",
    boundaries=_WAIT_BOUNDS)
llm_decode_tokens_total = Counter(
    "rt_llm_decode_tokens_total",
    "tokens after the first of finished requests (rt_llm_decode_seconds' work)")
llm_prefill_waves_total = Counter(
    "rt_llm_prefill_waves_total", "batched prefill dispatches")
llm_prefill_prompts_total = Counter(
    "rt_llm_prefill_prompts_total", "prompts those dispatches prefilled")
# How often admission kept the pipeline full: of the prefill waves above,
# those dispatched while a decode block was in flight that nothing waited for
# (the engine's loops retire a slot whose end is scheduled and refill it behind
# the block that ends it). The first wave into an idle engine has no block to
# be behind.
llm_admit_waves_undrained_total = Counter(
    "rt_llm_admit_waves_undrained_total",
    "prefill waves dispatched behind a decode block in flight, unwaited")
llm_prefill_true_tokens_total = Counter(
    "rt_llm_prefill_true_tokens_total", "prompt tokens prefilled")
llm_prefill_padded_tokens_total = Counter(
    "rt_llm_prefill_padded_tokens_total",
    "rows x pad of the prefill programs run, dummy rows included")
# Read amplification of decode attention, reckoned on the host at each
# block's sync (llm/engine.py _observe_kv_reads): the positions the live
# slots attended, and the positions the family's decode program fetched
# from its page pool to attend them — pages walked x page size where the
# program reads in place, slots x table x page size a step where it gathers
# the whole window.
# A cache of more than one kind of page (window and full layers,
# llm/programs.py PageKind): the untagged sample is the mean over the layers
# — positions within a layer's REACH, and fetched for it — and each kind has
# a sample of its own layers under its name.
llm_decode_kv_tokens_live_total = Counter(
    "rt_llm_decode_kv_tokens_live_total",
    "positions attended by live decode slots, summed over steps",
    tag_keys=("kind",))
llm_decode_kv_tokens_read_total = Counter(
    "rt_llm_decode_kv_tokens_read_total",
    "positions the decode programs fetched from the page pool for them",
    tag_keys=("kind",))
# The allocator of such a cache, a kind: pages its slots hold now, pages
# drawn (whenever: at admission for the prompt, then as the slot grows), and
# the pages of window layers that a ring table wrote over as the window slid
# past them (never drawn a second time). Of EVERY family, one kind or more:
# the pages drawn after admission, and the loop turns on which the head of
# the queue was refused, by what it waited for — how often admission by the
# timeline of demand (llm/engine.py _timeline) engages.
llm_pages_held = Gauge(
    "rt_llm_pages_held", "pages of a kind that slots hold now",
    tag_keys=("kind",))
llm_pages_drawn_total = Counter(
    "rt_llm_pages_drawn_total", "pages of a kind drawn, whenever",
    tag_keys=("kind",))
llm_pages_grown_total = Counter(
    "rt_llm_pages_grown_total",
    "pages of a kind drawn after admission, as a slot's sequence grew",
    tag_keys=("kind",))
llm_admit_deferred_total = Counter(
    "rt_llm_admit_deferred_total",
    "loop turns on which the head of the queue was not admitted",
    tag_keys=("for",))
llm_window_pages_released_total = Counter(
    "rt_llm_window_pages_released_total",
    "pages a window slid past that its ring table reused, counted when the "
    "slot is freed")
# What a model family's decode programs count themselves, a step
# (llm/programs.py ServePrograms.stats): the sums ride back with each block's
# tokens and land here when the block is synced. The expert layers of
# llm/mla_moe.py: rows routed to the experts held here, distinct experts
# that got any, the largest expert's rows, experts held x expert layers,
# and the grouped product's passes over an expert's matrices — each summed
# over expert layers and decode steps.
LLM_MODEL_STATS = {
    "moe_assignments": Counter(
        "rt_llm_moe_assignments_total",
        "token-to-expert assignments of live decode slots"),
    "moe_experts_touched": Counter(
        "rt_llm_moe_experts_touched_total",
        "distinct experts with at least one token, a step a layer"),
    "moe_max_load": Counter(
        "rt_llm_moe_max_load_total",
        "tokens of the most loaded expert, a step a layer"),
    "moe_expert_slots": Counter(
        "rt_llm_moe_expert_slots_total",
        "experts held x expert layers x decode steps: what touched is a share of"),
    "moe_passes": Counter(
        "rt_llm_moe_expert_passes_total",
        "times an expert's matrices went through the MXU, a step a layer: "
        "once a touched expert, more where its rows took several chunks"),
    # the learned sparse attention of llm/sparse_moe.py, each summed over
    # layers, live slots and decode steps
    "sparse_scored": Counter(
        "rt_llm_sparse_positions_scored_total",
        "cached positions the indexer scored for live decode slots"),
    "sparse_attended": Counter(
        "rt_llm_sparse_rows_attended_total",
        "positions in the selected sets: min(length, topk) a slot a layer"),
    "sparse_kv_fetched": Counter(
        "rt_llm_sparse_kv_positions_fetched_total",
        "K/V positions the attention fetched for them: whole pages walked, "
        "or the rows gathered"),
    "ssm_updates": Counter(
        "rt_llm_ssm_state_updates_total",
        "state rows a decode step read and wrote: live slots x state-space "
        "blocks"),
    # the walks of ops/paged_attention.py over a K and a V pool, as the
    # program that makes them counts (llm/ssm_moe.py), each summed over
    # attention layers, live slots and decode steps
    "walk_blocks": Counter(
        "rt_llm_walk_blocks_total",
        "sub-runs of a block of pages (the unit of a copy) a K/V walk "
        "fetched"),
    "walk_run_blocks": Counter(
        "rt_llm_walk_run_blocks_total",
        "those of them fetched as ONE copy, or inside a whole block's: all "
        "pages hold tokens and lie one after the other in the pool"),
    "delta_updates": Counter(
        "rt_llm_delta_state_updates_total",
        "delta-rule state rows a decode step read and wrote: live slots x "
        "delta-rule layers"),
    "moe_tokens_here": Counter(
        "rt_llm_moe_tokens_here_total",
        "live tokens that chose at least one held expert, a step an expert "
        "layer: of live slots x expert layers, the share this holder sees"),
    "eva_pairs": Counter(
        "rt_llm_eva_pairs_written_total",
        "pooled key/value pairs a decode step wrote: chunks filled x layers"),
    "sparse_walk_blocks": Counter(
        "rt_llm_sparse_walk_blocks_total",
        "blocks of pages the indexer's and the selected walk fetched"),
    "sparse_walk_run_blocks": Counter(
        "rt_llm_sparse_walk_run_blocks_total",
        "those of them fetched as ONE copy: all pages hold tokens and lie "
        "one after the other in the pool"),
    "sparse_select_walked": Counter(
        "rt_llm_sparse_select_columns_walked_total",
        "table columns the selection's passes walked: up to the longest "
        "live slot of each tile of slots"),
    "sparse_select_width": Counter(
        "rt_llm_sparse_select_columns_width_total",
        "slots x table width: what walked is a share of"),
    "cca_row_updates": Counter(
        "rt_llm_cca_row_updates_total",
        "convolution rows a decode step read and wrote beside the K/V page "
        "it wrote: live slots x layers (llm/cca_moe.py)"),
    # the looped family (llm/looped.py), each summed over decode steps
    "looped_live_slots": Counter(
        "rt_llm_looped_live_slots_total",
        "live slots of a decode step: a share of steps x max_batch — where "
        "the pages run dry, slots stand empty beside a queue"),
    "looped_exit_depth": Counter(
        "rt_llm_looped_exit_depth_total",
        "the pass the exit rule chose, summed over live slots: over live "
        "slots, the mean depth (n_passes at a threshold of 1)"),
}
serve_lane_seconds = Histogram(
    "rt_serve_lane_seconds",
    "actor-lane call: ring (submit to pop) and loop (pop to the call's "
    "start on the event loop)",
    boundaries=_WAIT_BOUNDS, tag_keys=("leg",))
# --- bring-up (PR 50) -------------------------------------------------------
# Every stage between a process's start and its first step, by the one
# vocabulary of utils/tracing.py STAGES: tracing.stage times the stages the
# program walks through itself, tracing.build_duration what jax reports of
# every program it builds (its trace, its lowering, and a read from the
# persistent compile cache or a compile). Sums and counts, so a count is the
# number of programs, tries or trees.
bringup_seconds = Histogram(
    "rt_bringup_seconds",
    "stages of bring-up: backend start, weights, pools, a program's trace / "
    "lower / cache read / compile, a train group's placement and set-up",
    boundaries=(0.001, 0.01, 0.1, 1.0, 10.0, 100.0), tag_keys=("stage",))
STAGE_FAMILIES = (
    llm_engine_phase_seconds, llm_queue_wait_seconds,
    llm_prefill_wait_seconds, llm_decode_seconds, llm_decode_tokens_total,
    llm_prefill_waves_total, llm_prefill_prompts_total,
    llm_prefill_true_tokens_total, llm_prefill_padded_tokens_total,
    llm_admit_waves_undrained_total,
    llm_decode_kv_tokens_live_total, llm_decode_kv_tokens_read_total,
    llm_pages_held, llm_pages_drawn_total, llm_window_pages_released_total,
    llm_pages_grown_total, llm_admit_deferred_total,
    *LLM_MODEL_STATS.values(), serve_lane_seconds, bringup_seconds)


def family_totals(m) -> dict:
    """``{tag value or "": {"sum", "count"}}`` of one family, cumulative
    since process start (a counter has no ``count``)."""
    if isinstance(m, Histogram):
        return {(k[0][1] if k else ""):
                {"sum": m._sums.get(k, 0.0), "count": sum(c)}
                for k, c in list(m._counts.items())}
    return {(k[0][1] if k else ""): {"sum": v}
            for k, v in list(m._values.items())}


def stage_totals() -> dict:
    """``{family: family_totals}`` of the stage families above."""
    return {m.name: family_totals(m) for m in STAGE_FAMILIES}


# serve SLO cumulatives: serve_slo_breach_fraction = breaches/requests
# per window slot (boundary-free, unlike bucketing latencies at the SLO)
serve_requests_total = Counter(
    "rt_serve_requests_total", "serve requests completed by a replica",
    tag_keys=("key",))
serve_slo_breaches_total = Counter(
    "rt_serve_slo_breaches_total",
    "serve requests that finished over their deployment's latency SLO",
    tag_keys=("key",))
# NOTE: rt_request_critical_path_us (the GCS trace assembler's per-stage
# request-latency histogram) is deliberately NOT declared here: the GCS
# hand-rolls its cells (core/gcs.py _trace_metrics_tick) because an
# in-process GCS shares this process-global registry with the driver,
# and publishing the shared snapshot under a second kv key would
# double-count every driver metric.
# Native shm transport counters (ring.cc RingStats / store.cc StoreStats),
# summed over live lanes and set at flush time.
fastpath_ring = Gauge(
    "rt_fastpath_ring",
    "shm task-ring counters summed over live lanes (ring.cc RingStats)",
    tag_keys=("which", "stat"))
object_store_stat = Gauge(
    "rt_object_store",
    "shm arena counters (store.cc StoreStats)",
    tag_keys=("stat",))
