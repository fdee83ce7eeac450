"""Perf harness: core microbenchmarks + single-chip Llama train step.

Mirrors the reference's microbenchmark suite
(ref: python/ray/_private/ray_perf.py:1, release/microbenchmark/run_microbenchmark.py)
and compares against the checked-in expectations in BASELINE.md
(release/perf_metrics/microbenchmark.json, v2.46.0).

Usage:
    python bench.py               # full run; prints ONE headline JSON line
    python bench.py --micro       # microbenchmarks only
    python bench.py --model       # model benchmark only
    python bench.py --quick       # short windows (CI smoke)

Side effect: writes BENCHVS.md (ours-vs-reference table) and
bench_results.json (all raw numbers).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# Reference numbers from BASELINE.md (release/perf_metrics/microbenchmark.json).
BASELINE = {
    "single_client_get_calls": 10_723.0,
    "single_client_put_calls": 5_113.0,
    "single_client_put_gigabytes": 20.1,
    "single_client_tasks_sync": 970.0,
    "single_client_tasks_async": 8_081.0,
    "multi_client_tasks_async": 21_960.0,
    "1_1_actor_calls_sync": 2_020.0,
    "1_1_actor_calls_async": 7_484.0,
    "1_n_actor_calls_async": 8_318.0,
    "n_n_actor_calls_async": 27_465.0,
    "1_1_async_actor_calls_sync": 1_484.0,
    "1_1_async_actor_calls_async": 4_133.0,
    "single_client_wait_1k_refs": 4.8,
    "placement_group_create_removal": 769.0,
}

HEADLINE = "single_client_tasks_async"

# Host-health gate: raw single-thread warm memcpy on this VM ceilings at
# ~20 GB/s; below this floor the shared host is absorbing heavy neighbor
# load and every wall-clock number in the run is deflated. Such runs are
# stamped host_degraded and their vs_baseline ratio is withheld so a bad
# box can't silently rewrite the perf record.
HOST_MEMCPY_FLOOR_GBPS = 4.0

# bf16 peak FLOP/s per chip by device kind (public TPU specs).
TPU_PEAK_FLOPS = {
    "v4": 275e12,
    "v5e": 197e12,
    "v5 lite": 197e12,
    "v5p": 459e12,
    "v5": 459e12,
    "v6e": 918e12,
    "v6 lite": 918e12,
}


def timeit(fn, *, window: float, multiplier: int = 1, trials: int = 2) -> float:
    """Run fn repeatedly for ``window`` seconds per trial; return best
    ops/sec (ops = calls * multiplier). Mirrors the reference's
    ray_microbenchmark_helpers.timeit shape."""
    fn()  # warmup
    best = 0.0
    for _ in range(trials):
        count = 0
        start = time.perf_counter()
        while True:
            fn()
            count += 1
            elapsed = time.perf_counter() - start
            if elapsed >= window:
                break
        best = max(best, count * multiplier / elapsed)
    return best


def lint_findings() -> int | None:
    """Unsuppressed raylint findings over ray_tpu/ (the test_lint.py
    self-check gate, surfaced in bench artifacts); None if the linter
    itself fails so a lint crash can't sink the perf numbers."""
    try:
        from ray_tpu.devtools.lint import lint_paths

        pkg = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "ray_tpu")
        return len(lint_paths([pkg]))
    except Exception as e:
        print(f"raylint gate failed: {e!r}", file=sys.stderr)
        return None


def lint_flow_findings() -> tuple[int | None, float | None]:
    """(unsuppressed interprocedural findings over ray_tpu/, wall
    seconds for the pass) — the `ray_tpu lint --flow` self-check gate
    (RT020-RT023), surfaced with its cost so call-graph growth that
    pushes the pass toward the tier-1 ceiling shows up in BENCHVS before
    it times out CI. (None, None) on a flow-pass crash."""
    try:
        from ray_tpu.devtools.lint import flow

        pkg = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "ray_tpu")
        t0 = time.monotonic()
        n = len(flow.analyze_paths([pkg]))
        return n, round(time.monotonic() - t0, 3)
    except Exception as e:
        print(f"raylint flow gate failed: {e!r}", file=sys.stderr)
        return None, None


def _stage_latency_results(prefix: str = "") -> dict[str, float]:
    """Per-stage fast-lane percentiles via state.list_task_latency()
    (published on the ~1s flush timer: poll briefly for the freshest
    window). Flat keys so they ride the BENCHVS table. ``prefix="actor_"``
    reads the actor-call stage window (published beside the task one)
    and emits the ROADMAP item-1 ``actor_stage_*`` rows."""
    from ray_tpu import state

    out: dict[str, float] = {}
    lat: dict = {}
    deadline = time.time() + 5.0
    while time.time() < deadline:
        try:
            lat = state.list_task_latency()
        except Exception:
            lat = {}
        if lat.get(f"{prefix}total", {}).get("count", 0) > 0:
            break
        time.sleep(0.3)
    for stage in ("ring_sub", "deserialize", "exec", "ring_reply", "total"):
        row = lat.get(f"{prefix}{stage}")
        if row:
            out[f"{prefix}stage_{stage}_p50_us"] = row["p50_us"]
            out[f"{prefix}stage_{stage}_p99_us"] = row["p99_us"]
    return out


def _recorder_direct_overhead_us() -> float:
    """Direct on-vs-off measurement of the exact per-task recorder
    operations, run against the real modules: the ON arm executes the
    driver's reply-apply additions (submit stamp, t0 registration and
    pop, one raw stats-ring store) plus the worker pump's additions (two
    exec-boundary clock reads, the 16-byte stage stamp, the 1-in-16
    W_TASK slot); the OFF arm executes the residual disabled-gate
    checks. This is the only estimator with sub-µs resolution on a
    shared host — end-to-end wall/CPU per task swings ±30-200µs between
    runs, ~two orders of magnitude above the 1µs budget under test
    (the subprocess A/B arms below bracket that end-to-end noise)."""
    import time as _t

    from ray_tpu.core import fastpath as _fp
    from ray_tpu.utils import recorder as _rec

    N = 50_000
    tid = b"x" * 16
    rec = _rec.Recorder(4096, None)
    st = _rec.StageStats(4096)
    stamp = _fp.pack_stamp(100, 200, 300)
    clock = _t.perf_counter_ns
    stamp_pack = _fp._STAMP.pack  # the pump's bound fast path
    t0ns: dict = {}
    now_ns = _t.perf_counter_ns()

    lane = object()  # stand-in for the routing value both arms store
    # process_replies inlines the stats store with ring/cap hoisted
    sring, scap = st.ring, st.cap

    def task(i, on):
        # ONE function, recorder work behind the same gated branches the
        # real code uses — the on-vs-off delta is exactly the recorder's
        # marginal, not harness-structure noise. Baseline ops BOTH arms
        # pay: the oid-lane routing dict store + pop.
        t0 = now_ns if on else 0  # driver submit stamp (the ns clock
        #                           read already exists for burst
        #                           detection; the stamp reuses it)
        t0ns[i] = (lane, t0)
        ent = t0ns.pop(i)
        if ent[1]:  # driver reply-apply: one raw stats-ring store
            sring[st.n % scap] = (ent[1], 1234567890, tid, stamp)
            st.n += 1
        if on:  # worker pump: exec-boundary clocks + stamp + W_TASK/16
            t_x0 = clock()
            t_x1 = clock()
            try:
                s = stamp_pack(t_x0 - 1000, 500, t_x1 - t_x0)
            except Exception:
                s = stamp
            # i advances once per task, exactly like the pump's wt_n
            if not (i & 15):
                rec.record_wtask(tid, t_x1, 100, 500, t_x1 - t_x0)
        else:
            s = b""
        return s

    def one_round(on) -> float:
        t0 = clock()
        for i in range(N):
            task(i, on)
        return (clock() - t0) / N

    one_round(True)
    one_round(False)  # warm both code paths
    on_t, off_t = [], []
    for _ in range(7):  # alternating rounds; min-per-arm (the timeit
        on_t.append(one_round(True))        # doctrine: interference is
        off_t.append(one_round(False))      # additive-positive, so the
    return max(0.0, (min(on_t) - min(off_t)) / 1e3)  # minima are the
    # least-interfered estimates of each arm's deterministic cost


# Recorder end-to-end A/B child: a fresh cluster per arm (the recorder
# switch propagates to workers through the serialized config), async
# batches because they have the lowest per-task cost and therefore the
# most sensitive denominator.
_AB_CHILD = r"""
import json, sys, time
import ray_tpu
batches, per_batch = int(sys.argv[1]), int(sys.argv[2])
ray_tpu.init(num_cpus=16)

@ray_tpu.remote
def _n():
    return b"ok"

ray_tpu.get([_n.remote() for _ in range(per_batch)])  # warm lanes
best = None
for _ in range(batches):
    t0 = time.perf_counter()
    ray_tpu.get([_n.remote() for _ in range(per_batch)])
    us = (time.perf_counter() - t0) / per_batch * 1e6
    best = us if best is None else min(best, us)
ray_tpu.shutdown()
print(json.dumps({"wall_us": best}))
"""


def _metrics_direct_overhead_us() -> float:
    """metrics_overhead_us: the per-task cost of the metrics plumbing a
    fast-lane task actually pays — one untagged ``Counter.inc()`` at
    submit plus one tagged ``inc(tags={"outcome": ...})`` at reply-apply
    (the rollup plane adds NOTHING here: counters stay cumulative dict
    bumps; windowing happens GCS-side off the 1/s flush). Same
    min-per-arm alternating-rounds estimator as the recorder number;
    budget < 1.0µs/task."""
    import time as _t

    from ray_tpu.utils.metrics import Counter

    N = 50_000
    submitted = Counter("bench_m_submitted")
    finished = Counter("bench_m_finished", tag_keys=("outcome",))
    tags_ok = {"outcome": "ok"}
    clock = _t.perf_counter_ns
    sink: dict = {}

    def task(i, on):
        # baseline both arms pay: the routing dict store + pop the real
        # submit/reply pair does around the metric bumps
        sink[i] = i
        sink.pop(i)
        if on:
            submitted.inc()
            finished.inc(tags=tags_ok)

    def one_round(on) -> float:
        t0 = clock()
        for i in range(N):
            task(i, on)
        return (clock() - t0) / N

    one_round(True)
    one_round(False)  # warm both code paths
    on_t, off_t = [], []
    for _ in range(7):
        on_t.append(one_round(True))
        off_t.append(one_round(False))
    return max(0.0, (min(on_t) - min(off_t)) / 1e3)


def run_metrics_overhead() -> dict[str, float]:
    """Fresh-subprocess direct measurement (same heap-amortization
    argument as the recorder number: this process's post-suite heap
    would bill the counters for the harness's garbage)."""
    import subprocess

    proc = subprocess.run(
        [sys.executable, "-c",
         "import bench, json; "
         "print(json.dumps(bench._metrics_direct_overhead_us()))"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        cwd=os.path.dirname(os.path.abspath(__file__)),
        capture_output=True, text=True, timeout=300)
    if proc.returncode == 0:
        return {"metrics_overhead_us": json.loads(
            proc.stdout.strip().splitlines()[-1])}
    print(f"metrics direct measure failed:\n{proc.stderr[-1000:]}",
          file=sys.stderr)
    return {"metrics_overhead_us": _metrics_direct_overhead_us()}


def run_recorder_ab(quick: bool) -> dict[str, float]:
    """recorder_overhead_us: the flight recorder forced off vs on.
    The headline number is the DIRECT per-task operation delta
    (_recorder_direct_overhead_us — sub-µs resolution); the subprocess
    wall A/B arms (recorder_ab_wall_*_us, best-of per arm across
    alternating-order rounds) bracket the end-to-end effect, whose
    between-run noise on this shared 1-vCPU host (±30-200µs/task)
    swamps any µs-scale delta."""
    import subprocess

    # the direct measurement runs in a FRESH subprocess: after the full
    # micro suite this process's heap makes every allocation's gc
    # amortization ~50% more expensive, which would bill the recorder
    # for the bench harness's garbage
    proc = subprocess.run(
        [sys.executable, "-c",
         "import bench, json; "
         "print(json.dumps(bench._recorder_direct_overhead_us()))"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        cwd=os.path.dirname(os.path.abspath(__file__)),
        capture_output=True, text=True, timeout=300)
    out = {}
    if proc.returncode == 0:
        out["recorder_overhead_us"] = json.loads(
            proc.stdout.strip().splitlines()[-1])
    else:
        print(f"recorder direct measure failed:\n{proc.stderr[-1000:]}",
              file=sys.stderr)
        out["recorder_overhead_us"] = _recorder_direct_overhead_us()
    rounds = 2 if quick else 3
    batches, per_batch = (4, 250) if quick else (8, 500)
    env_base = {**os.environ, "JAX_PLATFORMS": "cpu"}
    arms: dict[str, list[float]] = {"off": [], "on": []}
    order = [("off", "0"), ("on", "1")]
    for r in range(rounds):
        for arm, flag in (order if r % 2 == 0 else order[::-1]):
            env = {**env_base, "RT_RECORDER_ENABLED": flag}
            proc = subprocess.run(
                [sys.executable, "-c", _AB_CHILD, str(batches),
                 str(per_batch)],
                env=env, capture_output=True, text=True, timeout=600)
            if proc.returncode != 0:
                print(f"recorder A/B arm {arm} failed:\n{proc.stderr[-2000:]}",
                      file=sys.stderr)
                return out
            val = json.loads(proc.stdout.strip().splitlines()[-1])
            arms[arm].append(val["wall_us"])
    out["recorder_ab_wall_off_us"] = min(arms["off"])
    out["recorder_ab_wall_on_us"] = min(arms["on"])
    return out


# tracing A/B child: sync round trips on the task fast lane and the
# actor ring lane — the exact record paths the 2.1 trace leg touches.
# Closed-loop on purpose: per-CALL overhead is the unsampled claim.
_TRACE_AB_CHILD = r"""
import json, sys, time
import ray_tpu

rounds, per_round = int(sys.argv[1]), int(sys.argv[2])
ray_tpu.init(num_cpus=4)

@ray_tpu.remote
def _leaf(i):
    return i

class _Echo:
    def echo(self, x):
        return x

a = ray_tpu.remote(_Echo).remote()
for i in range(200):  # warm: leases, lanes, jit of nothing, flush timers
    ray_tpu.get(_leaf.remote(i))
    ray_tpu.get(a.echo.remote(i))
best_task = best_actor = float("inf")
for r in range(rounds):
    t0 = time.perf_counter()
    for i in range(per_round):
        ray_tpu.get(_leaf.remote(i))
    best_task = min(best_task, (time.perf_counter() - t0) / per_round * 1e6)
    t0 = time.perf_counter()
    for i in range(per_round):
        ray_tpu.get(a.echo.remote(i))
    best_actor = min(best_actor, (time.perf_counter() - t0) / per_round * 1e6)
print(json.dumps({"task_us": best_task, "actor_us": best_actor}))
ray_tpu.shutdown()
"""


def run_tracing_bench(quick: bool) -> dict[str, float]:
    """tracing_overhead_us: interleaved A/B/C over the fast-lane record
    paths — tracing off / on-but-unsampled (rate 0: the one-branch wire
    path every record pays) / sampled at 1% (the Dapper production
    default). The headline is the UNSAMPLED task-lane delta, which must
    stay within noise of the off arm (the tentpole's cost claim); the
    sampled arm prices the spans + wire legs actually taken."""
    import subprocess

    rounds = 2 if quick else 3
    inner_rounds, per_round = (2, 300) if quick else (3, 600)
    arms = {
        "off": {"RT_TRACING_ENABLED": "0"},
        "unsampled": {"RT_TRACING_ENABLED": "1",
                      "RT_TRACE_SAMPLE_RATE": "0.0"},
        "sampled1": {"RT_TRACING_ENABLED": "1",
                     "RT_TRACE_SAMPLE_RATE": "0.01"},
    }
    best: dict[str, dict[str, float]] = {k: {} for k in arms}
    order = list(arms)
    for r in range(rounds):
        for arm in (order if r % 2 == 0 else order[::-1]):
            env = {**os.environ, "JAX_PLATFORMS": "cpu", **arms[arm]}
            proc = subprocess.run(
                [sys.executable, "-c", _TRACE_AB_CHILD,
                 str(inner_rounds), str(per_round)],
                env=env, capture_output=True, text=True, timeout=600,
                cwd=os.path.dirname(os.path.abspath(__file__)))
            if proc.returncode != 0:
                print(f"tracing A/B arm {arm} failed:\n"
                      f"{proc.stderr[-2000:]}", file=sys.stderr)
                return {}
            val = json.loads(proc.stdout.strip().splitlines()[-1])
            for k, v in val.items():
                best[arm][k] = min(best[arm].get(k, float("inf")), v)
    out = {}
    for k in ("task_us", "actor_us"):
        for arm in arms:
            out[f"tracing_{k[:-3]}_{arm}_us"] = round(best[arm][k], 1)
    out["tracing_overhead_us"] = round(
        best["unsampled"]["task_us"] - best["off"]["task_us"], 2)
    out["tracing_sampled1_overhead_us"] = round(
        best["sampled1"]["task_us"] - best["off"]["task_us"], 2)
    return out


def _chaos_point_overhead_us() -> dict[str, float]:
    """chaos_overhead_us: per-fault-point cost A/B — fault points
    compiled out (chaos disabled: the bare ``if chaos.ENABLED`` gate
    every hot path pays) vs armed-but-idle (controller enabled with a
    plan matching NO hot point: gate + point() call + the controller's
    lock-free name prefilter). Min-per-arm over alternating rounds, the
    timeit doctrine; the acceptance budget is < 0.5µs."""
    import time as _t

    from ray_tpu.devtools import chaos

    N = 100_000

    def loop():
        t0 = _t.perf_counter()
        for _ in range(N):
            if chaos.ENABLED:
                chaos.point("bench.hot")
        return (_t.perf_counter() - t0) / N * 1e6

    chaos.disable()
    loop()  # warm
    plan = chaos.ChaosPlan(seed=0, rules=[
        {"point": "bench.other", "action": "drop"}])
    off_t, on_t = [], []
    for _ in range(5):
        chaos.disable()
        off_t.append(loop())
        chaos.enable(plan)
        on_t.append(loop())
    chaos.disable()
    return {
        "chaos_overhead_us": max(0.0, min(on_t) - min(off_t)),
        "chaos_gate_us": min(off_t),
    }


# chaos_recovery_s child: a fixed retryable workload (5 waves x 12
# tasks, get() between waves) drained under the standard seeded kill
# plan: every exec flips a seeded 5% coin on SIGKILLing its worker.
# Probabilistic (not exec-count) timing matters: the worker pump
# batches completions, so a kill pinned to a fixed exec index inside
# the batch window would strike before ANY reply lands every
# generation — a livelock the chaos engine itself surfaced (the test
# suite pins exec-count kills deliberately; a recovery benchmark needs
# progress). max_retries is generous: one death charges every task of
# the dying worker's batch, and the arm measures recovery TIME, not
# retry frugality.
_CHAOS_RECOVERY_CHILD = r"""
import json, sys, time
import ray_tpu
waves = int(sys.argv[1])
t0 = time.perf_counter()
ray_tpu.init(num_cpus=4)

@ray_tpu.remote(max_retries=30)
def _c(i):
    import time as _t
    _t.sleep(0.02)
    return i

out = []
for wave in range(waves):
    refs = [_c.remote(wave * 12 + j) for j in range(12)]
    out.extend(ray_tpu.get(refs, timeout=600))
assert sorted(out) == list(range(waves * 12))
dt = time.perf_counter() - t0
ray_tpu.shutdown()
print(json.dumps({"recovery_s": dt}))
"""

CHAOS_RECOVERY_PLAN = {
    "seed": 42,
    "rules": [{"point": "worker.exec", "action": "kill", "prob": 0.05}],
}


def run_chaos_bench(quick: bool) -> dict[str, float]:
    import subprocess
    import tempfile

    out = _chaos_point_overhead_us()
    plan_path = os.path.join(tempfile.mkdtemp(prefix="rt_chaosb_"),
                             "plan.json")
    with open(plan_path, "w") as f:
        json.dump(CHAOS_RECOVERY_PLAN, f)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "RT_CHAOS_ENABLED": "1",
           "RT_CHAOS_PLAN": plan_path,
           "RT_CHAOS_LOG_DIR": plan_path + ".log"}
    waves = 2 if quick else 5  # quick mode shrinks the kill-churn arm
    try:
        proc = subprocess.run([sys.executable, "-c", _CHAOS_RECOVERY_CHILD,
                               str(waves)],
                              env=env, capture_output=True, text=True,
                              timeout=900)
    except subprocess.TimeoutExpired:
        # a wedged recovery child must not discard the overhead numbers
        # already measured above
        print("chaos recovery arm timed out", file=sys.stderr)
        return out
    if proc.returncode == 0:
        out["chaos_recovery_s"] = json.loads(
            proc.stdout.strip().splitlines()[-1])["recovery_s"]
    else:
        print(f"chaos recovery arm failed:\n{proc.stderr[-1500:]}",
              file=sys.stderr)
    return out


# serve data-plane child: a fixed request stream against a 2-replica
# deployment with the full FT stack enabled (retries, deadlines,
# hedging) — 8 closed-loop client threads, per-request latency sampled
# client-side. argv[2] picks the data-plane arm: "dataplane" = fast-lane
# router + adaptive (AIMD) batching under a 50ms SLO; "baseline" = RPC
# routing + fixed batch size (the pre-dataplane configuration, same
# handler). Run bare for serve_qps/serve_p99_ms; run under the
# checked-in seeded kill-replicas plan (tests/plans/) for
# serve_error_rate_chaos — the ROADMAP SLO sentence as a number.
_SERVE_BENCH_CHILD = r"""
import concurrent.futures, json, math, sys, time
import ray_tpu
from ray_tpu import serve

n_requests = int(sys.argv[1])
adaptive = sys.argv[2] == "dataplane"  # fastlane rides RT_SERVE_FASTLANE
ray_tpu.init(num_cpus=8)

@serve.deployment(num_replicas=2, max_ongoing_requests=16,
                  max_request_retries=4, request_timeout_s=60.0,
                  retry_on="*", hedge_after_ms=400.0,
                  latency_slo_ms=50.0 if adaptive else None)
class Echo:
    @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.0002)
    async def __call__(self, xs):
        return [x * 2 for x in xs]

handle = serve.run(Echo.bind(), name="bench")
for i in range(16):  # warm: routers, replicas, connections, lanes
    ray_tpu.get(handle.remote(i), timeout=60)

THREADS = 8
per = max(1, n_requests // THREADS)

def closed_loop(k):
    out = []
    for i in range(k):
        t0 = time.perf_counter()
        try:
            assert ray_tpu.get(handle.remote(i), timeout=120) == i * 2
            out.append(time.perf_counter() - t0)
        except Exception:
            out.append(None)  # counted as an error
    return out

t0 = time.perf_counter()
with concurrent.futures.ThreadPoolExecutor(max_workers=THREADS) as pool:
    outs = [f.result() for f in
            [pool.submit(closed_loop, per) for _ in range(THREADS)]]
wall = time.perf_counter() - t0
lat = sorted(v for o in outs for v in o if v is not None)
errs = sum(1 for o in outs for v in o if v is None)
total = THREADS * per
# nearest-rank percentile: ceil(0.99n)-1, NOT int(0.99n) (one rank
# high — degenerates to the max for n <= 100)
p99_ms = (lat[max(0, math.ceil(len(lat) * 0.99) - 1)] * 1e3
          if lat else -1.0)
from ray_tpu.serve.handle import _router_for
stats = _router_for("bench", "Echo").lane_stats()
serve.shutdown()
ray_tpu.shutdown()
print("RES=" + json.dumps({"qps": total / wall, "p99_ms": p99_ms,
                           "error_rate": errs / total,
                           "fast_calls": stats["fast_calls"],
                           "rpc_calls": stats["rpc_calls"]}))
"""

# autoscale-lag child: a load step against a scaled-to-min autoscaled
# deployment; the metric is the wall time from the first request of the
# step to the controller's target reaching the converged count — the
# "how long are users hurting before capacity arrives" number.
_SERVE_AUTOSCALE_CHILD = r"""
import json, threading, time
import ray_tpu
from ray_tpu import serve

ray_tpu.init(num_cpus=8)

@serve.deployment(max_ongoing_requests=4, max_request_retries=4,
                  retry_on="*", request_timeout_s=60.0,
                  autoscaling_config=dict(
                      min_replicas=1, max_replicas=3,
                      target_ongoing_requests=2.0,
                      upscale_delay_s=0.3, downscale_delay_s=1.0,
                      metrics_window_s=0.8, metrics_interval_s=0.2,
                      cooldown_s=1.0))
class Sluggish:
    def __call__(self, x):
        time.sleep(0.1)
        return x

handle = serve.run(Sluggish.bind(), name="lag")
ray_tpu.get(handle.remote(0), timeout=60)  # warm

stop = threading.Event()
def pound():
    while not stop.is_set():
        try:
            ray_tpu.get(handle.remote(1), timeout=60)
        except Exception:
            pass

t0 = time.perf_counter()
threads = [threading.Thread(target=pound, daemon=True) for _ in range(10)]
for t in threads:
    t.start()
lag = None
deadline = time.monotonic() + 60
while time.monotonic() < deadline:
    st = serve.status().get("lag", {}).get("Sluggish", {})
    if st.get("target_replicas", 1) >= 2:
        lag = time.perf_counter() - t0
        break
    time.sleep(0.05)
stop.set()
for t in threads:
    t.join(timeout=30)
serve.shutdown()
ray_tpu.shutdown()
print("RES=" + json.dumps({"lag_s": lag if lag is not None else -1.0}))
"""


def run_serve_bench(quick: bool) -> dict[str, float]:
    """Interleaved serve data-plane A/B (best-of over alternating
    rounds): `serve_qps`/`serve_p99_ms` with the full data plane on
    (fast-lane router + AIMD adaptive batching), `serve_qps_baseline`/
    `serve_p99_ms_baseline` with RPC routing + fixed batching — same
    handler, same 8-thread closed-loop client. Plus
    `serve_autoscale_lag_s` (load step -> target-replica convergence)
    and `serve_error_rate_chaos` (data plane under the seeded
    kill-replicas plan)."""
    import subprocess
    import tempfile

    root = os.path.dirname(os.path.abspath(__file__))
    out: dict[str, float] = {}

    def arm(n: int, env: dict, mode: str = "dataplane",
            child: str = _SERVE_BENCH_CHILD) -> dict | None:
        try:
            proc = subprocess.run(
                [sys.executable, "-c", child, str(n), mode],
                env=env, capture_output=True, text=True, timeout=900)
        except subprocess.TimeoutExpired:
            print("serve bench arm timed out", file=sys.stderr)
            return None
        if proc.returncode != 0:
            print(f"serve bench arm failed:\n{proc.stderr[-1500:]}",
                  file=sys.stderr)
            return None
        line = [ln for ln in proc.stdout.splitlines()
                if ln.startswith("RES=")]
        return json.loads(line[-1][4:]) if line else None

    n = 240 if quick else 800
    base_env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    rounds = 1 if quick else 3  # best-of interleaved (the r8 protocol)
    best: dict[str, dict] = {}
    for _ in range(rounds):  # interleaved A/B, best-of per arm
        for mode, env in (
                ("baseline", {**base_env, "RT_SERVE_FASTLANE": "0"}),
                ("dataplane", {**base_env, "RT_SERVE_FASTLANE": "1"})):
            res = arm(n, env, mode)
            if res is not None and (mode not in best
                                    or res["qps"] > best[mode]["qps"]):
                best[mode] = res
    if "dataplane" in best:
        out["serve_qps"] = best["dataplane"]["qps"]
        out["serve_p99_ms"] = best["dataplane"]["p99_ms"]
        out["serve_fast_calls"] = best["dataplane"]["fast_calls"]
    if "baseline" in best:
        out["serve_qps_baseline"] = best["baseline"]["qps"]
        out["serve_p99_ms_baseline"] = best["baseline"]["p99_ms"]

    res = arm(0, base_env, child=_SERVE_AUTOSCALE_CHILD)
    if res is not None and res.get("lag_s", -1) > 0:
        out["serve_autoscale_lag_s"] = res["lag_s"]

    plan = os.path.join(root, "tests", "plans", "serve_kill_replicas.json")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "RT_CHAOS_ENABLED": "1",
           "RT_CHAOS_PLAN": plan,
           "RT_CHAOS_LOG_DIR": tempfile.mkdtemp(prefix="rt_servb_")}
    res = arm(min(n, 480), env)
    if res is not None:
        out["serve_error_rate_chaos"] = res["error_rate"]
    return out


_TUNNEL_BENCH_CHILD = r"""
import json, os, subprocess, sys, tempfile, threading, time
import numpy as np
from ray_tpu.core import api as _api
from ray_tpu.core.core_client import CoreClient
from ray_tpu.utils import rpc as _rpc

mode = sys.argv[1]   # "tunnel" | "rpc" (RT_NODE_TUNNEL set by the parent)
n = int(sys.argv[2])

# two REAL raylet processes on this host (the forced-onto-the-tunnel
# topology): driver attaches to A, actors/workers land on B via the
# "bee" resource — every fast call crosses nodes
procs = []
addr_file = tempfile.mktemp(prefix="rt_tb_gcs_")
procs.append(subprocess.Popen(
    [sys.executable, "-m", "ray_tpu.core.gcs", "--address-file", addr_file],
    stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))
deadline = time.time() + 30
while not os.path.exists(addr_file) and time.time() < deadline:
    time.sleep(0.05)
gcs_host, gcs_port = open(addr_file).read().strip().rsplit(":", 1)
gcs_addr = (gcs_host, int(gcs_port))
sess = f"tb{os.getpid()}"

def spawn_raylet(tag, extra):
    p = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu.core.raylet",
         "--gcs", f"{gcs_host}:{gcs_port}", "--session", f"{sess}{tag}",
         *extra],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
    procs.append(p)
    line = p.stdout.readline()  # "raylet <id> on host:port"
    hp = line.strip().rsplit(" ", 1)[-1]
    host, port = hp.rsplit(":", 1)
    return (host, int(port))

addr_a = spawn_raylet("a", ["--num-cpus", "2"])
addr_b = spawn_raylet("b", ["--num-cpus", "4", "--resources", "bee=16"])

io = _rpc.EventLoopThread()
core = CoreClient(loop=io.loop)
io.run(core.connect(gcs_addr, addr_a))
_api._core = core

import atexit
def _cleanup():
    for p in procs[::-1]:
        try:
            p.terminate()
        except Exception:
            pass
atexit.register(_cleanup)

class Echo:
    def ping(self, i):
        return i

h = core.create_actor(Echo, (), {}, resources={"CPU": 0.5, "bee": 0.5})

def get(refs, timeout=180):
    # the public get: fast-lane refs resolve on THIS thread via
    # fast_prepass (no loop task per ref), exactly what users pay
    return _api.get(refs, timeout=timeout)

assert get([core.submit_actor_task(h, "ping", (0,), {})])[0] == 0
tmpl = core.actor_call_template(h.actor_id, "ping", 1, None)
deadline = time.time() + 15
while mode == "tunnel" and time.time() < deadline:
    lane = core._fast_actor_lanes.get(h.actor_id)
    if lane is not None and not lane.broken:
        break
    get([core.submit_actor_task(h, "ping", (0,), {}, _tmpl=tmpl)])
    time.sleep(0.1)

# warm both arms identically
get([core.submit_actor_task(h, "ping", (i,), {}, _tmpl=tmpl)
     for i in range(32)])

# burst arm: fire n, await all — the coalescing shape (one frame per
# burst window on the tunnel vs one pickled spec per call on RPC)
best_burst = 0.0
for _ in range(3):
    t0 = time.perf_counter()
    refs = [core.submit_actor_task(h, "ping", (i,), {}, _tmpl=tmpl)
            for i in range(n)]
    vals = get(refs)
    wall = time.perf_counter() - t0
    assert vals == list(range(n))
    best_burst = max(best_burst, n / wall)
# coalescing counters captured NOW: the closed-loop arm below sends
# singles by design and would dilute the burst-phase avg_batch
st_burst = core.tunnel_stats()

# threaded closed-loop arm (4 callers, the serve request shape)
per = max(1, n // 4)
def loop_arm(k):
    for i in range(k):
        assert get([core.submit_actor_task(h, "ping", (i,), {},
                                           _tmpl=tmpl)])[0] == i
t0 = time.perf_counter()
ths = [threading.Thread(target=loop_arm, args=(per,)) for _ in range(4)]
for t in ths: t.start()
for t in ths: t.join()
closed = (per * 4) / (time.perf_counter() - t0)

# cross-node batched pull: 64MB sealed on node B, adopted on A in one
# pull_objects round trip per batch
def produce(k):
    import numpy as np
    return np.ones(k, dtype=np.uint8)

chunks = 8
size = 64 * 1024 * 1024 // chunks
prefs = [core.submit_task(produce, (size,), {},
                          resources={"CPU": 1.0, "bee": 1.0})
         for _ in range(chunks)]
core._run_sync(core.wait_async(prefs, chunks, 180, False), 190)
t0 = time.perf_counter()
pvals = get(prefs, 180)
pull_wall = time.perf_counter() - t0
nbytes = sum(v.nbytes for v in pvals)
assert nbytes == chunks * size

st = core.tunnel_stats()
print("RES=" + json.dumps({
    "burst_calls_per_s": best_burst,
    "closed_calls_per_s": closed,
    "pull_gbps": nbytes / pull_wall / 1e9,
    "avg_batch": st_burst["avg_batch"],
    "tx_records": st["tx_records"],
    "tx_frames": st["tx_frames"],
}))
_api._core = None
try:
    io.run(core.close(), timeout=15)
except Exception:
    pass
io.stop()
_cleanup()
"""


def run_tunnel_bench(quick: bool) -> dict[str, float]:
    """Cross-node fast lane A/B (interleaved best-of): two raylets on
    one host, driver on A, actor + task workers on B — every fast call
    crosses nodes, so the node tunnel is the only fast lane in play.
    The baseline arm (RT_NODE_TUNNEL=0) takes the per-call RPC path.
    Emits ``tunnel_calls_per_s`` (+_rpc twin), the closed-loop twins,
    ``tunnel_coalesce_avg_batch`` and ``cross_node_pull_gbps``."""
    import subprocess

    out: dict[str, float] = {}
    n = 160 if quick else 600

    def arm(mode: str) -> dict | None:
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "RT_NODE_TUNNEL": "1" if mode == "tunnel" else "0"}
        try:
            proc = subprocess.run(
                [sys.executable, "-c", _TUNNEL_BENCH_CHILD, mode, str(n)],
                env=env, capture_output=True, text=True, timeout=600,
                cwd=os.path.dirname(os.path.abspath(__file__)))
        except subprocess.TimeoutExpired:
            print("tunnel bench arm timed out", file=sys.stderr)
            return None
        if proc.returncode != 0:
            print(f"tunnel bench arm failed:\n{proc.stderr[-1500:]}",
                  file=sys.stderr)
            return None
        line = [ln for ln in proc.stdout.splitlines()
                if ln.startswith("RES=")]
        return json.loads(line[-1][4:]) if line else None

    rounds = 1 if quick else 3  # best-of interleaved (the r8 protocol)
    best: dict[str, dict] = {}
    for _ in range(rounds):  # interleaved A/B, best-of per arm
        for mode in ("rpc", "tunnel"):
            res = arm(mode)
            if res is not None and (
                    mode not in best
                    or res["burst_calls_per_s"]
                    > best[mode]["burst_calls_per_s"]):
                best[mode] = res
    if "tunnel" in best:
        out["tunnel_calls_per_s"] = best["tunnel"]["burst_calls_per_s"]
        out["tunnel_closed_calls_per_s"] = \
            best["tunnel"]["closed_calls_per_s"]
        out["tunnel_coalesce_avg_batch"] = best["tunnel"]["avg_batch"]
        out["cross_node_pull_gbps"] = best["tunnel"]["pull_gbps"]
    if "rpc" in best:
        out["tunnel_calls_per_s_rpc"] = best["rpc"]["burst_calls_per_s"]
        out["tunnel_closed_calls_per_s_rpc"] = \
            best["rpc"]["closed_calls_per_s"]
    return out


_SHARDED_BENCH_CHILD = """
import json, os, time
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("RT_FORCE_CPU_DEVICES", "8")
import numpy as np, jax
jax.config.update("jax_platforms", "cpu")
from jax.sharding import NamedSharding, PartitionSpec as P
import ray_tpu
from ray_tpu.parallel.mesh import MeshSpec
from ray_tpu.sharded import telemetry

mb = int(os.environ.get("RT_SHARDED_MB", "128"))
ray_tpu.init(num_cpus=8)
mesh = MeshSpec(dp=4, tp=2).build()
rows = 4096
cols = max(1, mb * 1024 * 1024 // 4 // rows)
arr = np.random.randn(rows, cols).astype(np.float32)
garr = jax.device_put(arr, NamedSharding(mesh, P("dp", "tp")))
jax.block_until_ready(garr)
nbytes = arr.nbytes

telemetry.reset_counters()
t0 = time.perf_counter()
sref = ray_tpu.put_sharded(garr)
t_put = time.perf_counter() - t0
t0 = time.perf_counter()
out = ray_tpu.get_sharded(sref, mesh=mesh)
jax.block_until_ready(out)
t_get = time.perf_counter() - t0
del out
ray_tpu.reshard(sref, P("tp"), mesh=mesh)  # warm: compile the program
t0 = time.perf_counter()
r2 = ray_tpu.reshard(sref, P("tp"), mesh=mesh)  # steady state, jit cached
t_rs = time.perf_counter() - t0
c = telemetry.counters()
print("RES=" + json.dumps({
    "put_gbps": nbytes / t_put / 1e9,
    "get_gbps": nbytes / t_get / 1e9,
    "reshard_gbps": nbytes / t_rs / 1e9,
    "driver_bytes": c["driver_bytes"],
    "array_bytes": c["array_bytes"],
}))
ray_tpu.shutdown()
"""


def run_sharded_bench(quick: bool) -> dict[str, float]:
    """Sharded object plane arm: put/get/reshard throughput on a
    dp=4 x tp=2 mesh plus the driver-bytes counter that proves the
    zero-copy claim — driver traffic stays O(manifest) while the array
    bytes move through shm and the XLA collective."""
    import subprocess

    mb = 32 if quick else 128
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "RT_SHARDED_MB": str(mb)}
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _SHARDED_BENCH_CHILD], env=env,
            capture_output=True, text=True, timeout=600)
    except subprocess.TimeoutExpired:
        print("sharded bench arm timed out", file=sys.stderr)
        return {}
    if proc.returncode != 0:
        print(f"sharded bench arm failed:\n{proc.stderr[-1500:]}",
              file=sys.stderr)
        return {}
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RES=")]
    if not line:
        return {}
    res = json.loads(line[-1][4:])
    return {
        "sharded_put_gbps": res["put_gbps"],
        "sharded_get_gbps": res["get_gbps"],
        "reshard_gbps": res["reshard_gbps"],
        "sharded_driver_bytes": float(res["driver_bytes"]),
        "sharded_array_bytes": float(res["array_bytes"]),
    }


# placement-group churn child: a real GCS + N simulated raylet endpoints
# (ray_tpu.devtools.churn) joining/leaving on a seeded schedule while PG
# create/remove cyclers and persistent PG-bound sim actors run, with the
# checked-in seeded 2PC-fault plan (tests/plans/pg_churn.json) armed via
# the env. Emits the ROADMAP item-5 scheduling-scale-under-failure rows.
_PG_CHURN_CHILD = r"""
import json, sys
from ray_tpu.devtools.churn import ChurnHarness

nodes, dur = int(sys.argv[1]), float(sys.argv[2])
h = ChurnHarness(nodes=nodes, seed=7)
h.start()
try:
    m = h.run(duration_s=dur, pg_cyclers=4, persistent_pgs=8,
              bundles_per_pg=2, actors_per_pg=1, kill_every_s=0.8,
              min_nodes=max(4, nodes // 2))
    audit = h.audit()
    m["churn_leaked_bundles"] = len(audit["leaked"]) + len(audit["missing"])
    m["churn_nodes"] = nodes
finally:
    h.stop()
print("RES=" + json.dumps(m))
"""


def run_pg_churn_bench(quick: bool) -> dict[str, float]:
    """Simulated-churn arm (ROADMAP item 5): scheduling scale under
    failure as tracked numbers. Bounded node count + duration so the arm
    stays tier-2-safe under the suite ceiling; the same harness scales
    to hundreds of nodes off-CI."""
    import subprocess
    import tempfile

    nodes, dur = (32, 5.0) if quick else (96, 15.0)
    plan = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tests", "plans", "pg_churn.json")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "RT_CHAOS_ENABLED": "1",
           "RT_CHAOS_PLAN": plan,
           "RT_CHAOS_LOG_DIR": tempfile.mkdtemp(prefix="rt_pgchurn_")}
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _PG_CHURN_CHILD, str(nodes), str(dur)],
            env=env, capture_output=True, text=True, timeout=600)
    except subprocess.TimeoutExpired:
        print("pg churn arm timed out", file=sys.stderr)
        return {}
    if proc.returncode != 0:
        print(f"pg churn arm failed:\n{proc.stderr[-1500:]}",
              file=sys.stderr)
        return {}
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RES=")]
    if not line:
        return {}
    res = json.loads(line[-1][4:])
    return {
        "pg_create_removal_per_s": res["pg_create_removal_per_s"],
        "pg_reschedule_p50_ms": res["pg_reschedule_p50_ms"],
        "pg_reschedule_p99_ms": res["pg_reschedule_p99_ms"],
        "churn_unsatisfied_pg_s": res["churn_unsatisfied_pg_s"],
        "churn_node_kills": float(res["node_kills"]),
        "churn_leaked_bundles": float(res["churn_leaked_bundles"]),
        "churn_nodes": float(res["churn_nodes"]),
    }


def run_micro(window: float) -> dict[str, float]:
    import numpy as np

    import ray_tpu

    results: dict[str, float] = {}
    # host-condition marker: raw single-thread warm memcpy of 100MB. The
    # physical ceiling on this VM is ~20 GB/s; a low number means the
    # shared host is absorbing neighbor load and EVERY wall-clock metric
    # in this run is deflated accordingly — read ratios against it.
    src = np.zeros(100 * 1024 * 1024, dtype=np.uint8)
    dst = np.empty_like(src)
    best = 0.0
    for _ in range(4):
        t0 = time.perf_counter()
        np.copyto(dst, src)
        best = max(best, src.nbytes / (time.perf_counter() - t0) / 1e9)
    results["host_memcpy_gbps"] = best
    del src, dst

    ray_tpu.init(num_cpus=max(16, 2 * (os.cpu_count() or 8)))

    try:
        # ------------------------------------------------------ object plane
        small = {"k": 1}
        results["single_client_put_calls"] = timeit(
            lambda: ray_tpu.put(small), window=window
        )

        ref = ray_tpu.put(b"ok")
        results["single_client_get_calls"] = timeit(
            lambda: ray_tpu.get(ref), window=window
        )

        big = np.zeros(100 * 1024 * 1024, dtype=np.uint8)  # 100 MB
        results["single_client_put_gigabytes"] = timeit(
            lambda: ray_tpu.put(big), window=max(window, 2.0)
        ) * (big.nbytes / 1e9)

        def settle():
            # measurement hygiene on a 1-vCPU box: let ref-GC frees, spill
            # threads and idle-lease returns from the previous section
            # drain so they don't tax the next section's numbers
            import gc

            gc.collect()
            time.sleep(1.5)

        settle()

        # ------------------------------------------------------------- tasks
        @ray_tpu.remote
        def small_value():
            return b"ok"

        results["single_client_tasks_sync"] = timeit(
            lambda: ray_tpu.get(small_value.remote()), window=window
        )

        # flight-recorder per-stage breakdown of the sync round trips
        # just measured (submit-ring hop / deserialize / exec / reply
        # hop / total — read HERE so the window holds lone round trips,
        # not the 1000-deep pipelined burst below whose queueing delay
        # would swamp every stage), read back through the state API it
        # ships on — proving recorder -> GCS -> list_task_latency end
        # to end
        results.update(_stage_latency_results())

        def batch_tasks(n=1000):
            ray_tpu.get([small_value.remote() for _ in range(n)])

        results["single_client_tasks_async"] = timeit(
            batch_tasks, window=max(window, 2.0), multiplier=1000
        )

        # Driver-side CPU time per steady-state .remote() (PR 2): the
        # noise-immune counter for the submit hot path — thread_time is
        # CPU time, so neighbor load on this shared VM mostly cancels.
        # Median of 5 in-process windows. Window size: thread_time on
        # this host advances in 10ms quanta, so each window must span
        # MANY ticks — 1600 calls x >=100us is >=16 ticks (<=6%
        # quantization), while staying under the 4096 ring inflight cap
        # so every call exercises the same submit path.
        import statistics

        ray_tpu.get([small_value.remote() for _ in range(100)])  # steady
        cpu_samples = []
        for _ in range(5):
            refs = []
            t0 = time.thread_time()
            for _ in range(1600):
                refs.append(small_value.remote())
            dt = time.thread_time() - t0
            cpu_samples.append(dt / 1600 * 1e6)
            ray_tpu.get(refs)
        results["submit_cpu_us_per_call"] = statistics.median(cpu_samples)

        # coalesced-flush stats: how many submit records rode each native
        # batch push (1.0 = no coalescing engaged)
        from ray_tpu.core import api as _core_api

        flush = _core_api.get_core().fast_flush_stats()
        results["fastpath_flush_avg_batch"] = flush["avg_batch"]

        settle()

        @ray_tpu.remote
        def task_fanout(n):
            import ray_tpu as rt

            rt.get([small_value.remote() for _ in range(n)])
            return 0

        def multi_client(n=500, clients=4):
            ray_tpu.get([task_fanout.remote(n) for _ in range(clients)])

        results["multi_client_tasks_async"] = timeit(
            multi_client, window=max(window, 2.0), multiplier=2000
        )

        settle()

        # ------------------------------------------------------------ actors
        @ray_tpu.remote(num_cpus=0)
        class Actor:
            def small_value(self):
                return b"ok"

        a = Actor.remote()
        ray_tpu.get(a.small_value.remote())
        results["1_1_actor_calls_sync"] = timeit(
            lambda: ray_tpu.get(a.small_value.remote()), window=window
        )

        # actor-call stage breakdown of the lone sync round trips just
        # measured (ROADMAP item 1: actor stages in the flight recorder
        # like tasks) — read here, before the pipelined bursts below
        # whose queueing delay would swamp every stage
        results.update(_stage_latency_results(prefix="actor_"))

        def actor_batch(n=500):
            ray_tpu.get([a.small_value.remote() for _ in range(n)])

        results["1_1_actor_calls_async"] = timeit(
            actor_batch, window=max(window, 2.0), multiplier=500
        )

        n_servers = 4
        servers = [Actor.remote() for _ in range(n_servers)]
        ray_tpu.get([s.small_value.remote() for s in servers])

        def one_n(n=250):
            refs = []
            for s in servers:
                refs.extend(s.small_value.remote() for _ in range(n))
            ray_tpu.get(refs)

        results["1_n_actor_calls_async"] = timeit(
            one_n, window=max(window, 2.0), multiplier=250 * n_servers
        )

        @ray_tpu.remote(num_cpus=0)
        class Client:
            def __init__(self, server):
                self.server = server

            def batch(self, n):
                import ray_tpu as rt

                rt.get([self.server.small_value.remote() for _ in range(n)])

        clients = [Client.remote(s) for s in servers]

        def n_n(n=250):
            ray_tpu.get([c.batch.remote(n) for c in clients])

        results["n_n_actor_calls_async"] = timeit(
            n_n, window=max(window, 2.0), multiplier=250 * n_servers
        )

        @ray_tpu.remote(num_cpus=0, max_concurrency=8)
        class AsyncActor:
            async def small_value(self):
                return b"ok"

        aa = AsyncActor.remote()
        ray_tpu.get(aa.small_value.remote())
        results["1_1_async_actor_calls_sync"] = timeit(
            lambda: ray_tpu.get(aa.small_value.remote()), window=window
        )

        def async_actor_batch(n=500):
            ray_tpu.get([aa.small_value.remote() for _ in range(n)])

        results["1_1_async_actor_calls_async"] = timeit(
            async_actor_batch, window=max(window, 2.0), multiplier=500
        )

        # ------------------------------------------------------------- wait
        refs_1k = [ray_tpu.put(i) for i in range(1000)]

        def wait_1k():
            ray_tpu.wait(refs_1k, num_returns=len(refs_1k))

        results["single_client_wait_1k_refs"] = timeit(wait_1k, window=window)

        # -------------------------------------------------- placement groups
        def pg_cycle():
            pg = ray_tpu.placement_group([{"CPU": 1}], strategy="PACK")
            pg.ready(timeout=5)
            ray_tpu.remove_placement_group(pg)

        results["placement_group_create_removal"] = timeit(pg_cycle, window=window)
    finally:
        ray_tpu.shutdown()
    return results


def _tpu_peak_flops(device) -> float | None:
    kind = getattr(device, "device_kind", "").lower()
    for key, flops in sorted(TPU_PEAK_FLOPS.items(), key=lambda kv: -len(kv[0])):
        if key in kind:
            return flops
    if "tpu" in kind or device.platform == "tpu":
        raise ValueError(
            f"no peak FLOP/s known for TPU device_kind {device.device_kind!r}: "
            f"add it to TPU_PEAK_FLOPS with its source, do not guess")
    return None


def run_model(quick: bool) -> dict:
    """Single-chip Llama train step: tokens/s and MFU, attn_impl='auto' so the
    Pallas flash kernel is on the measured path (VERDICT r1 #3)."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.llama import LlamaConfig, llama_init, llama_loss

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    peak = _tpu_peak_flops(dev)

    if on_tpu and not quick:
        cfg = LlamaConfig(
            vocab_size=32_000,
            d_model=1536,
            n_layers=12,
            n_heads=12,
            n_kv_heads=12,
            d_ff=6144,
            max_seq_len=8192,
            dtype="bfloat16",
        )
        seqs = [512, 2048, 8192]
        tokens_per_step = 16_384
        steps = 10
    else:  # CPU smoke shape
        cfg = LlamaConfig(
            vocab_size=512,
            d_model=128,
            n_layers=2,
            n_heads=4,
            n_kv_heads=4,
            d_ff=256,
            max_seq_len=1024,
            dtype="float32",
        )
        seqs = [256]
        tokens_per_step = 512
        steps = 3

    optimizer = optax.adamw(1e-4)
    n_params = None

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: llama_loss(p, {"tokens": tokens}, cfg, mesh=None, attn_impl="auto")
        )(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    out = {"device": getattr(dev, "device_kind", str(dev)),
           "platform": dev.platform, "seq": {}, "flagship": {}}
    configs = [(None, cfg, T, max(1, tokens_per_step // T)) for T in seqs]
    if on_tpu and not quick:
        # flagship scale: a TinyLlama-class ~1.26B model on the single
        # chip (VERDICT r3 #7 — the parallelism/perf claims need a >=1B
        # anchor, not just the 551M sweep model)
        flagship = LlamaConfig(
            vocab_size=32_000, d_model=2048, n_layers=22, n_heads=16,
            n_kv_heads=16, d_ff=5632, max_seq_len=2048, dtype="bfloat16")
        configs.append(("flagship_1b", flagship, 2048, 2))
    for label, cfg, T, B in configs:
        # fresh state + executable per shape: carrying donated buffers and
        # stale executables across differently-shaped sweeps costs HBM and
        # measured T=8192 6x slower than the same config run clean
        params = llama_init(jax.random.PRNGKey(0), cfg)
        cfg_params = sum(x.size for x in jax.tree.leaves(params))
        if n_params is None:
            n_params = cfg_params
        opt_state = optimizer.init(params)
        jit_step = jax.jit(step, donate_argnums=(0, 1))
        toks = jax.random.randint(
            jax.random.PRNGKey(1), (B, T + 1), 0, cfg.vocab_size, dtype=jnp.int32
        )
        import numpy as np

        def fence(params, loss):
            # device→host copies as the completion fence: a d2h read of
            # the *last* update's outputs cannot return before the work
            # that produces them has run.
            np.asarray(loss)
            np.asarray(jax.tree.leaves(params)[0]).ravel()[0]

        params, opt_state, loss = jit_step(params, opt_state, toks)  # compile
        fence(params, loss)
        start = time.perf_counter()
        for _ in range(steps):
            params, opt_state, loss = jit_step(params, opt_state, toks)
        fence(params, loss)
        dt = (time.perf_counter() - start) / steps
        del params, opt_state
        jax.clear_caches()
        tok_s = B * T / dt
        # train FLOPs/token ≈ 6N (matmuls, fwd+bwd) + 6·L·d_model·T (causal
        # attention scores fwd+bwd) — the scaling-book accounting.
        flops_per_token = 6 * cfg_params + 6 * cfg.n_layers * cfg.d_model * T
        entry = {"tokens_per_s": tok_s, "step_ms": dt * 1e3,
                 "loss": float(loss), "params": cfg_params}
        if peak:
            entry["mfu_pct"] = 100.0 * tok_s * flops_per_token / peak
        out["seq" if label is None else "flagship"][
            str(T) if label is None else label] = entry
    out["params"] = n_params
    return out


def run_llm_engine(quick: bool) -> dict:
    """Continuous-batching engine decode throughput (the owned vLLM-role
    engine): N concurrent requests share the paged-KV decode batch."""
    import asyncio

    import jax

    from ray_tpu.llm.engine import ContinuousBatchingEngine
    from ray_tpu.models.llama import LlamaConfig, llama_init

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if on_tpu and not quick:
        cfg = LlamaConfig(vocab_size=32_000, d_model=1024, n_layers=8,
                          n_heads=8, n_kv_heads=8, d_ff=4096,
                          max_seq_len=2048, dtype="bfloat16")
        # batch 64 is this chip's sweet spot (r5 sweep: 16→3.4k, 32→7.9k,
        # 64→15.3k, 128→10.7k tok/s — decode is weight-bandwidth-bound up
        # to 64 slots, past that the page-table attention gather wins)
        max_batch, max_tokens, n_req = 64, 64, 192
        # KV sized to the workload (prompt 64 + 64 generated = 128 < 160);
        # oversizing max_seq_len pads every decode step's attention reads
        page_size, n_pages, max_seq = 32, 1024, 160
        prompt_len = 64
    else:
        cfg = LlamaConfig(vocab_size=512, d_model=128, n_layers=2, n_heads=4,
                          n_kv_heads=4, d_ff=256, max_seq_len=512,
                          dtype="float32")
        max_batch, max_tokens, n_req = 4, 12, 8
        page_size, n_pages, max_seq = 16, 128, 128
        prompt_len = 16
    params = llama_init(jax.random.PRNGKey(0), cfg)
    import numpy as np

    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, cfg.vocab_size, prompt_len))
               for _ in range(n_req)]

    async def go(kv_dtype, mb, reqs):
        eng = ContinuousBatchingEngine(
            params, cfg, max_batch=mb, page_size=page_size,
            n_pages=n_pages, max_seq_len=max_seq, max_waiting=1024,
            kv_dtype=kv_dtype)
        await eng.start()
        # warm run: compiles prefill buckets + every decode block bucket
        # the measured run will use (first-compile is ~20s/program here)
        await asyncio.gather(
            *[eng.generate(p, max_tokens=max_tokens) for p in reqs])
        best = 0.0
        for _ in range(2):
            tokens0 = eng.tokens_out
            t0 = time.perf_counter()
            await asyncio.gather(
                *[eng.generate(p, max_tokens=max_tokens) for p in reqs])
            dt = time.perf_counter() - t0
            best = max(best, (eng.tokens_out - tokens0) / dt)
        await eng.stop()
        return best

    rate = asyncio.run(go(None, max_batch, prompts))
    out = {
        "device": getattr(dev, "device_kind", str(dev)),
        "platform": dev.platform,
        "concurrent_requests": n_req,
        "max_batch": max_batch,
        "decode_tokens_per_s": rate,
    }
    if on_tpu and not quick:
        # int8 KV halves the page-table gather bytes — the bottleneck
        # that capped bf16 at batch 64 — so its knee sits at 128 slots
        # (r5 sweep: int8 64→10.5k, 128→18.4k, 256→14.3k tok/s vs bf16
        # 64→5.8k, 128→9.7k same-session)
        prompts2 = [list(rng.integers(1, cfg.vocab_size, prompt_len))
                    for _ in range(2 * n_req)]
        out["decode_tokens_per_s_int8kv"] = asyncio.run(
            go("int8", 128, prompts2))
        out["int8kv_max_batch"] = 128
        out["int8kv_concurrent_requests"] = len(prompts2)
    return out


_SPEC_BENCH_CHILD = r"""
import asyncio, json, sys, time

import jax

from ray_tpu.llm.engine import ContinuousBatchingEngine
from ray_tpu.models.llama import LlamaConfig, llama_init

quick = sys.argv[1] == "1"
# Acceptance-friendly long-generation workload: constant-token prompts
# at the model's own greedy attractors ([2]*64 / [39]*64 stay period-1
# for the whole horizon under PRNGKey(0) weights — the highly
# repetitive continuation the prompt-lookup drafter exists for). Long
# generations over a near-full 512-token window put the decode in the
# page-table-gather-bound regime, where one fused multi-position
# verify amortizes the window read over k+1 positions — the
# speculative win that survives even on a compute-heavy CPU backend.
# (Mixed spec/plain/wandering batches are covered by tier-1 parity
# tests; low-acceptance workloads decay toward the plain engine's rate
# since rejected steps still emit the target's own token.)
cfg = LlamaConfig(vocab_size=512, d_model=128, n_layers=2, n_heads=4,
                  n_kv_heads=4, d_ff=256, max_seq_len=1024,
                  dtype="float32")
params = llama_init(jax.random.PRNGKey(0), cfg)
prompts = [[2] * 64, [39] * 64] * 4
MT = 192 if quick else 384


def make_engine(spec):
    return ContinuousBatchingEngine(
        params, cfg, max_batch=8, page_size=16, n_pages=512,
        max_seq_len=512, spec_enable=spec, spec_k=6)


async def go():
    engines = {"plain": make_engine(False), "spec": make_engine(True)}
    for eng in engines.values():
        await eng.start()
        # warm: compiles every decode/spec block bucket the run uses
        await asyncio.gather(
            *[eng.generate(p, max_tokens=32) for p in prompts])
    spec_eng = engines["spec"]
    # measured-rounds-only counter baseline (warmup excluded; lifetime
    # counters, not the bounded block deque — long runs overflow it)
    base = (spec_eng.tokens_out, spec_eng.spec_steps,
            spec_eng.spec_proposed, spec_eng.spec_accepted)
    best = {"plain": 0.0, "spec": 0.0}
    for _ in range(2 if quick else 3):  # interleaved best-of rounds
        for name, eng in engines.items():
            t0 = eng.tokens_out
            w0 = time.perf_counter()
            await asyncio.gather(
                *[eng.generate(p, max_tokens=MT) for p in prompts])
            best[name] = max(best[name],
                             (eng.tokens_out - t0)
                             / (time.perf_counter() - w0))
    d_tok = spec_eng.tokens_out - base[0]
    d_steps = max(1, spec_eng.spec_steps - base[1])
    d_prop = max(1, spec_eng.spec_proposed - base[2])
    d_acc = spec_eng.spec_accepted - base[3]
    B = spec_eng.B
    for eng in engines.values():
        await eng.stop()
    return {
        "spec_tok_s": best["spec"],
        "spec_tok_s_plain": best["plain"],
        "spec_speedup": best["spec"] / max(1e-9, best["plain"]),
        "spec_accept_rate": d_acc / d_prop,
        # batch-average emitted tokens per spec step per slot over the
        # measured rounds (tail/ramp effects included)
        "spec_tokens_per_step": d_tok / d_steps / B,
        "spec_k": 6,
    }

print("RES=" + json.dumps(asyncio.run(go())))
"""


def _run_llm_child(child_src: str, label: str, quick: bool,
                   extra_args: tuple = ()) -> dict:
    """Shared runner for the LLM bench children (disagg/spec/serve-llm):
    one CPU-pinned subprocess, a RES= json line out, failures logged
    and swallowed so one arm can't sink the others."""
    import subprocess

    try:
        proc = subprocess.run(
            [sys.executable, "-c", child_src, "1" if quick else "0",
             *extra_args],
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=1800)
    except subprocess.TimeoutExpired:
        print(f"{label} bench arm timed out", file=sys.stderr)
        return {}
    if proc.returncode != 0:
        print(f"{label} bench arm failed:\n{proc.stderr[-1500:]}",
              file=sys.stderr)
        return {}
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RES=")]
    return json.loads(line[-1][4:]) if line else {}


def run_spec_bench(quick: bool) -> dict:
    """Speculative-decoding A/B (ROADMAP item 4): the SAME engine with
    spec off vs on (on-device n-gram drafter + fused multi-position
    verify inside the scan), interleaved best-of rounds in a
    subprocess. Greedy outputs are token-identical by construction
    (tier-1 asserts it); the A/B measures the tokens/s multiplier and
    reports the accept rate beside it."""
    return _run_llm_child(_SPEC_BENCH_CHILD, "spec", quick)


_SERVE_LLM_BENCH_CHILD = r"""
import concurrent.futures, json, sys, time

import numpy as np

import ray_tpu
from ray_tpu import serve
from ray_tpu.llm.disagg.scheduler import build_disagg_deployment
from ray_tpu.models.llama import LlamaConfig

quick = sys.argv[1] == "1"
# serve item 2 composition at real QPS: router -> prefill pool -> KV
# plane -> TWO decode replicas, closed-loop load with a shared prefix
# (the prefix cache serves the suffix-only path) — the full L5-L7
# decode path end to end through the serve data plane.
cfg = LlamaConfig(vocab_size=512, d_model=128, n_heads=4, n_layers=2,
                  n_kv_heads=4, d_ff=256, max_seq_len=512, dtype="float32")
PS = 8
rng = np.random.default_rng(7)
shared = list(map(int, rng.integers(1, cfg.vocab_size, 4 * PS)))
n_requests = 48 if quick else 120
CLIENTS = 8

ray_tpu.init(num_cpus=8)
app = build_disagg_deployment(
    cfg, n_prefill=1, n_decode=2, max_batch=8, page_size=PS,
    n_pages=256, max_seq_len=256, max_wave=8, wave_wait_s=0.004,
    max_ongoing_requests=32, spec_enable=True, spec_k=4)
handle = serve.run(app, name="llmbench")


def one(i):
    toks = shared + [int(100 + i % 17), int(200 + i % 13)]
    t0 = time.perf_counter()
    r = ray_tpu.get(handle.remote({"prompt_tokens": toks,
                                   "max_tokens": 8}), timeout=120)
    assert len(r["completion_tokens"]) == 8
    return time.perf_counter() - t0


for i in range(8):  # warm: compiles + prefix cache + routers + lanes
    one(i)

per = max(1, n_requests // CLIENTS)


def client(_):
    lats = []
    errs = 0
    for i in range(per):
        try:
            lats.append(one(i))
        except Exception:
            errs += 1
    return lats, errs

t0 = time.perf_counter()
with concurrent.futures.ThreadPoolExecutor(max_workers=CLIENTS) as pool:
    outs = [f.result() for f in [pool.submit(client, c)
                                 for c in range(CLIENTS)]]
wall = time.perf_counter() - t0
done = sum(len(o[0]) for o in outs)
errs = sum(o[1] for o in outs)
st = ray_tpu.get(handle.stats.remote(), timeout=60)
lat = st["kv_plane"]  # pool-wide ledger incl. spec counters
out = {
    "serve_llm_qps": done / wall,
    "serve_llm_errors": errs,
    "serve_llm_decode_tokens": st["decode_tokens"],
    "serve_llm_hit_rate": st["prefix_cache"]["hit_rate"],
    "serve_llm_spec_steps": lat.get("spec_steps", 0),
}
# TTFT/TPOT percentiles from the scheduler replica's stage windows,
# fetched THROUGH the deployment (the windows live in its process)
for key, vals in (ray_tpu.get(handle.stage_windows.remote(),
                              timeout=60) or {}).items():
    vals = sorted(vals)
    if vals:
        from ray_tpu.utils.recorder import percentile

        out[f"serve_llm_{key}_p50_ms"] = percentile(vals, 0.5) / 1e6
        out[f"serve_llm_{key}_p99_ms"] = percentile(vals, 0.99) / 1e6
print("RES=" + json.dumps(out))
ray_tpu.shutdown()
"""


def run_serve_llm_bench(quick: bool) -> dict:
    """Serve-driven disagg QPS arm (ROADMAP items 2+4 composed): the
    LLM decode pools driven through the serve data plane at closed-loop
    load — router -> prefill -> 2 decode replicas — reporting
    `serve_llm_qps`, TTFT/TPOT percentiles from the same stage windows
    the autoscaler reads, and the per-replica decode token counters
    that prove BOTH rings carried traffic."""
    return _run_llm_child(_SERVE_LLM_BENCH_CHILD, "serve-llm", quick)


_SERVE_LLM_STREAM_CHILD = r"""
import json, sys, time

import numpy as np

import ray_tpu
from ray_tpu import serve
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.utils.recorder import percentile

quick = sys.argv[1] == "1"
ray_tpu.init(num_cpus=8)


# --- wire-plane chunk overhead, no LLM noise: ONE deployment streaming
# N small chunks ("G" records on the ring) vs returning the same N as a
# single unary list — per-chunk overhead = (stream - unary) / N.
@serve.deployment(num_replicas=1)
class Chunks:
    def gen(self, n):
        for i in range(n):
            yield i

    def unary(self, n):
        return list(range(n))


h = serve.run(Chunks.bind(), name="chunks")
N = 256 if quick else 512
for _ in range(3):  # warm: lanes, stream sinks, reply pump
    assert list(h.gen.stream_chunks(N))[-1] == N - 1
    ray_tpu.get(h.unary.remote(N), timeout=60)
best_s = best_u = float("inf")
for _ in range(5):  # interleaved best-of: same host weather both arms
    t0 = time.perf_counter()
    xs = list(h.gen.stream_chunks(N))
    best_s = min(best_s, time.perf_counter() - t0)
    assert len(xs) == N
    t0 = time.perf_counter()
    ray_tpu.get(h.unary.remote(N), timeout=60)
    best_u = min(best_u, time.perf_counter() - t0)
chunk_overhead_us = (best_s - best_u) / N * 1e6
serve.delete("chunks")

# --- LLM streaming A/B against the aggregated engine deployment:
# stream_deltas (one "G" chunk per fused decode block) interleaved
# with the unary __call__ on the SAME prompts — token identity is
# asserted per pair; TTFC is measured client-side beside a unary
# max_tokens=1 request (the externally observable TTFT: routing +
# prefill + one block for both).
from ray_tpu.llm.serving import build_llm_engine_deployment

cfg = LlamaConfig(vocab_size=512, d_model=128, n_heads=4, n_layers=2,
                  n_kv_heads=4, d_ff=256, max_seq_len=256,
                  dtype="float32")
app = build_llm_engine_deployment(cfg, max_batch=8, page_size=8,
                                  n_pages=128, max_seq_len=256)
lh = serve.run(app, name="sllm")
rng = np.random.default_rng(7)
prompts = [[int(x) for x in rng.integers(1, 500, 12)]
           for _ in range(12 if quick else 24)]
MT = 24
for p in prompts[:2]:  # warm: prefill/decode compiles, stream path
    ray_tpu.get(lh.remote({"prompt_tokens": p, "max_tokens": MT}),
                timeout=300)
    list(lh.stream_deltas.stream_chunks(
        {"prompt_tokens": p, "max_tokens": MT}))

ttfc, gaps, ttft1, identical = [], [], [], 0
n_chunks = 0
for p in prompts:
    req = {"prompt_tokens": p, "max_tokens": MT}
    ref = ray_tpu.get(lh.remote(dict(req)),
                      timeout=300)["completion_tokens"]
    t0 = time.perf_counter()
    ray_tpu.get(lh.remote({"prompt_tokens": p, "max_tokens": 1}),
                timeout=300)
    ttft1.append(time.perf_counter() - t0)
    toks = []
    t0 = last = time.perf_counter()
    for d in lh.stream_deltas.stream_chunks(dict(req)):
        now = time.perf_counter()
        if not toks:
            ttfc.append(now - t0)
        elif d["tokens"]:
            gaps.append(now - last)
        last = now
        toks += list(d["tokens"])
        n_chunks += 1
    identical += toks == ref

assert identical == len(prompts), (identical, len(prompts))
out = {
    "serve_stream_chunk_overhead_us": chunk_overhead_us,
    "serve_stream_chunks_per_req": n_chunks / len(prompts),
    "serve_stream_tokens_identical": identical,
    "serve_stream_ttfc_p50_ms": percentile(sorted(ttfc), 0.5) * 1e3,
    "serve_stream_ttfc_p99_ms": percentile(sorted(ttfc), 0.99) * 1e3,
    "serve_stream_gap_p50_ms": percentile(sorted(gaps), 0.5) * 1e3,
    "serve_stream_gap_p99_ms": percentile(sorted(gaps), 0.99) * 1e3,
    "serve_stream_unary_ttft1_p50_ms": percentile(sorted(ttft1),
                                                  0.5) * 1e3,
}
out["serve_stream_ttfc_vs_ttft1"] = (
    out["serve_stream_ttfc_p50_ms"]
    / max(1e-9, out["serve_stream_unary_ttft1_p50_ms"]))
print("RES=" + json.dumps(out))
ray_tpu.shutdown()
"""


def run_serve_llm_streaming(quick: bool) -> dict:
    """Streaming serve arm (ROADMAP item 2 acceptance): token deltas as
    "G" chunk records end to end. Reports client-side TTFC p50/p99
    beside a unary max_tokens=1 TTFT proxy (acceptance: ratio ~1),
    inter-chunk gap percentiles, per-chunk wire overhead from a
    no-LLM stream-vs-unary interleaved A/B, and asserts every streamed
    completion token-identical to its unary twin."""
    return _run_llm_child(_SERVE_LLM_STREAM_CHILD, "serve-llm-stream",
                          quick)


_DISAGG_BENCH_CHILD = r"""
import asyncio, json, sys, time

import numpy as np

import ray_tpu
from ray_tpu.llm.disagg import telemetry as dtel
from ray_tpu.llm.disagg.scheduler import DisaggLLMServer
from ray_tpu.llm.engine import ContinuousBatchingEngine
from ray_tpu.models.llama import LlamaConfig, llama_init
from ray_tpu.utils.recorder import percentile

quick = sys.argv[1] == "1"
# Prefill-heavy shared-prefix traffic — the disaggregation regime: a
# 384-token shared system prompt (24 full pages at PS=16) + mixed-length
# user tails. The aggregated engine recomputes the shared prefix per
# request; the disagg stack prefills it once and serves the rest from
# the prefix cache. The model is sized so prefill FLOPs dominate the
# per-request RPC/ship overheads (the production-shaped ratio).
cfg = LlamaConfig(vocab_size=512, d_model=256, n_layers=4, n_heads=8,
                  n_kv_heads=4, d_ff=512, max_seq_len=512, dtype="float32")
PS, n_pages, max_seq, max_batch = 16, 256, 512, 8
max_tokens = 8
n_req = 12 if quick else 24
rng = np.random.default_rng(7)
shared = list(map(int, rng.integers(1, cfg.vocab_size, 24 * PS)))
prompts = []
for i in range(n_req):  # mixed lengths: every 3rd tail is 8x longer
    tail = list(map(int, rng.integers(
        1, cfg.vocab_size, 4 * PS if i % 3 == 0 else PS // 2)))
    prompts.append(shared + tail)


class _AggLLM:
    # the aggregated baseline: ONE engine doing prefill AND decode
    def __init__(self, model_config):
        from ray_tpu.utils.device import configure_jax

        configure_jax()
        import jax

        params = llama_init(jax.random.PRNGKey(0), model_config)
        self.engine = ContinuousBatchingEngine(
            params, model_config, max_batch=max_batch, page_size=PS,
            n_pages=n_pages, max_seq_len=max_seq, max_waiting=1024)

    async def generate(self, prompt, mt):
        await self.engine.start()
        return await self.engine.generate(list(prompt), max_tokens=mt,
                                          temperature=0.0)


ray_tpu.init(num_cpus=8)
agg = ray_tpu.remote(_AggLLM).options(max_concurrency=64).remote(cfg)
dis = DisaggLLMServer(cfg, n_prefill=2, n_decode=2, max_batch=max_batch,
                      page_size=PS, n_pages=n_pages, max_seq_len=max_seq,
                      max_wave=8, wave_wait_s=0.004)


async def agg_round():
    t0 = time.perf_counter()
    outs = await asyncio.gather(
        *(agg.generate.remote(p, max_tokens) for p in prompts))
    return sum(len(o) for o in outs) / (time.perf_counter() - t0)


async def dis_round():
    t0 = time.perf_counter()
    outs = await asyncio.gather(
        *(dis({"prompt_tokens": p, "max_tokens": max_tokens})
          for p in prompts))
    return sum(len(o["completion_tokens"]) for o in outs) / (
        time.perf_counter() - t0)


async def go():
    # warm both arms to steady state: each round hits fresh pad-bucket
    # jit compiles (full-prefill, suffix, decode block shapes) and the
    # disagg arm needs a hot prefix cache — one round is NOT enough
    for _ in range(2 if quick else 3):
        await agg_round()
        await dis_round()
    best_a = best_d = 0.0
    for _ in range(2):  # interleaved: same host weather for both arms
        best_a = max(best_a, await agg_round())
        best_d = max(best_d, await dis_round())
    stats = await dis.stats()
    await dis.shutdown()
    return best_a, best_d, stats


best_a, best_d, stats = asyncio.run(go())
import jax

out = {
    "disagg_platform": jax.devices()[0].platform,
    "llm_agg_tokens_per_s": best_a,
    "llm_disagg_tokens_per_s": best_d,
    "prefix_cache_hit_rate": stats["prefix_cache"]["hit_rate"],
    "kv_ship_driver_bytes": stats["kv_plane"]["kv_driver_bytes"],
    "kv_ship_array_bytes": stats["kv_plane"]["kv_array_bytes"],
    "disagg_requests": stats["requests"],
}
for stage, key in ((dtel.TTFT, "ttft"), (dtel.TPOT, "tpot")):
    win = sorted(dtel.stage_window(stage))
    if win:
        out[key + "_p50_ms"] = percentile(win, 0.5) / 1e6
        out[key + "_p99_ms"] = percentile(win, 0.99) / 1e6
ray_tpu.shutdown()
print("RES=" + json.dumps(out))
"""


def run_disagg_bench(quick: bool) -> dict:
    """Disaggregated vs aggregated LLM serving A/B under a mixed
    prompt-length, shared-prefix load (ROADMAP item 4; the DistServe
    composition over the KV-page plane). Interleaved best-of rounds in a
    subprocess; TTFT/TPOT percentiles come straight from the scheduler's
    flight-recorder stage windows, the byte ledger from the pool-wide
    kv_plane counters."""
    return _run_llm_child(_DISAGG_BENCH_CHILD, "disagg", quick)


_TIERING_BENCH_CHILD = r"""
import asyncio, json, sys, time

import numpy as np

import ray_tpu
from ray_tpu.config import get_config
from ray_tpu.llm.disagg.scheduler import DisaggLLMServer
from ray_tpu.models.llama import LlamaConfig

quick = sys.argv[1] == "1"
# All arms (5x spill/drop A/B + the 2x/10x sweep) run in THIS one
# driver: pool leases flow back between arms now that unreferenced
# actors are auto-killed and shutdown() kills its pools explicitly —
# the per-factor subprocess isolation the sweep used to need is gone.
# The r9 disagg model/page shape, but the workload is G distinct
# shared-prefix tenants whose combined radix-tree working set is held
# 2x/5x/10x ABOVE the prefix-cache arena budget. Every round replays
# every tenant: the spill arm keeps evicted prefixes on tier-1 and
# restores them through the batched pull path; the drop arm (tiering
# off) re-prefills each evicted tenant from scratch.
cfg = LlamaConfig(vocab_size=512, d_model=256, n_layers=4, n_heads=8,
                  n_kv_heads=4, d_ff=512, max_seq_len=512, dtype="float32")
PS, n_pages, max_seq, max_batch = 16, 256, 512, 8
PREFIX_PAGES = 24  # 384-token shared system prompt per tenant
G = 4 if quick else 8
rng = np.random.default_rng(18)
tenants = [list(map(int, rng.integers(1, cfg.vocab_size, PREFIX_PAGES * PS)))
           for _ in range(G)]
# fixed tails: every round replays the identical request set so prefix
# pages can hit across rounds
tails = {(i, j): list(map(int, rng.integers(1, cfg.vocab_size, PS // 2)))
         for i in range(G) for j in range(2)}
# analytic working set: fp32 KV bytes/token = 2 sides x layers x
# kv_heads x head_dim x 4B (matches ship_pages' manifest nbytes)
tok_bytes = 2 * cfg.n_layers * cfg.n_kv_heads * (cfg.d_model // cfg.n_heads) * 4
WS = G * PREFIX_PAGES * PS * tok_bytes

ray_tpu.init(num_cpus=8)


async def run_arm(spill, factor):
    get_config().prefix_cache_spill = spill
    get_config().spill_cold_after_s = 0.0
    s = DisaggLLMServer(cfg, n_prefill=2, n_decode=2, max_batch=max_batch,
                        page_size=PS, n_pages=n_pages, max_seq_len=max_seq,
                        prefix_cache_bytes=max(1, WS // factor),
                        max_wave=8, wave_wait_s=0.004)

    async def round_():
        t0 = time.perf_counter()
        outs = await asyncio.gather(
            *(s({"prompt_tokens": tenants[i] + tails[(i, j)],
                 "max_tokens": 8})
              for i in range(G) for j in range(2)),
            return_exceptions=True)
        errs = [o for o in outs if isinstance(o, Exception)]
        for e in errs[:3]:
            print("ERR", type(e).__name__, e, file=sys.stderr, flush=True)
        toks = sum(len(o["completion_tokens"]) for o in outs
                   if not isinstance(o, Exception))
        return toks / (time.perf_counter() - t0), len(errs)

    errors = 0
    for _ in range(2):  # warm: jit compiles + first-touch inserts
        _, e = await round_()
        errors += e
    best = 0.0
    for _ in range(2 if quick else 3):  # the adoption-burst rounds
        tps, e = await round_()
        errors += e
        best = max(best, tps)
    st = await s.stats()
    await s.shutdown()
    pc = st["prefix_cache"]
    return {"tok_s": best, "errors": errors,
            "hit_rate": pc["hit_rate"],
            "tier1_hits": pc.get("tier1_hits", 0),
            "tier1_hit_share": (pc.get("tier1_hits", 0) /
                                max(1, pc.get("hits", 0) or 1)),
            "spills": pc.get("spills", 0),
            "pages_restored": st["kv_plane"].get("pages_restored", 0)}


def restore_gbps_leg():
    # tier-1 restore bandwidth, measured straight: ship r9-sized KV
    # pages, push them all to disk, time one batched adopt back
    from ray_tpu.core import api
    from ray_tpu.llm import engine as _engine
    from ray_tpu.llm.disagg.kv_plane import adopt_pages, ship_pages

    kpool, vpool = _engine.make_kv_pools(cfg, PS, 64, None)
    m = ship_pages(kpool, vpool, list(range(48)),
                   list(range(1, 48 * PS + 1)), page_size=PS)
    core = api.get_core()
    oids = [ref.id for p in m.pages for ref in p.refs.values()]
    res = core.spill_objects(oids)
    if not res or not all(v["ok"] for v in res.values()):
        return 0.0
    nbytes = sum(p.nbytes for p in m.pages)
    t0 = time.perf_counter()
    adopt_pages(m)
    return nbytes / (time.perf_counter() - t0) / 1e9


async def go():
    spill5 = await run_arm(True, 5)
    drop5 = await run_arm(False, 5)
    out = {
        "tier_hit_rate": spill5["hit_rate"],
        "tier1_hit_share": spill5["tier1_hit_share"],
        "tok_s_under_pressure": spill5["tok_s"],
        "tok_s_under_pressure_nospill": drop5["tok_s"],
        "tiering_hit_rate_nospill": drop5["hit_rate"],
        "tiering_spills": spill5["spills"],
        "tiering_pages_restored": spill5["pages_restored"],
        "tiering_oom_errors": spill5["errors"] + drop5["errors"],
    }
    if not quick:
        for f in (2, 10):
            arm = await run_arm(True, f)
            out[f"tier_hit_rate_{f}x"] = arm["hit_rate"]
            out[f"tok_s_spill_{f}x"] = arm["tok_s"]
            out["tiering_oom_errors"] += arm["errors"]
    return out


out = asyncio.run(go())
out["restore_gbps"] = restore_gbps_leg()
import jax

out["tiering_platform"] = jax.devices()[0].platform
out["tiering_ws_bytes"] = WS
ray_tpu.shutdown()
print("RES=" + json.dumps(out))
"""


def run_tiering_bench(quick: bool) -> dict:
    """Memory-tiering A/B (ROADMAP item 3): the r9 disagg workload with
    the prefix-cache arena held 2x/5x/10x under the tenant working set,
    tiering on (cold prefixes spill to disk, hits restore through the
    batched pull path) vs off (capacity evictions re-prefill). Also
    times raw tier-1 restore bandwidth and counts OOM/arena-full errors
    under the concurrent adoption-burst rounds (acceptance: 0). The
    whole sweep shares one driver/cluster: pool leases return between
    arms via actor-handle autokill + explicit shutdown() kills."""
    return _run_llm_child(_TIERING_BENCH_CHILD, "tiering", quick)


def write_benchvs(micro: dict, model: dict | None,
                  llm: dict | None = None,
                  findings: int | None = None,
                  degraded: bool = False,
                  flow_findings: int | None = None,
                  flow_s: float | None = None) -> None:
    lines = [
        "# BENCHVS — ours vs reference (BASELINE.md, Ray 2.46.0 release metrics)",
        "",
        "Reference hardware: single 64-vCPU m5.16xlarge node. Ours: this machine "
        f"({os.cpu_count()} cpus). Produced by `python bench.py`.",
        "",
    ]
    if degraded:
        lines += [
            f"> **HOST DEGRADED**: `host_memcpy_gbps={micro.get('host_memcpy_gbps', 0):.1f}` "
            f"is below the {HOST_MEMCPY_FLOOR_GBPS:.1f} GB/s health floor — "
            "neighbor load deflated every wall-clock number in this run. "
            "Ratios below are NOT comparable to healthy-box records; do not "
            "treat them as regressions or improvements.",
            "",
        ]
    if findings is not None:
        lines += [
            f"`lint_findings={findings}` — raylint static-analysis gate "
            "(`python -m ray_tpu lint ray_tpu/`, see README § Static "
            "analysis); 0 is the tier-1 requirement.",
            "",
        ]
    if flow_findings is not None:
        lines += [
            f"`lint_flow_findings={flow_findings}` `lint_flow_s={flow_s}` "
            "— interprocedural hot-path effect gate (`python -m ray_tpu "
            "lint --flow ray_tpu/`, RT020-RT023); 0 findings is the "
            "tier-1 requirement and the pass must stay under its 60s "
            "self-check ceiling.",
            "",
        ]
    lines += [
        "| Metric | Ours | Reference | Ratio |",
        "|---|---:|---:|---:|",
    ]
    for name, value in micro.items():
        if name.startswith("tracing_"):
            continue  # rendered as the dedicated r13 A/B section below
        base = BASELINE.get(name)
        if name == "host_memcpy_gbps":
            unit = "GB/s (host-load marker: physical ceiling ~20)"
        elif "gigabytes" in name:
            unit = "GB/s"
        elif name.endswith("_us_per_call") or name.endswith("_us"):
            unit = "µs"  # lower is better; no reference counterpart
        elif name.endswith("_ms"):
            unit = "ms"  # lower is better; no reference counterpart
        elif "error_rate" in name:
            unit = "(error fraction; SLO < 0.01)"
        elif name.endswith("_gbps"):
            unit = "GB/s"
        elif name.endswith("_bytes"):
            unit = "bytes"
        elif name.endswith("_avg_batch"):
            unit = "recs/flush"
        elif name.endswith("_per_s"):
            unit = "/s"
        elif name in ("churn_node_kills", "churn_leaked_bundles",
                      "churn_nodes", "serve_fast_calls"):
            unit = "(count)"
        elif name.endswith("_s"):
            unit = "s"  # lower is better; no reference counterpart
        else:
            unit = "/s"
        ratio = f"{value / base:.2f}×" if base else "—"
        base_s = f"{base:,.1f}" if base else "—"
        lines.append(f"| {name} | {value:,.1f} {unit} | {base_s} | {ratio} |")
    lines += [
        "",
        "`submit_cpu_us_per_call` — driver-side CPU time per steady-state "
        "`.remote()` call (median of 5 in-process windows, "
        "`time.thread_time`): the noise-immune counter the submission "
        "fast path (template cache + coalesced ring flush, README § "
        "Submission fast path) is judged on. `fastpath_flush_avg_batch` "
        "is how many submit records rode each native ring push "
        "(1.0 = coalescing never engaged).",
        "",
        "`sharded_put_gbps` / `sharded_get_gbps` / `reshard_gbps` — the "
        "sharded object plane (README § Sharded object plane): sealing, "
        "device-local reassembly, and collective-backed respec of a 128MB "
        "dp=4·tp=2-sharded array. `sharded_driver_bytes` (manifests + "
        "shard descriptors, **4.0KB** for three ops over the 128MB array) "
        "vs `sharded_array_bytes` (payload through shm/XLA, 402MB = 3 "
        "seals) is the zero-copy evidence: driver traffic stays "
        "O(manifest), a ~1e-5 fraction of the array. `sharded_get_gbps` "
        "swings 13–86 GB/s run to run and can EXCEED memcpy because "
        "CPU-backend device_put aliases the shm mapping — assembly really "
        "is zero-copy; `sharded_put_gbps` is the cold-arena first-touch "
        "floor (same effect as single_client_put_gigabytes' cold pages: "
        "repeats warm to ~7 GB/s); `reshard_gbps` is one XLA all-gather + "
        "reseal on ONE physical core driving 8 virtual devices — reseal + "
        "program execution bound, not fabric (the identity program itself "
        "is lru-cached per (mesh, spec): ~104µs/dispatch warm, was "
        "24ms/call when it recompiled each time).",
        "",
        "`pg_create_removal_per_s` / `pg_reschedule_p50/p99_ms` / "
        "`churn_unsatisfied_pg_s` — the simulated-churn arm (README § "
        "Placement-group fault tolerance): `churn_nodes` simulated "
        "raylet endpoints join/leave on a seeded schedule (a kill every "
        "~0.8s, `churn_node_kills` total) under the checked-in seeded "
        "2PC-fault plan `tests/plans/pg_churn.json` while PG "
        "create/remove cyclers and persistent PG-bound actors run. "
        "Create/remove throughput is measured WITH the churn and faults "
        "active; reschedule latency is node death → RESCHEDULING → "
        "re-CREATED from the GCS's pgs pubsub stream; "
        "`churn_unsatisfied_pg_s` integrates PG·seconds spent out of "
        "CREATED; `churn_leaked_bundles` is the post-settle audit "
        "(every reservation on every surviving node cross-checked "
        "against the GCS table) and must be 0.",
        "",
        "## Serve data plane A/B (r11, same-host interleaved)",
        "",
        "The serve arm is itself an interleaved A/B (3 alternating "
        "rounds, best-of per arm, same batched handler + 8-thread "
        "closed-loop client): `serve_qps`/`serve_p99_ms` above is arm B "
        "— fast-lane router (replica calls over the actor shm rings, "
        "untracked + unordered, README § Serve data plane) + AIMD "
        "adaptive batching under a 50ms `latency_slo_ms`; "
        "`serve_qps_baseline` is arm A — RPC routing + fixed batch "
        "size, the pre-dataplane configuration. Measured r11: "
        "**1,259.6/s vs 1,011.5/s (1.25×)**, and **1.56× the r6 805/s "
        "record** the ROADMAP acceptance is anchored to (same "
        "2-replica same-node workload; r6 ran unbatched — batching is "
        "part of what the data plane buys). `serve_fast_calls` 814/816 "
        "— the ring carried steady-state traffic, 2 bootstrap calls "
        "per replica took RPC while the lane attached. En route the "
        "whole serve path was profiled flat: promise refs ride the "
        "prefix+counter id scheme (ObjectID.from_random was one "
        "~288µs urandom syscall per request), blocking gets on promise "
        "refs resolve on the caller thread off a threading.Event twin "
        "(no loop round trip), reply wakes coalesce behind one armed "
        "drain (a self-pipe write per reply measured ~140µs of loop "
        "time), and the hedge arm + fast-await dropped "
        "wait_for/shield wrappers for bare futures + call_later. "
        "`serve_autoscale_lag_s` **0.51s** is load-step → scaled-up "
        "target: 10 threads slam a min-scaled autoscaled deployment "
        "(0.1s handler, target_ongoing 2, upscale_delay 0.3s) and the "
        "SLO-feedback autoscaler converges within ~2 metric windows.",
        "",
        "## Cross-node fast lane A/B (r12, two raylets on one host)",
        "",
        "The tunnel arm spawns a real GCS + TWO raylet subprocesses on "
        "this host; the driver attaches to node A and the actor/workers "
        "land on node B (resource fence), so every fast call crosses "
        "nodes and rides the node tunnel (README § Cross-node fast "
        "lane) — the SAME packed ring records the shm lanes use, "
        "coalesced into multiplexed per-node-pair frames. The baseline "
        "arm (RT_NODE_TUNNEL=0) is the per-call RPC path (pickled spec "
        "+ frame + loop write per request, scatter-batched transport). "
        "Interleaved alternating subprocess rounds, best-of per arm: "
        f"`tunnel_calls_per_s` "
        f"{micro.get('tunnel_calls_per_s', 0):,.0f}/s vs "
        f"{micro.get('tunnel_calls_per_s_rpc', 0):,.0f}/s burst "
        "(600-call fire-then-await, the coalescing shape), with "
        f"`tunnel_coalesce_avg_batch` "
        f"{micro.get('tunnel_coalesce_avg_batch', 0):,.1f} records per "
        "tunnel frame during the burst — the win stacks submit-side "
        "txbuf coalescing, worker-side one-executor-hop batch "
        "execution, and caller-thread reply resolution "
        "(`fast_prepass` drains tunnel completions without a loop "
        "task per ref; routing gets through `_run_sync(get_async)` "
        "instead measured 3× slower than the public `ray_tpu.get`). "
        "The threaded CLOSED-loop twins "
        f"({micro.get('tunnel_closed_calls_per_s', 0):,.0f}/s vs "
        f"{micro.get('tunnel_closed_calls_per_s_rpc', 0):,.0f}/s) sit "
        "near parity: a lone request's latency pays the tunnel's two "
        "extra hops (driver→raylet→worker vs driver→worker direct) "
        "with nothing to coalesce — the tunnel is a throughput plane, "
        "and per-call RPC remains a fine road for isolated calls "
        "(which is exactly the per-call fallback the lanes keep). "
        f"`cross_node_pull_gbps` "
        f"{micro.get('cross_node_pull_gbps', 0):,.2f} GB/s is a 64MB "
        "8-object result set sealed on node B adopted on A through the "
        "batched pull_objects path (chunked streaming through two "
        "python raylets on a shared box; the per-oid directory lookups "
        "it replaced were the latency term, not the byte pump).",
        "",
    ]
    if "tracing_overhead_us" in micro:
        lines += [
            "## Tracing overhead A/B (r13, fast-lane record paths)",
            "",
            "Wire-level trace context (protocol 2.1, README § Distributed "
            "tracing) priced as an interleaved three-arm A/B over the exact "
            "record paths the trace leg touches: subprocess clusters running "
            "closed-loop sync round trips on the task fast lane and the "
            "actor ring lane, arms alternating order per round, best-of per "
            "arm — **off** (`RT_TRACING_ENABLED=0`), **on-but-unsampled** "
            "(tracing on, `trace_sample_rate=0`: every record pays the "
            "one-branch wire path and ships zero trace bytes), and "
            "**sampled at 1%** (the Dapper production default: 1-in-100 "
            "requests carry the 25-byte leg, a submit point span, a worker "
            "exec span and the reply-apply `::call` span).",
            "",
            "| arm | task lane (µs/call) | actor lane (µs/call) |",
            "|---|---:|---:|",
            f"| tracing off | {micro.get('tracing_task_off_us', 0):,.1f} "
            f"| {micro.get('tracing_actor_off_us', 0):,.1f} |",
            f"| on, unsampled | {micro.get('tracing_task_unsampled_us', 0):,.1f} "
            f"| {micro.get('tracing_actor_unsampled_us', 0):,.1f} |",
            f"| sampled 1% | {micro.get('tracing_task_sampled1_us', 0):,.1f} "
            f"| {micro.get('tracing_actor_sampled1_us', 0):,.1f} |",
            "",
            "`tracing_overhead_us` (unsampled − off, task lane) measured "
            "**+12.6µs on one run and −4.9µs on the repeat** — the sign "
            "flips run to run and the sampled arm landed *under* the "
            "unsampled one (307.7 vs 311.9), so both deltas sit inside this "
            "shared 2-vCPU box's ±13µs between-run noise on a ~300µs "
            "closed-loop round trip, exactly the r12 `tunnel_calls_per_s`"
            "/task-lane noise band. That is the acceptance claim: the "
            "unsampled record path is byte-identical to wire 2.0 (the trace "
            "flag is a free bit in the existing stamp field) and costs one "
            "cached-attribute branch per submit — the chaos-gate cost "
            "model. The priced sampled-path work (span dicts through the "
            "existing 1Hz task-event flush, 25 wire bytes per record) is "
            "head-gated by `trace_sample_rate`, so production pays it on 1% "
            "of requests.",
            "",
        ]
    lines += [
        "## Placement-group 2PC A/B (r10, same-host interleaved)",
        "",
        "Pre/post the PG lifecycle rework (BundleTxn parallel "
        "prepare/commit over pooled GCS→raylet connections + repair, "
        "README § Placement-group fault tolerance), alternating-order "
        "subprocess rounds on one host, best-of per arm. The "
        "`placement_group_create_removal` row above swings with the "
        "shared box (828→680→476/s across three same-code runs as "
        "`host_memcpy_gbps` fell 10.4→7.2); the interleaved A/B is the "
        "controlled comparison:",
        "",
        "| Arm | A (pre) best | B (post) best | Ratio |",
        "|---|---:|---:|---:|",
        "| 1-bundle create+remove, end-to-end | 890/s | 1,028/s | **1.15×** |",
        "| 4-bundle create+remove, end-to-end | 486/s | 529/s | **1.09×** |",
        "| 1-bundle cycle, GCS-side (in-process) | 475µs | 439µs | **1.08×** |",
        "| 4-bundle cycle, GCS-side (in-process) | 1,505µs | 1,216µs | **1.24×** |",
        "",
        "The end-to-end cycle is dominated by the driver→GCS RTT "
        "(~250µs of ~1ms), so the pooled-connection savings read "
        "larger GCS-side; the 4-bundle gap is the parallel prepare "
        "(RTTs overlap instead of summing). Two costs were tuned out "
        "en route, both ~70µs/Task on this host: single-bundle phases "
        "skip the asyncio.gather wrapping, and the per-call wait_for "
        "timeout was replaced by the pool's "
        "drop-connection-on-node-death guarantee (a dead node fails "
        "in-flight 2PC calls via ConnectionLost instead of a timer).",
        "",
        "## Sub-baseline metrics: hardware-bound analysis",
        "",
        "The reference's numbers come from a 64-vCPU m5.16xlarge; this host "
        "has ONE vCPU. Two metric families are bound by that difference, "
        "with measurements (r5, `/proc/stat` + dedicated probes):",
        "",
        "- **multi_client_tasks_async / n_n_actor_calls_async** (fan-in): "
        "with a SINGLE client the host CPU is already 100% busy and "
        "aggregate throughput is FLAT from 1 to 4 clients (13.3k -> 14.4k "
        "-> 14.0k nested calls/s measured on the bench's own fanout "
        "shape, r5) — perfect work conservation, no software "
        "serialization beyond the core. The reference's multi-client "
        "scaling (8.1k single -> 22.0k multi) is spare-core parallelism "
        "this host does not have; every per-lane path here "
        "(single-client async 1.1-1.7x, actor lanes 1.4-2.7x baseline) "
        "meets or exceeds the reference on the same hardware budget. "
        "For hosts WITH spare cores the control plane now also ships a "
        "C++ epoll RPC mux (_native/src/mux.cc, auto-enabled at >= "
        "RT_NATIVE_MUX_MIN_CPUS cores) that drains all client sockets on "
        "a native thread concurrent with Python — on THIS 1-core host it "
        "measures 25-35% slower (the IO thread can only preempt the "
        "interpreter), so it auto-disables.",
        "- **single_client_put_gigabytes**: the baseline EQUALS this "
        "VM's physical ceiling. Raw single-thread warm memcpy of the "
        "same 100MB buffer measures **20.1 GB/s** (numpy copyto, best "
        "of 8) — exactly the 20.1 GB/s reference number. A put IS that "
        "memcpy plus arena allocation, seal, and registration, so "
        "matching the baseline here would require a zero-overhead copy; "
        "the end-to-end 13-14.5 GB/s measured is ~70% of the physical "
        "ceiling (cold-arena first-touch page faults: 1.8 GB/s until "
        "pages recycle).",
        "",
        ("**1_1_actor_calls_sync** was the one fan-in metric that was NOT "
         "hardware-bound; the r5 redesign (executor-resident ring pump — "
         "zero cross-thread handoffs worker-side — plus coalesced driver "
         "loop wakeups) moved it from a stable 1.7k/s (r4) to "
         "**2.0-2.3k/s on quiet-box runs (1.0-1.15x baseline)**; "
         f"{micro.get('1_1_actor_calls_sync', 0):,.0f}/s this particular "
         "run. This metric is one futex round-trip per call, so it "
         "swings hardest with neighbor load: the bare shm-ring ping-pong "
         "floor here is 247us/round-trip (futex wakes cost 60-200us on "
         "this VM vs ~5-20us on bare metal), bounding ANY sync call "
         "design to ~4.0k/s."),
        "",
        "Run-to-run note: this shared 1-vCPU VM swings +/-30% between "
        "runs (neighbor load); judge trends across runs, not "
        "single numbers.",
        "",
        "## Actor fast lane A/B (r8, same-host interleaved)",
        "",
        "Pre/post actor fast lane v2 (per-(handle, method) call "
        "templates, seq-matched out-of-order completions for "
        "async/threaded/grouped actors, per-call instead of per-lane "
        "RPC fallback for ref-args/generators, and prefix+counter actor "
        "task ids — README § Actor fast lane), measured as 3 "
        "interleaved rounds of fresh subprocesses on one host, best-of "
        "per arm:",
        "",
        "| Metric | A (pre) best | B (post) best | Ratio |",
        "|---|---:|---:|---:|",
        "| 1_1_actor_calls_sync | 1,787/s | 1,952/s | **1.09×** |",
        "| 1_1_actor_calls_async | 12,766/s | 23,639/s | **1.85×** |",
        "| 1_n_actor_calls_async | 3,747/s | 13,018/s | **3.47×** |",
        "| n_n_actor_calls_async | 16,644/s | 16,542/s | 0.99× (CPU-saturated) |",
        "| 1_1_async_actor_calls_sync | 1,074/s | 1,129/s | **1.05×** |",
        "| 1_1_async_actor_calls_async | 7,868/s | 8,968/s | **1.14×** |",
        "",
        "Every family lands at >= 2x its r7 absolute (1_n 5.2x, n_n "
        "2.9x, async-actor sync 3.4x, async-actor async 5.4x of the r7 "
        "records). The single biggest submit-side win was replacing "
        "TaskID.generate_actor's per-call os.urandom(16) — ~288us under "
        "this box's syscall-intercepting sandbox, >60% of the whole "
        "actor submit path — with the same per-process prefix+counter "
        "normal tasks already used. 1_n additionally rides the "
        "templates + coalesced flush; async actors ride the ring at all "
        "(they NEED_SLOWed to RPC before) with one loop wake per popped "
        "batch. n_n is the aggregate-saturation shape (9 processes on 2 "
        "vCPUs): per-call CPU savings shift work between processes but "
        "the box is already at 100%, so the A/B reads parity — its "
        "gain shows against the r7 record, not the same-phase base.",
        "",
        "## Completion fast lane A/B (r6, same-host interleaved)",
        "",
        "Pre/post the completion fast lane (result ring + inline returns "
        "+ location cache + caller-thread get/wait), measured as 3 "
        "interleaved A/B rounds of fresh subprocesses on one host, "
        "host-health marker `host_memcpy_gbps` 7.1-8.0 (healthy; floor "
        f"{HOST_MEMCPY_FLOOR_GBPS:.1f}) in every round:",
        "",
        "| Metric | A (pre) best | B (post) best | Ratio |",
        "|---|---:|---:|---:|",
        "| single_client_tasks_sync | 339.7/s | 1,166.1/s | **3.4×** |",
        "| single_client_get_calls | 4,356.6/s | 121,809.3/s | **28.0×** |",
        "| single_client_wait_1k_refs | 923.2/s | 1,802.5/s | **2.0×** |",
        "",
        "tasks_sync: lone submit-then-block calls now ride the shm ring "
        "(blocking get steals the reply-ring consumer; zero-futex "
        "ping-pong when the 64-yield spin pairs up). get_calls: ready "
        "refs resolve on the calling thread — no event-loop round trip. "
        "wait_1k: caller-thread ready-count + reply-stream cv instead of "
        "a loop hop with watcher tasks.",
        "",
        "## Flight recorder (README § Observability)",
        "",
        "`stage_<name>_p50_us`/`_p99_us` are the always-on flight "
        "recorder's per-stage breakdown of the fast-lane tasks the bench "
        "just ran, read back through `state.list_task_latency()`: "
        "ring_sub (submit pack → worker pop, the submit-ring hop, "
        "includes coalescing defer), deserialize (pop → user-function "
        "entry), exec (the user function), ring_reply (exec end → "
        "driver apply, the completion-ring hop) and total. "
        "`actor_stage_*` are the same stages for ACTOR fast-lane calls "
        "(own recorder window, published beside the task one — ROADMAP "
        "item 1's actor stage breakdown; for dispatched async methods "
        "the deserialize stage includes the pump→loop hop and exec is "
        "per-call wall, so concurrent awaits overlap inside it). "
        "`recorder_overhead_us` is the recorder-off-vs-on delta of the "
        "exact per-task recorder operations (driver: submit stamp + "
        "one raw stats store at reply-apply; worker: two exec-boundary "
        "clock reads + 16-byte stage stamp + 1-in-16 W_TASK shm slot), "
        "measured directly against the real modules behind the same "
        "gated branches the runtime uses (min-per-arm over alternating "
        "rounds, the timeit doctrine) — the only estimator with sub-µs "
        "resolution here, since end-to-end per-task wall/CPU between "
        "runs on this shared 1-vCPU box swings ±30-200µs, two orders "
        "of magnitude above the < 1.0µs/task budget under test. The "
        "number swings ~±0.15µs with host phase; note this VM's clock "
        "read alone costs 120-155ns (vs ~25ns on reference-class "
        "hardware), so the two exec-boundary reads are ~0.3µs of it "
        "here and ~0.05µs there. recorder_ab_wall_*_us bracket the "
        "end-to-end effect (RT_RECORDER_ENABLED off vs on, fresh "
        "subprocess cluster per arm, alternating order, best-of per "
        "arm): their delta sits inside host noise. "
        "`metrics_overhead_us` is the same-doctrine direct A/B of the "
        "metric bumps a task pays (one untagged Counter.inc at submit + "
        "one tagged inc at reply-apply; the GCS rollup plane adds zero "
        "hot-path cost — windowing rides the 1/s flush). Budget < "
        "1.0µs/task.",
        "",
        "## Chaos engine (README § Fault injection)",
        "",
        "`chaos_overhead_us` is the per-fault-point A/B: fault points "
        "compiled out (chaos disabled — the bare `if chaos.ENABLED` "
        "gate, also reported as `chaos_gate_us`) vs armed-but-idle "
        "(controller enabled with a plan matching no hot point: gate + "
        "point() call + the controller's lock-free name prefilter). "
        "Budget < 0.5µs — the hot paths pay only the gate in "
        "production. `chaos_recovery_s` is the end-to-end cost of "
        "absorbing repeated worker loss: a fixed 60-task retryable "
        "workload drained under the standard seeded kill plan (each "
        "exec flips a seeded 5% coin on SIGKILLing its worker, seed "
        "42) — worker death, lease re-grant, and task retry all inside "
        "the measured wall.",
        "",
        "`serve_qps`/`serve_p99_ms` — the serve data plane under 8 "
        "closed-loop client threads against a 2-replica batched "
        "deployment with the full request-FT stack on (retries, 60s "
        "deadline, 400ms hedging; README §§ Serve fault tolerance + "
        "Serve data plane). Interleaved A/B, best-of per arm: the "
        "headline row runs the fast-lane router (replica calls over "
        "the actor shm rings) + AIMD adaptive batching under a 50ms "
        "SLO; `serve_qps_baseline`/`serve_p99_ms_baseline` is the SAME "
        "handler with RPC routing and a fixed batch size (the "
        "pre-dataplane configuration). `serve_fast_calls` counts "
        "requests that actually rode the ring. "
        "`serve_autoscale_lag_s` is the load-step-to-scale-up wall "
        "time: 10 closed-loop threads slam a min-scaled autoscaled "
        "deployment and the clock stops when the SLO-feedback "
        "autoscaler's target reaches 2 replicas. "
        "`serve_error_rate_chaos` is the data-plane workload under the "
        "checked-in seeded kill-replicas-under-load plan "
        "(tests/plans/serve_kill_replicas.json: every replica process "
        "SIGKILLs itself at its 31st request) — the ROADMAP serve SLO "
        "is error rate < 1% for idempotent traffic, enforced in tier-1 "
        "by tests/test_serve_ft.py (and by the kill-while-autoscaling "
        "plan in tests/test_serve_dataplane.py).",
    ]
    if model:
        lines += [
            "",
            "## Model: Llama single-chip train step "
            f"({model['params']/1e6:.0f}M params, {model['device']}, "
            f"platform={model['platform']})",
            "",
            "| Seq len | tokens/s | step ms | MFU % |",
            "|---:|---:|---:|---:|",
        ]
        for T, e in model["seq"].items():
            mfu = f"{e['mfu_pct']:.1f}" if "mfu_pct" in e else "—"
            lines.append(
                f"| {T} | {e['tokens_per_s']:,.0f} | {e['step_ms']:.1f} | {mfu} |"
            )
        for name, e in model.get("flagship", {}).items():
            mfu = f"{e['mfu_pct']:.1f}" if "mfu_pct" in e else "—"
            lines.append(
                f"| {name} ({e['params']/1e9:.2f}B, T=2048) | "
                f"{e['tokens_per_s']:,.0f} | {e['step_ms']:.1f} | {mfu} |"
            )
        lines += [
            "",
            "No reference model-throughput numbers are checked in "
            "(BASELINE.md: 'No ML-model numbers'); MFU is vs chip bf16 peak.",
        ]
    if llm:
        # the engine arm and the disagg arm can succeed independently —
        # a disagg-only dict must not crash on the engine-arm keys
        lines += ([
            "",
            "## LLM engine: continuous-batching decode "
            f"({llm['device']}, platform={llm['platform']})",
            "",
            f"{llm['concurrent_requests']} concurrent requests over a "
            f"max_batch={llm['max_batch']} paged-KV decode loop: "
            f"**{llm['decode_tokens_per_s']:,.0f} tokens/s**. "
            "(The reference delegates this engine to vLLM; no comparable "
            "number is checked into its repo.)",
            "",
            ] if "decode_tokens_per_s" in llm else [
            "",
            "## LLM engine (this run: disagg arm only)",
            "",
            ]) + ([
            f"With the int8 KV cache (`kv_dtype=\"int8\"`, per-token "
            f"per-kv-head symmetric scales) at its batch-128 knee "
            f"({llm.get('int8kv_concurrent_requests', '2x')} concurrent "
            f"requests): "
            f"**{llm['decode_tokens_per_s_int8kv']:,.0f} tokens/s** — "
            "the quantized cache halves the page-table gather bytes "
            "that cap the bf16 cache at batch 64 (~97% greedy-token "
            "agreement with bf16 on the parity model).",
            "",
            ] if "decode_tokens_per_s_int8kv" in llm else []) + ([
            "### Disaggregated serving A/B (llm/disagg: 2 prefill + 2 "
            "decode workers vs ONE aggregated engine, platform="
            f"{llm.get('disagg_platform', '?')})",
            "",
            "| metric | aggregated | disaggregated |",
            "|---|---|---|",
            f"| tokens/s (mixed prompt lengths, shared prefix) | "
            f"{llm['llm_agg_tokens_per_s']:,.0f} | "
            f"{llm['llm_disagg_tokens_per_s']:,.0f} |",
            "",
            "Workload: a 384-token shared prefix (24 full pages — the "
            "shared-system-prompt shape) + mixed 64/8-token user tails, "
            "24 concurrent requests, model sized so prefill FLOPs "
            "dominate RPC/ship overheads. The aggregated engine "
            "recomputes the shared prefix for every request; the disagg "
            "stack prefills it once, serves it from the radix cache, and "
            "runs only each request's suffix — that saved recompute is "
            "the whole margin. "
            f"Same interleaved load (best-of-2 rounds each): "
            f"`prefix_cache_hit_rate={llm['prefix_cache_hit_rate']:.2f}`"
            f", TTFT p50/p99 "
            f"{llm.get('ttft_p50_ms', 0):,.1f}/"
            f"{llm.get('ttft_p99_ms', 0):,.1f} ms, TPOT p50/p99 "
            f"{llm.get('tpot_p50_ms', 0):,.2f}/"
            f"{llm.get('tpot_p99_ms', 0):,.2f} ms (scheduler "
            "flight-recorder stage windows). KV pages moved "
            f"{llm['kv_ship_array_bytes']:,} payload bytes via the "
            "shm/object plane against "
            f"{llm['kv_ship_driver_bytes']:,} bytes of manifest "
            "metadata through the driver/actor RPC plane "
            f"(~{llm['kv_ship_driver_bytes'] / max(1, llm['kv_ship_array_bytes']):.1e})"
            " — the zero-copy proof: prefilled KV reaches decode "
            "workers without transiting the driver.",
            "",
            ] if "llm_disagg_tokens_per_s" in llm else []) + ([
            "### Memory tiering A/B (r16: prefix-cache arena 5x under "
            "the tenant working set; spill-to-tier-1 on vs capacity-drop, "
            f"platform={llm.get('tiering_platform', '?')})",
            "",
            "| metric | drop (tiering off) | spill (tiering on) |",
            "|---|---:|---:|",
            f"| tokens/s under pressure | "
            f"{llm['tok_s_under_pressure_nospill']:,.0f} | "
            f"**{llm['tok_s_under_pressure']:,.0f} "
            f"({llm['tok_s_under_pressure'] / max(1e-9, llm['tok_s_under_pressure_nospill']):.2f}x)** |",
            f"| prefix-cache hit rate | "
            f"{llm.get('tiering_hit_rate_nospill', 0):.2f} | "
            f"**{llm['tier_hit_rate']:.2f}** |",
            "",
            "Workload: 8 tenants x 384-token shared prefixes "
            f"(working set {llm.get('tiering_ws_bytes', 0):,} KV bytes) "
            "replayed every round against a cache arena one fifth that "
            "size. With tiering off every capacity eviction is a "
            "dropped subtree the next round re-prefills; with tiering "
            "on the radix cache spills unpinned leaves to the raylet's "
            "tier-1 and a later hit costs one sequential disk restore "
            "through the batched pull path "
            f"(`restore_gbps={llm.get('restore_gbps', 0):.2f}` GB/s "
            "measured on a 48-page adopt of fully-spilled KV; "
            f"{llm.get('tiering_spills', 0)} spills / "
            f"{llm.get('tiering_pages_restored', 0)} pages restored "
            f"this run, tier-1 hit share "
            f"{llm.get('tier1_hit_share', 0):.2f}). "
            f"`tiering_oom_errors={llm.get('tiering_oom_errors', 0)}` "
            "across every concurrent adoption-burst round (acceptance: "
            "0 — the pull-admission window queues restores against "
            "arena headroom instead of letting them race it to an "
            "arena-full). Sweep: hit rate "
            f"{llm.get('tier_hit_rate_2x', 0):.2f} at 2x / "
            f"{llm['tier_hit_rate']:.2f} at 5x / "
            f"{llm.get('tier_hit_rate_10x', 0):.2f} at 10x under.",
            "",
            ] if "tier_hit_rate" in llm else []) + ([
            "### Speculative decoding A/B (same engine, spec off vs on; "
            "fused n-gram draft + multi-position verify)",
            "",
            "| metric | plain | speculative |",
            "|---|---:|---:|",
            f"| tokens/s (acceptance-friendly long-gen workload) | "
            f"{llm['spec_tok_s_plain']:,.0f} | "
            f"**{llm['spec_tok_s']:,.0f} ({llm['spec_speedup']:.2f}x)** |",
            "",
            f"`spec_accept_rate={llm['spec_accept_rate']:.2f}` at "
            f"k={llm.get('spec_k', 6)} (on-device 2-gram prompt-lookup "
            "drafter), "
            f"`spec_tokens_per_step={llm['spec_tokens_per_step']:.2f}` "
            "per slot. Greedy outputs are token-identical to the "
            "non-speculative engine (tier-1 asserts it, prefix cache on "
            "and off); the workload is constant-token prompts at the "
            "model's own greedy attractors (period-1 generations the "
            "drafter predicts exactly), 384-token generations over a "
            "near-full 512-token window — the page-table-gather-bound "
            "regime where one fused verify amortizes the window read "
            "over k+1 positions. Low-acceptance loads decay toward the "
            "plain rate (every verify still emits the target's own "
            "token); mixed spec/plain/wandering batches are covered by "
            "tier-1 parity tests.",
            "",
            ] if "spec_tok_s" in llm else []) + ([
            "### Serve-driven disagg QPS (router -> prefill -> 2 decode "
            "replicas, closed-loop)",
            "",
            f"`serve_llm_qps={llm['serve_llm_qps']:.1f}` over "
            f"{llm.get('serve_llm_errors', 0)} errors, per-replica "
            "decode-ring token counters "
            f"{llm.get('serve_llm_decode_tokens')} (both rings carried "
            "traffic — the cross-replica batching proof), prefix-cache "
            f"hit rate {llm.get('serve_llm_hit_rate', 0):.2f}, TTFT "
            f"p50/p99 {llm.get('serve_llm_ttft_p50_ms', 0):,.1f}/"
            f"{llm.get('serve_llm_ttft_p99_ms', 0):,.1f} ms, TPOT "
            f"p50/p99 {llm.get('serve_llm_tpot_p50_ms', 0):,.2f}/"
            f"{llm.get('serve_llm_tpot_p99_ms', 0):,.2f} ms. The "
            "scheduler admits on decode tokens-in-flight + page "
            "headroom (probed signals, not request counts), and the "
            "serve router folds the same signal into its pow-2 choice "
            "via the `__serve_load__` probe field.",
            "",
            ] if "serve_llm_qps" in llm else []) + [
            "Roofline note: the bench model is ~200M params bf16 "
            "(~0.4 GB). Decode is weight-bandwidth-bound, so tokens/step "
            "scale with batch until the page-table attention gather "
            "takes over: the r5 slot sweep measured 16->3.4k, 32->7.9k, "
            "64->15.3k, 128->10.7k tok/s — batch 64 is the knee. The "
            "engine fuses up to 64 decode steps into one lax.scan "
            "program, keeps the (token, position) carry on device across "
            "blocks, admits via one batched prefill per wave, and paces "
            "dispatch two blocks ahead of emission so the host "
            "round-trip rides under device compute.",
            "",
            "Flash-attention tile sweep (551M train step, T=8192, MFU%): "
            "512/512 54.2, 512/1024 59.4, 1024/512 55.9, "
            "**1024/1024 61.7** (now the default); bk=2048 exceeds VMEM. "
            "Bigger tiles amortize online-softmax rescales and causal "
            "masking over 4x the MXU work per grid cell.",
        ]
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "BENCHVS.md"), "w") as f:
        f.write("\n".join(lines) + "\n")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--micro", action="store_true")
    ap.add_argument("--model", action="store_true")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    do_micro = args.micro or not args.model
    do_model = args.model or not args.micro

    window = 0.5 if args.quick else 2.0
    micro = run_micro(window) if do_micro else {}
    if do_micro:
        try:
            micro.update(run_recorder_ab(args.quick))
        except Exception as e:  # the A/B must not sink the micro numbers
            print(f"recorder A/B failed: {e!r}", file=sys.stderr)
        try:
            micro.update(run_metrics_overhead())
        except Exception as e:
            print(f"metrics overhead bench failed: {e!r}", file=sys.stderr)
        try:
            micro.update(run_chaos_bench(args.quick))
        except Exception as e:
            print(f"chaos bench failed: {e!r}", file=sys.stderr)
        try:
            micro.update(run_tracing_bench(args.quick))
        except Exception as e:
            print(f"tracing bench failed: {e!r}", file=sys.stderr)
        try:
            micro.update(run_serve_bench(args.quick))
        except Exception as e:
            print(f"serve bench failed: {e!r}", file=sys.stderr)
        try:
            micro.update(run_tunnel_bench(args.quick))
        except Exception as e:
            print(f"tunnel bench failed: {e!r}", file=sys.stderr)
        try:
            micro.update(run_sharded_bench(args.quick))
        except Exception as e:
            print(f"sharded bench failed: {e!r}", file=sys.stderr)
        try:
            micro.update(run_pg_churn_bench(args.quick))
        except Exception as e:
            print(f"pg churn bench failed: {e!r}", file=sys.stderr)
    model = None
    if do_model:
        try:
            model = run_model(args.quick)
        except Exception as e:  # model bench must not sink the micro numbers
            print(f"model bench failed: {e!r}", file=sys.stderr)

    llm = None
    if do_model:
        try:
            llm = run_llm_engine(args.quick)
        except Exception as e:
            print(f"llm engine bench failed: {e!r}", file=sys.stderr)
        try:
            disagg = run_disagg_bench(args.quick)
            if disagg:
                llm = {**(llm or {}), **disagg}
        except Exception as e:
            print(f"disagg bench failed: {e!r}", file=sys.stderr)
        try:
            tier = run_tiering_bench(args.quick)
            if tier:
                llm = {**(llm or {}), **tier}
        except Exception as e:
            print(f"tiering bench failed: {e!r}", file=sys.stderr)
        try:
            spec = run_spec_bench(args.quick)
            if spec:
                llm = {**(llm or {}), **spec}
        except Exception as e:
            print(f"spec bench failed: {e!r}", file=sys.stderr)
        try:
            sllm = run_serve_llm_bench(args.quick)
            if sllm:
                llm = {**(llm or {}), **sllm}
        except Exception as e:
            print(f"serve-llm bench failed: {e!r}", file=sys.stderr)
        try:
            sstream = run_serve_llm_streaming(args.quick)
            if sstream:
                llm = {**(llm or {}), **sstream}
        except Exception as e:
            print(f"serve-llm streaming bench failed: {e!r}",
                  file=sys.stderr)

    root = os.path.dirname(os.path.abspath(__file__))
    out_path = os.path.join(root, "bench_results.json")
    # partial runs (--micro / --model) keep the other sections from the
    # previous results file rather than clobbering them with null
    raw = {"micro": micro, "model": model, "llm_engine": llm}
    # static-analysis gate, surfaced alongside the perf numbers: nonzero
    # means tests/test_lint.py::test_self_check is failing too
    findings = lint_findings()
    flow_findings, flow_s = lint_flow_findings()
    stored_findings = findings
    stored_flow, stored_flow_s = flow_findings, flow_s
    try:
        with open(out_path) as f:
            prev = json.load(f)
        for key in raw:
            if not raw[key]:
                raw[key] = prev.get(key)
        if stored_findings is None:  # lint crash: keep last known gate state
            stored_findings = prev.get("lint_findings")
        if stored_flow is None:
            stored_flow = prev.get("lint_flow_findings")
            stored_flow_s = prev.get("lint_flow_s")
    except (OSError, json.JSONDecodeError):
        pass
    raw["lint_findings"] = stored_findings
    raw["lint_flow_findings"] = stored_flow
    raw["lint_flow_s"] = stored_flow_s
    # host-health gate: a degraded box must not rewrite the perf record
    memcpy = (raw["micro"] or {}).get("host_memcpy_gbps")
    degraded = memcpy is not None and memcpy < HOST_MEMCPY_FLOOR_GBPS
    raw["host_degraded"] = degraded
    if degraded:
        print(
            f"WARNING: host_memcpy_gbps={memcpy:.1f} is below the "
            f"{HOST_MEMCPY_FLOOR_GBPS:.1f} GB/s health floor — neighbor "
            "load is deflating every wall-clock metric in this run; "
            "vs_baseline is withheld (host_degraded=true stamped in "
            "bench_results.json)", file=sys.stderr)
    with open(out_path, "w") as f:
        json.dump(raw, f, indent=2)

    if findings is not None:
        print(f"lint_findings={findings}")
    if flow_findings is not None:
        print(f"lint_flow_findings={flow_findings} lint_flow_s={flow_s}")

    if raw["micro"]:
        write_benchvs(raw["micro"], raw["model"], raw["llm_engine"],
                      findings=findings, degraded=degraded,
                      flow_findings=flow_findings, flow_s=flow_s)

    value = micro.get(HEADLINE)
    if value is not None:
        headline = {
            "metric": HEADLINE,
            "value": round(value, 1),
            "unit": "tasks/s",
        }
        if degraded:
            headline["vs_baseline"] = None
            headline["host_degraded"] = True
        else:
            headline["vs_baseline"] = round(value / BASELINE[HEADLINE], 3)
        print(json.dumps(headline))
    elif model:
        first = next(iter(model["seq"].values()))
        print(json.dumps({
            "metric": "llama_train_tokens_per_s",
            "value": round(first["tokens_per_s"], 1),
            "unit": "tokens/s",
            "vs_baseline": round(first.get("mfu_pct", 0) / 100, 3),
        }))


if __name__ == "__main__":
    main()
