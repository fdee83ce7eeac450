"""Driver of the serving cells: ``serve.run`` -> handle/router -> replica ->
engine, under an open loop (a schedule fixed by the traffic file and the
seed) or a closed loop (callers that each wait for a reply). The load runs as
coroutines on the runtime's own loop in this one process: no thread a
request, no second process."""
from __future__ import annotations

import asyncio
import os
import tempfile
import time

from benchmarks.lib import traffic as T
from benchmarks.lib.configs import llama_config

APP, DEPLOYMENT = "bench", "LLMEngineServer"


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def deploy(cfg, engine_kw: dict, seed: int):
    from ray_tpu import serve
    from benchmarks.lib.replica import BenchEngineServer, make_params_fn

    dep = serve.deployment(BenchEngineServer, name=DEPLOYMENT, num_replicas=1,
                           max_ongoing_requests=64,
                           ray_actor_options={"num_tpus": 1})
    app = dep.bind(cfg, None, make_params_fn(cfg, seed, engine_kw.get("eos_id")),
                   **engine_kw)
    serve.run(app, name=APP, timeout_s=1100)
    return serve.get_deployment_handle(DEPLOYMENT, APP)


def reachable_pads(traffic: dict, page_size: int) -> list[int]:
    """Every prefill pad bucket the file's prompt lengths can reach, by the
    engine's own rule (a prompt pads to whole pages)."""
    lengths = set(T.quantile_lengths(traffic["prompt"], 4096))
    lengths.add(int(traffic["reference_check"]["prompt_len"]))
    return sorted({-(-n // page_size) * page_size for n in lengths})


# ------------------------------------------------------------------- loops
async def _one_stream(handle, req: dict, rec: dict) -> None:
    """One streamed request; stamps on this process's clock."""
    rec["sent"] = time.monotonic()
    n, stream = 0, handle.stream_deltas.stream_chunks(req)
    try:
        async for chunk in stream:
            if chunk["tokens"]:
                now = time.monotonic()
                rec.setdefault("first", now)
                rec["last"] = now
                n += len(chunk["tokens"])
            if chunk.get("done"):
                rec["replica_ttft_s"] = chunk["usage"]["replica_ttft_s"]
    finally:
        await stream.aclose()
    rec["tokens"] = n
    rec["done"] = time.monotonic()


async def _one_unary(handle, req: dict, rec: dict) -> None:
    rec["sent"] = time.monotonic()
    out = await handle.remote(req)
    rec["tokens"] = len(out["completion_tokens"])
    rec["done"] = rec["last"] = time.monotonic()


async def open_loop(handle, schedule, prompts, stream: bool, hooks) -> dict:
    send = _one_stream if stream else _one_unary
    t0 = time.monotonic() - schedule[0].due_s + 0.05
    recs = [{"index": r.index, "due": t0 + r.due_s, "sampled": r.sampled,
             "prompt_len": r.prompt_len, "max_tokens": r.max_tokens}
            for r in schedule]
    left = sum(r.sampled for r in schedule)
    all_done = asyncio.Event()

    async def one(r, rec):
        nonlocal left
        await asyncio.sleep(max(0.0, rec["due"] - time.monotonic()))
        if all_done.is_set():
            return  # the tail stops once every sampled request has finished
        try:
            await send(handle, {"prompt_tokens": prompts[r.index],
                                "max_tokens": r.max_tokens}, rec)
        except asyncio.CancelledError:
            raise
        except Exception as e:  # counted in ``failed``, never dropped
            rec["error"] = f"{type(e).__name__}: {e}"
        if r.sampled:
            left -= 1
            if left == 0:
                all_done.set()

    tasks = [asyncio.ensure_future(one(r, rec)) for r, rec in zip(schedule, recs)]
    hook_task = asyncio.ensure_future(hooks(t0))
    await all_done.wait()
    for r, task in zip(schedule, tasks):
        if not r.sampled and not task.done():
            task.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    await hook_task
    return {"t0": t0, "recs": recs}


async def closed_loop(handle, traffic: dict, pairs, prompts, seconds: float,
                      stream: bool, hooks) -> dict:
    send = _one_stream if stream else _one_unary
    lead = float(traffic["lead_in_s"])
    start = time.monotonic() + 0.05
    t0, t1 = start + lead, start + lead + seconds
    recs: list[dict] = []
    cursor = iter(range(10**9))

    async def caller(i: int):
        await asyncio.sleep(max(0.0, start + i * traffic["caller_stagger_s"]
                                - time.monotonic()))
        while time.monotonic() < t1:
            j = next(cursor) % len(pairs)
            rec = {"index": j, "prompt_len": pairs[j][0], "max_tokens": pairs[j][1]}
            recs.append(rec)
            try:
                await send(handle, {"prompt_tokens": prompts[j],
                                    "max_tokens": pairs[j][1]}, rec)
            except Exception as e:
                rec["error"] = f"{type(e).__name__}: {e}"
                rec["done"] = time.monotonic()

    hook_task = asyncio.ensure_future(hooks(t0))
    await asyncio.gather(*(caller(i) for i in range(int(traffic["callers"]))))
    await hook_task
    for rec in recs:  # the sample: what finished inside the window
        rec["sampled"] = "done" in rec and t0 <= rec["done"] < t1
    return {"t0": t0, "recs": recs}


# --------------------------------------------------------------------- run
def setup(cell: dict, args, clock) -> dict:
    """Deploy, check the device, warm every reachable program, compare with
    the plain reference. Everything before the first measured instant."""
    import ray_tpu

    cf, traffic = cell["config_file"], cell["traffic_file"]
    if args.allow_cpu:
        cf, traffic = {**cf, **cf["tiny"]}, {**traffic, **traffic["tiny"]}
    cfg = llama_config(cf)
    engine_kw = dict(cf["engine"])

    handle = deploy(cfg, engine_kw, args.seed)
    clock.mark("deployed")
    device = ray_tpu.get(handle.bench_stats.remote(), timeout=300)["device"]
    if device["platform"] != "tpu" and not args.allow_cpu:
        raise RuntimeError(f"the replica runs on {device['platform']!r}: a CPU "
                           f"device is a failure, never a fallback")

    pads = reachable_pads(traffic, engine_kw["page_size"])
    warm = ray_tpu.get(handle.warm.remote(pads, traffic["warm_waves"],
                                          cfg.vocab_size), timeout=1100)
    say(f"warm-up: {warm['programs']} programs in {warm['total_s']:.1f}s "
        f"(prefill waves {warm['prefill_s']:.1f}s), missing {warm['missing']}")
    if warm["missing"]:
        raise RuntimeError(f"warm-up did not reach {warm['missing']}")
    clock.mark("warmed")

    rc = traffic["reference_check"]
    ref = ray_tpu.get(handle.reference_check.remote(
        args.seed, cfg, rc["prompt_len"], rc["max_tokens"],
        engine_kw.get("eos_id"), getattr(args, "control_mode", None) or "float32"),
        timeout=600)
    clock.mark("reference")
    return {"handle": handle, "cfg": cfg, "engine": engine_kw,
            "traffic": traffic, "reference": ref}


def window(ctx: dict, seed: int, seconds: float, trace: bool = False,
           trace_seconds: float = 10.0, clock=None, poll_s: float = 0.0) -> dict:
    """One measured window on a system that is set up: its traffic from
    ``seed``, counters at both ends, the profiler if asked."""
    import ray_tpu
    from ray_tpu.core.api import get_core

    handle, cfg, traffic = ctx["handle"], ctx["cfg"], ctx["traffic"]
    stream = bool(traffic["stream"])
    if traffic["loop"] == "open":
        schedule = T.open_schedule(traffic, seed, seconds)
        prompts = {r.index: T.prompt_tokens(seed, r.index, r.prompt_len,
                                            cfg.vocab_size) for r in schedule}
        say(f"offered in the window: {T.offered(schedule)}; "
            f"{len(schedule)} requests with lead-in and tail")
    else:
        pairs = T.closed_list(traffic, seed)
        prompts = {j: T.prompt_tokens(seed, j, p, cfg.vocab_size)
                   for j, (p, _) in enumerate(pairs)}

    trace_dir = os.path.join(tempfile.gettempdir(), "bench_trace")
    traced: dict = {}

    async def hooks(t0: float):
        """Counters at the window's two ends and, in a traced run, the
        profiler over ``trace_seconds`` from two seconds into the window."""
        await asyncio.sleep(max(0.0, t0 - time.monotonic()))
        traced["start"] = await handle.bench_stats.remote()
        if trace:
            await asyncio.sleep(2.0)
            span = min(float(trace_seconds), max(1.0, seconds - 3.0))
            traced["before"] = await handle.bench_stats.remote()
            # the replica's own stamps of when the profiler went on and off:
            # one host, one monotonic clock. The stop waits for the replica's
            # loop, which a running block holds for up to seconds, so the
            # traced span is longer than the sleep and the records of the
            # whole of it belong to the trace
            t_a = await handle.trace_start.remote(trace_dir)
            await asyncio.sleep(span)
            traced["after"] = await handle.bench_stats.remote()
            traced["trace"] = await handle.trace_stop.remote(trace_dir)
            traced["window"] = (t_a, traced["trace"]["stopped"])
            traced["span_s"] = traced["trace"]["stopped"] - t_a
        traced["polls"] = []
        while poll_s and time.monotonic() + poll_s < t0 + seconds:
            await asyncio.sleep(poll_s)  # the sweep's look at the queue
            st = await handle.bench_stats.remote()
            traced["polls"].append((time.monotonic() - t0, st["waiting"], st["live"]))
        await asyncio.sleep(max(0.0, t0 + seconds - time.monotonic()))
        traced["end"] = await handle.bench_stats.remote()

    before = ray_tpu.get(handle.bench_stats.remote(), timeout=60)
    if clock is not None:
        clock.mark("window")  # set-up ends here: the first measured instant
    if traffic["loop"] == "open":
        coro = open_loop(handle, schedule, prompts, stream, hooks)
    else:
        coro = closed_loop(handle, traffic, pairs, prompts, seconds, stream, hooks)
    out = asyncio.run_coroutine_threadsafe(coro, get_core().loop).result(
        timeout=seconds + 300)
    after = ray_tpu.get(handle.bench_stats.remote(), timeout=60)
    if clock is not None:
        clock.mark("measured")

    recs = [r for r in out["recs"] if r.get("sampled")]
    bad = [r for r in recs if "error" in r or r.get("tokens") != r["max_tokens"]]
    for r in bad[:5]:
        say(f"failed request: {r.get('error') or r}")
    return {
        "device": after["device"], "recs": recs, "recs_all": out["recs"],
        "t0": out["t0"], "trace_window": traced.get("window"),
        "seconds": seconds, "attempted": len(recs), "failed": len(bad),
        "reference": ctx["reference"],
        "compiles_in_window": after["compiled"] - before["compiled"],
        "counters": {"before": before, "after": after, **traced},
        "trace": traced.get("trace"), "trace_span_s": traced.get("span_s"),
        "cfg": cfg, "engine": ctx["engine"], "traffic": traffic,
    }


def run(cell: dict, args, clock) -> dict:
    ctx = setup(cell, args, clock)
    return window(ctx, args.seed, float(args.seconds), bool(args.trace),
                  float(args.trace_seconds), clock)
