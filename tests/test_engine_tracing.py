"""Spans, phases and counters inside the LLM engine and the replica lane
(utils/tracing.phase, llm/engine.py stamps, core/worker.py lane legs):
what ``engine_stats()["stages"]`` counts, what a profiler trace holds on
the loop thread's line, what a sampled request's trace shows below the
replica's ``::run`` span, and what it all costs a process that never
imports jax."""

import asyncio
import glob
import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu import serve, state
from ray_tpu.models.llama import LlamaConfig, llama_init
from ray_tpu.utils import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENGINE_KW = dict(max_batch=4, page_size=8, n_pages=64, max_seq_len=128)


@pytest.fixture(scope="module")
def tiny():
    import jax

    cfg = LlamaConfig.tiny()
    return cfg, llama_init(jax.random.PRNGKey(0), cfg)


def _delta(after: dict, before: dict, family: str, tag: str = "") -> dict:
    a = after[family].get(tag, {})
    b = before[family].get(tag, {})
    return {k: a.get(k, 0) - b.get(k, 0) for k in ("sum", "count")}


def _run(tiny, eos_id, prompts, max_tokens):
    """The prompts through one engine, submitted together; returns the
    stage totals' growth and the wall seconds the engine lived."""
    from ray_tpu.llm.engine import ContinuousBatchingEngine

    cfg, params = tiny

    async def go():
        eng = ContinuousBatchingEngine(params, cfg, eos_id=eos_id, **ENGINE_KW)
        before = metrics.stage_totals()
        t0 = time.perf_counter()
        await eng.start()
        outs = await asyncio.gather(
            *(eng.generate(p, max_tokens=max_tokens) for p in prompts))
        await eng.stop()
        return before, metrics.stage_totals(), time.perf_counter() - t0, outs

    return asyncio.run(go())


# eos 1000 is outside the tiny vocabulary: the reactive loop, which is what
# deployments take, with no request ending early; None is the planned loop
@pytest.mark.parametrize("eos_id", [1000, None], ids=["reactive", "planned"])
def test_stage_counters_match_the_admissions(tiny, eos_id):
    # two pad buckets (8 and 16 tokens): 3 prompts of 5 and one of 11, all
    # waiting when the loop first runs, so two waves: 4 x 8 and 1 x 16 rows
    prompts = [[1, 2, 3, 4, 5 + i] for i in range(3)] + [list(range(1, 12))]
    before, after, wall, outs = _run(tiny, eos_id, prompts, 12)
    assert all(len(o) == 12 for o in outs)
    n = 4
    for fam in ("rt_llm_queue_wait_seconds", "rt_llm_prefill_wait_seconds",
                "rt_llm_decode_seconds"):
        d = _delta(after, before, fam)
        assert d["count"] == n and d["sum"] > 0, (fam, d)
    assert _delta(after, before, "rt_llm_decode_tokens_total")["sum"] == n * 11
    assert _delta(after, before, "rt_llm_prefill_waves_total")["sum"] == 2
    assert _delta(after, before, "rt_llm_prefill_prompts_total")["sum"] == n
    assert _delta(after, before,
                  "rt_llm_prefill_true_tokens_total")["sum"] == 3 * 5 + 11
    # rows x pad of the wave buckets: 4 rows x 8 (one a dummy) + 1 x 16
    assert _delta(after, before,
                  "rt_llm_prefill_padded_tokens_total")["sum"] == 4 * 8 + 16
    phases = {p: _delta(after, before, "rt_llm_engine_phase_seconds", p)
              for p in after["rt_llm_engine_phase_seconds"]}
    for p in ("engine.admit", "engine.prefill_sync", "engine.decode_dispatch",
              "engine.block_sync", "engine.emit", "engine.free"):
        assert phases[p]["count"] >= 1, (p, phases)
    assert phases["engine.prefill_sync"]["count"] == 2
    # phases of one loop thread never overlap, so they fit into its life
    assert 0 < sum(d["sum"] for d in phases.values()) <= wall


@pytest.mark.parametrize("n_pages", [21, 64], ids=["binding", "roomy"])
def test_growth_and_deferral_counters_add_up(n_pages):
    """How often admission by the timeline engages, counted by the program:
    ten requests of 16 + 12..48 on four slots of a family with two kinds of
    pages. Every page drawn is drawn at admission (the prompt's) or grown
    (``rt_llm_pages_grown_total``), of each kind; the head of the queue waits
    for a slot (``rt_llm_admit_deferred_total{for="slots"}``) and, only where
    the pool binds, for pages."""
    import importlib

    from ray_tpu.llm.engine import ContinuousBatchingEngine

    W = importlib.import_module("benchmarks.lib.weights_cohere2_moe")
    cfg = importlib.import_module("ray_tpu.models.cohere2_moe").Cohere2MoeConfig.tiny(
        experts_held=(4, 12), vocab_held=(256, 512))
    eng = ContinuousBatchingEngine(
        W.make_params(W.seed_key(5), cfg), cfg, max_batch=4, page_size=8,
        max_seq_len=96, eos_id=1000, block_buckets=(4, 8),
        n_pages={"full": n_pages, "window": 25})
    lens = [48, 20, 36, 12, 48, 28, 40, 16, 44, 24]

    async def go():
        before = metrics.stage_totals()
        await eng.start()
        outs = await asyncio.gather(*(
            eng.generate([3 + i] * 16, max_tokens=m) for i, m in enumerate(lens)))
        await eng.stop()
        return before, metrics.stage_totals(), outs

    before, after, outs = asyncio.run(go())
    assert [len(o) for o in outs] == lens

    def grown(family, tag):
        return _delta(after, before, family, tag)["sum"]

    # a prompt of 16 draws 2 pages of either kind at admission; a reply of m
    # tokens reaches 16 + m positions: the ring stops at its 4 entries + 1
    for kind, at_end in (("full", lambda n: -(-n // 8)),
                         ("window", lambda n: min(-(-n // 8), 5))):
        assert grown("rt_llm_pages_grown_total", kind) == sum(
            at_end(16 + m) - 2 for m in lens) > 0
        assert grown("rt_llm_pages_drawn_total", kind) == (
            grown("rt_llm_pages_grown_total", kind) + 2 * len(lens))
    assert grown("rt_llm_admit_deferred_total", "slots") > 0
    assert (grown("rt_llm_admit_deferred_total", "pages") > 0) == (n_pages == 21)


def _logged_engine(tiny, **kw):
    """An engine whose loop logs, in order, what it dispatches and what it
    waits for: ("wave", prompts) a prefill wave dispatched, ("block", steps)
    a decode block dispatched, ("sync", steps) a decode block read back."""
    from ray_tpu.llm.engine import ContinuousBatchingEngine

    cfg, params = tiny
    eng = ContinuousBatchingEngine(params, cfg, **{**ENGINE_KW, **kw})
    log = []
    admit, dispatch, emit = (eng._admit_dispatch, eng._dispatch_block,
                             eng._emit_block)

    async def admit_logged(behind=False):
        groups = await admit(behind)
        log.extend(("wave", len(reqs)) for reqs, _ in groups)
        return groups

    async def dispatch_logged(carry):
        out = await dispatch(carry)
        log.append(("block", out[0]))
        return out

    def emit_logged(entry):
        log.append(("sync", entry[0]))
        emit(entry)

    eng._admit_dispatch, eng._dispatch_block, eng._emit_block = (
        admit_logged, dispatch_logged, emit_logged)
    return eng, log


def test_the_loop_waits_for_no_block_in_order_to_admit(tiny):
    """Ten requests on four slots under an ``eos_id``: every wave after the
    first admission's is dispatched while the block that ends its slot's
    last holder is still in flight — that block is read back only AFTER the
    wave's prefill is queued behind it — and counts as undrained."""
    eng, log = _logged_engine(tiny, eos_id=1000, block_buckets=(4, 8))
    prompts = [[1 + i, 2, 3, 4 + i] for i in range(10)]
    lens = [9, 13, 6, 17, 8, 12, 5, 10, 7, 11]

    async def go():
        before = metrics.stage_totals()
        await eng.start()
        outs = await asyncio.gather(*(
            eng.generate(p, max_tokens=m) for p, m in zip(prompts, lens)))
        await eng.stop()
        return before, metrics.stage_totals(), outs

    before, after, outs = asyncio.run(go())
    assert [len(o) for o in outs] == lens
    waves = [i for i, e in enumerate(log) if e[0] == "wave"]
    assert len(waves) >= 4 and log[0] == ("wave", 4), log
    for i in waves[1:]:
        # blocks dispatched before this wave, less those read back: the
        # newest — the one that scheduled the end of the slot's holder — is
        # in flight, and is waited for only after the wave
        flying = (sum(e[0] == "block" for e in log[:i])
                  - sum(e[0] == "sync" for e in log[:i]))
        assert flying >= 1, (i, log)
    n = _delta(after, before, "rt_llm_prefill_waves_total")["sum"]
    assert n == len(waves)
    assert _delta(after, before,
                  "rt_llm_admit_waves_undrained_total")["sum"] == n - 1


def test_the_merge_program_is_built_by_the_warm_waves(tiny):
    """``merge_carry`` is one program a wave bucket, whatever the wave's
    prompts or pad, and EVERY admission takes it, the first into an idle
    engine too: after waves of 1, 2, 4 and 8 one-token requests (the
    benchmark's warm-up) a loaded engine that hands slots on behind blocks
    in flight builds none."""
    from ray_tpu.llm.programs import merge_carry

    cfg, params = tiny
    eng, log = _logged_engine(tiny, eos_id=1000, max_batch=8,
                              block_buckets=(4, 8))

    def merges():
        return sorted(key[1:] for key in eng._compiled if key[0] is merge_carry)

    async def go():
        await eng.start()
        for pad in (8, 16):
            for wave in (1, 2, 4, 8):
                await asyncio.gather(*(
                    eng.generate(list(range(1, pad - 1)), max_tokens=1)
                    for _ in range(wave)))
        warm = merges()
        before = metrics.stage_totals()
        await asyncio.gather(*(
            eng.generate([1 + i, 2, 3], max_tokens=5 + i % 7)
            for i in range(20)))
        await eng.stop()
        return warm, merges(), before, metrics.stage_totals()

    warm, loaded, before, after = asyncio.run(go())
    # keyed by the wave bucket alone: first, slots, lens — [N] each
    assert warm == [((n,),) * 3 for n in (1, 2, 4, 8)]
    assert loaded == warm
    assert _delta(after, before, "rt_llm_admit_waves_undrained_total")["sum"] >= 3
    assert {e[1] for e in log if e[0] == "wave"} <= {1, 2, 3, 4, 5, 6, 7, 8}


def _engine_events(trace_dir: str) -> dict:
    """{line name: [(start_ns, end_ns, name, stats)]} of the ``engine.*``
    events on the trace's ``/host:CPU`` plane."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert files, f"no .xplane.pb under {trace_dir}"
    out = {}
    for plane in ProfileData.from_file(max(files, key=os.path.getmtime)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                    dict(ev.stats))
                   for ev in line.events if ev.name.startswith("engine.")]
            if evs:
                out[line.name] = sorted(evs)
    return out


def test_phases_land_in_the_profilers_trace(tiny, tmp_path):
    import jax

    from ray_tpu.llm.engine import ContinuousBatchingEngine

    cfg, params = tiny

    async def go():
        eng = ContinuousBatchingEngine(params, cfg, eos_id=1000, **ENGINE_KW)
        await eng.start()
        await eng.generate([1, 2, 3], max_tokens=12)  # compiles outside
        jax.profiler.start_trace(str(tmp_path))
        try:
            await asyncio.gather(eng.generate([1, 2, 3], max_tokens=30),
                                 eng.generate([4, 5, 6], max_tokens=30))
        finally:
            jax.profiler.stop_trace()
        await eng.stop()

    asyncio.run(go())
    lines = _engine_events(str(tmp_path))
    assert len(lines) == 1, sorted(lines)  # one loop, one thread, one line
    events = next(iter(lines.values()))
    names = {e[2] for e in events}
    assert {"engine.decode_dispatch", "engine.block_sync", "engine.emit",
            "engine.admit", "engine.prefill_sync"} <= names, names
    dispatches = [e for e in events if e[2] == "engine.decode_dispatch"]
    assert dispatches and all(e[3]["steps"] in (1, 4, 8, 16, 32, 64)
                              and e[3]["live"] >= 1 for e in dispatches)
    # pages held now, and the most the residents will hold (the timeline's
    # peak): two requests of 3 + 30 on pages of 8 reach 5 pages each
    assert all(0 < e[3]["held"] <= e[3]["promised"] <= 10 for e in dispatches)
    assert {e[3]["promised"] for e in dispatches} == {10}
    assert sum(e[3]["tokens"] for e in events
               if e[2] == "engine.emit") == 2 * 29  # all but the first tokens
    # a prefill wave's admit says how many prompts and true tokens it holds
    waves = [e[3] for e in events if e[2] == "engine.admit" and e[3].get("pad")]
    assert waves and all(w["tokens"] == 3 * w["prompts"] for w in waves)
    # no two intervals of the thread overlap unless one holds the other
    for a, b in zip(events, events[1:]):
        assert b[0] >= a[1] or b[1] <= a[1], (a, b)


@pytest.fixture(scope="module")
def rt():
    from ray_tpu.config import Config, set_config

    cfg = Config.from_env()
    cfg.tracing_enabled = True
    cfg.trace_sample_rate = 1.0
    set_config(cfg)
    ray_tpu.init(num_cpus=8)
    yield ray_tpu
    serve.shutdown()
    ray_tpu.shutdown()
    set_config(Config.from_env())


@pytest.fixture(scope="module")
def llm(rt, tiny):
    from ray_tpu.llm import build_llm_engine_deployment

    cfg, params = tiny
    serve.run(build_llm_engine_deployment(cfg, params=params, eos_id=1000,
                                          **ENGINE_KW),
              name="traced_llm", timeout_s=300)
    handle = serve.get_deployment_handle("LLMEngineServer", "traced_llm")
    req = {"prompt_tokens": [1, 2, 3], "max_tokens": 12}
    for _ in range(3):  # compiles, lane attach (the first calls ride RPC)
        assert len(ray_tpu.get(handle.remote(dict(req)),
                               timeout=300)["completion_tokens"]) == 12
    return handle


def _engine_spans() -> list[dict]:
    return [s for s in state.list_spans(limit=100000)
            if s.get("name", "").startswith("engine::")]


def test_sampled_request_shows_the_engine_under_run(llm):
    deltas = list(llm.stream_deltas.stream_chunks(
        {"prompt_tokens": [1, 2, 3, 4], "max_tokens": 20}))
    assert sum(len(d["tokens"]) for d in deltas) == 20
    deadline = time.time() + 40
    tree = None
    while time.time() < deadline and tree is None:
        time.sleep(1.0)  # every process flushes its spans once a second
        for row in state.list_traces(limit=50):
            tr = state.get_trace(row["trace_id"])
            if tr is None:
                continue
            by_id = {s["span_id"]: s for s in tr["spans"]}
            runs = [s for s in tr["spans"]
                    if s["name"] == "handle_request_streaming::run"]
            kids = {s["name"]: s for s in tr["spans"]
                    if s["name"].startswith("engine::")}
            if runs and len(kids) == 3:
                tree = (tr, runs[0], kids, by_id)
                break
    assert tree is not None, [r.get("root_name")
                              for r in state.list_traces(limit=50)]
    tr, run, kids, by_id = tree
    assert set(kids) == {"engine::queue", "engine::prefill", "engine::decode"}
    for s in kids.values():
        # under the replica's ::run span, straight or through the
        # replica wrapper's own spans
        p = s
        while p is not None and p is not run:
            p = by_id.get(p.get("parent_span_id"))
        assert p is run, (s, run)
        assert run["start_ts"] <= s["start_ts"] <= s["end_ts"] <= run["end_ts"] + 1e-3
    assert kids["engine::queue"]["stage"] == "queue"
    assert kids["engine::queue"]["end_ts"] <= kids["engine::prefill"]["start_ts"] + 1e-6
    assert kids["engine::prefill"]["end_ts"] <= kids["engine::decode"]["start_ts"] + 1e-6
    assert tr["critical_path"]["stages"]["queue"] > 0


def test_unsampled_request_emits_no_engine_span(llm):
    from ray_tpu.config import get_config

    time.sleep(2.5)  # spans of the requests before this test have landed
    before = len(_engine_spans())
    get_config().trace_sample_rate = 0.0  # the router's head decision
    try:
        out = ray_tpu.get(llm.remote({"prompt_tokens": [1, 2, 3],
                                      "max_tokens": 12}), timeout=120)
    finally:
        get_config().trace_sample_rate = 1.0
    assert len(out["completion_tokens"]) == 12
    time.sleep(2.5)
    assert len(_engine_spans()) == before
    # and a sampled one after it does (the check above is not vacuous)
    ray_tpu.get(llm.remote({"prompt_tokens": [1, 2, 3], "max_tokens": 12}),
                timeout=120)
    deadline = time.time() + 30
    while time.time() < deadline and len(_engine_spans()) < before + 3:
        time.sleep(0.5)
    assert len(_engine_spans()) == before + 3


def test_tracing_module_and_a_phase_stay_off_jax():
    code = (
        "import sys, time\n"
        "from ray_tpu.utils import tracing, metrics\n"
        "with tracing.phase('engine.test', steps=4) as ph:\n"
        "    ph.set(tokens=3)\n"
        "    time.sleep(0.01)\n"
        "got = metrics.stage_totals()['rt_llm_engine_phase_seconds']"
        "['engine.test']\n"
        "assert got['count'] == 1 and got['sum'] >= 0.01, got\n"
        "assert not [m for m in sys.modules if m == 'jax' or "
        "m.startswith(('jax.', 'jaxlib'))], 'jax was imported'\n"
        "print('clean')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, check=True,
                         capture_output=True, text=True, timeout=120)
    assert out.stdout.strip().endswith("clean")


class _Lane:
    """An async actor: its methods run on the worker's event loop."""

    async def ping(self):
        return 1

    async def block(self, seconds):
        time.sleep(seconds)  # on purpose: holds the event loop
        return 2

    async def legs(self):
        return metrics.stage_totals()["rt_serve_lane_seconds"]


def test_lane_observes_the_wait_for_the_event_loop(rt):
    actor = ray_tpu.remote(_Lane).remote()
    deadline = time.time() + 60
    while True:  # until calls ride the ring: each then observes both legs
        a = ray_tpu.get(actor.legs.remote(), timeout=60)
        b = ray_tpu.get(actor.legs.remote(), timeout=60)
        if b.get("loop", {}).get("count", 0) > a.get("loop", {}).get("count", 0):
            break
        assert time.time() < deadline, "no call ever rode the lane"
        time.sleep(0.2)
    before = ray_tpu.get(actor.legs.remote(), timeout=60)
    blocked = actor.block.remote(0.4)
    time.sleep(0.1)  # block() holds the loop now; ping is popped and waits
    assert ray_tpu.get(actor.ping.remote(), timeout=60) == 1
    assert ray_tpu.get(blocked, timeout=60) == 2
    after = ray_tpu.get(actor.legs.remote(), timeout=60)
    # block, ping and the second legs() (a call observes before it runs):
    # once a lane call, each leg
    for leg in ("ring", "loop"):
        assert after[leg]["count"] - before[leg]["count"] == 3, (before, after)
    waited = after["loop"]["sum"] - before["loop"]["sum"]
    assert 0.2 <= waited < 5.0, waited  # ping sat out the rest of block()
