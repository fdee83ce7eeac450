#!/usr/bin/env python3
"""Proof that ray_tpu's two main paths start and compute correctly on a chip.

    python chip_smoke.py              one chip: serve phase, then train phase
    python chip_smoke.py --chips 4    one four-chip host: four one-chip replicas
                                      behind the router, then one four-chip trainer
                                      worker against a one-device mesh
    python chip_smoke.py --allow-cpu  rehearsal of the same code at tiny sizes on
                                      the CPU; its last line says "platform": "cpu"

serve:  ray_tpu.init() (chips detected, not passed) -> serve.run(
        build_llm_engine_deployment(cfg, params_fn=..., num_tpus=1)) -> router ->
        replica worker -> ContinuousBatchingEngine, at Llama-3-8B widths with the
        depth one 16 GB chip holds. The replica is redeployed once: the second
        process must find the first one's compiled programs in the persistent cache.
train:  JaxTrainer(ScalingConfig(use_tpu=True)) -> TrainWorker -> make_train_step
        with the Pallas flash kernels on the path, at Llama-2-7B widths.

This process never initialises a jax backend and pins itself to the CPU: a chip
belongs to one process, and here that is only ever the worker that was leased it.
Every phase has a deadline and any failure, timeout or CPU device exits non-zero.
The last line of standard output is one JSON object naming the device the
workers really ran on; everything informative is on earlier lines.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import glob
import json
import os
import shutil
import signal
import sys
import tempfile
import threading
import time

# before anything can import jax: this process stays off the chip
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SEED = 0


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ------------------------------------------------------------------- sizes
@dataclasses.dataclass(frozen=True)
class Sizes:
    serve_cfg: object
    serve_why: str
    engine_kw: dict
    prompt_len: int
    gen_tokens: int
    train_cfg: object
    train_why: str
    train_batch: int
    train_seq: int
    kernel_shape: tuple  # [B, T, H, D] of the flash-vs-reference check
    mesh_seq: int        # --chips 4 trainer: below the flash threshold


def real_sizes() -> Sizes:
    from ray_tpu.models.llama import LlamaConfig

    return Sizes(
        # widths are the published ones; only depth is cut
        serve_cfg=dataclasses.replace(LlamaConfig.llama3_8b(), n_layers=12),
        serve_why=(
            "Llama-3-8B widths (d_model 4096, 32/8 heads, d_ff 14336, vocab "
            "128256, bf16), depth 12 of 32: compiled for a described v5e chip, "
            "paged_decode_multi (batch 16, 32k-token bf16 pool) takes 8.95 GB "
            "of arguments + 3.44 GB of temporaries = 12.4 GB of the chip's "
            "15.75 GiB; 14 layers take 14.1 GB and leave no room for a second "
            "block in flight"),
        engine_kw=dict(max_batch=16, page_size=16, n_pages=2048,
                       max_seq_len=512, default_max_tokens=64),
        prompt_len=256, gen_tokens=64,
        train_cfg=dataclasses.replace(LlamaConfig.llama2_7b(), n_layers=6),
        train_why=(
            "Llama-2-7B widths (d_model 4096, 32 heads, d_ff 11008, vocab "
            "32000, bf16), depth 6 of 32: parameters + AdamW moments are 8.86 "
            "GB and the compiled step's temporaries 3.23 GB = 12.1 GB; 8 "
            "layers need 15.5 GB"),
        train_batch=2, train_seq=2048,
        kernel_shape=(1, 2048, 32, 128),
        mesh_seq=512,
    )


def tiny_sizes() -> Sizes:
    from ray_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=128, max_seq_len=256, dtype="float32")
    return Sizes(
        serve_cfg=cfg, serve_why="tiny rehearsal config",
        engine_kw=dict(max_batch=4, page_size=8, n_pages=128, max_seq_len=128,
                       default_max_tokens=20),
        prompt_len=24, gen_tokens=20,
        train_cfg=cfg, train_why="tiny rehearsal config",
        train_batch=2, train_seq=64,
        kernel_shape=(1, 256, 2, 64),
        mesh_seq=64,
    )


# ------------------------------------------------------- processes and chips
def descendants() -> list[int]:
    """Live processes this one started, directly or not."""
    ppid: dict[int, int] = {}
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                # pid (comm) state ppid ...; comm may hold spaces
                ppid[int(entry)] = int(f.read().rsplit(")", 1)[1].split()[1])
        except (OSError, IndexError, ValueError):
            continue  # exited while we looked
    out, frontier = [], [os.getpid()]
    while frontier:
        parent = frontier.pop()
        kids = [p for p, pp in ppid.items() if pp == parent]
        out += kids
        frontier += kids
    return out


def open_chip_files(pid: int | str = "self") -> list[str]:
    """Chip device files process ``pid`` holds open (``/dev/accel*`` or a
    numbered ``/dev/vfio/<group>``): which chips it has taken, read from
    outside without asking its jax. Every process of a chip's host sees the
    chip as device id 0, so the files are what tells four one-chip workers
    apart."""
    held = set()
    fd_dir = f"/proc/{pid}/fd"
    for fd in os.listdir(fd_dir):
        try:
            target = os.readlink(os.path.join(fd_dir, fd))
        except OSError:
            continue  # closed while listing
        if target.startswith("/dev/accel") or (
                target.startswith("/dev/vfio/")
                and target.rsplit("/", 1)[1].isdigit()):
            held.add(target)
    return sorted(held)


def chip_holders() -> dict[int, list[str]]:
    """pid -> chip device files it holds open, over this process and every
    process it started."""
    held = {}
    for pid in [os.getpid(), *descendants()]:
        try:
            files = open_chip_files(pid)
        except OSError:
            continue  # exited while we looked
        if files:
            held[pid] = files
    return held


def wait_chips_released(deadline_s: float = 90.0) -> float:
    t0 = time.monotonic()
    while chip_holders():
        if time.monotonic() - t0 > deadline_s:
            raise RuntimeError(
                f"chips still held {deadline_s}s after shutdown: {chip_holders()}")
        time.sleep(0.25)
    return time.monotonic() - t0


def check_only_holders(reports: list[dict], on_chip: bool) -> None:
    """The workers that were leased chips hold them — each as many as its
    lease — and no other live process of ours, nor this one, holds any."""
    from ray_tpu.utils.device import holds_tpu_backend

    assert not holds_tpu_backend(), "the parent initialised a TPU backend"
    held = chip_holders()
    if not on_chip:
        assert not held, f"chip files open on a CPU rehearsal: {held}"
        return
    want = {r["pid"]: r["chip_files"] for r in reports}
    assert held == want, f"chip files held {held}, leases say {want}"
    for r in reports:
        assert len(r["chip_files"]) == r["count"], r


@contextlib.contextmanager
def phase(name: str, seconds: float):
    """A phase that overruns its deadline ends the run: an unschedulable
    num_tpus=1 actor would otherwise wait for ever."""

    def expired():
        say(f"FAILED: phase {name!r} exceeded its {seconds:.0f}s deadline")
        kill_descendants()
        os._exit(3)

    timer = threading.Timer(seconds, expired)
    timer.daemon = True
    timer.start()
    t0 = time.monotonic()
    say(f"phase {name}: start (deadline {seconds:.0f}s)")
    try:
        yield
    finally:
        timer.cancel()
    say(f"phase {name}: ok in {time.monotonic() - t0:.1f}s")


def dump_worker_logs(since: float, lines: int = 25) -> None:
    """After a failure: the end of every worker's stderr of this run, where
    the reason usually is (a chip worker's own traceback never reaches the
    driver when it hangs or dies first)."""
    from ray_tpu.config import get_config

    pattern = os.path.join(get_config().temp_dir, "**", "worker-*.err")
    for path in sorted(glob.glob(pattern, recursive=True), key=os.path.getmtime):
        if os.path.getmtime(path) < since or not os.path.getsize(path):
            continue
        with open(path, errors="replace") as f:
            tail = f.readlines()[-lines:]
        print(f"--- {path}\n{''.join(tail)}", file=sys.stderr, flush=True)


def watch_family(seen: set[int]) -> None:
    """Keep adding the processes this run starts to ``seen`` (daemon
    thread): one that is killed cannot unlink its own /dev/shm names, and
    the clean-up at the end may only remove names that are this run's."""

    def watch():
        while True:
            seen.update(descendants())
            time.sleep(0.5)

    threading.Thread(target=watch, daemon=True).start()


def kill_descendants() -> None:
    for pid in descendants():
        with contextlib.suppress(OSError):
            os.kill(pid, signal.SIGKILL)


# ------------------------------------------------------------- serve phase
def compile_log_path(pid: int) -> str:
    return os.path.join(tempfile.gettempdir(), "chip_smoke_compiles",
                        f"{pid}.jsonl")


def log_compiles() -> None:
    """From here on, every program this process gets ready appends
    [name, seconds, came_from_the_cache] to its compile log, from jax's own
    monitoring events: the seconds of the backend compile, or of the read
    from the persistent cache."""
    import jax.monitoring

    path = compile_log_path(os.getpid())
    os.makedirs(os.path.dirname(path), exist_ok=True)
    # the hit event fires on the compiling thread, inside the span the
    # duration event closes: remember it there until the span ends
    hit = threading.local()

    def on_event(event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            hit.flag = True

    def on_duration(event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with open(path, "a") as f:
                f.write(json.dumps([kw.get("fun_name", "?"), secs,
                                    getattr(hit, "flag", False)]) + "\n")
            hit.flag = False

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)


def make_params_fn(cfg, seed: int):
    """Weights are made in the replica from a seed, never shipped from here;
    one jitted program, so the second replica reads it from the cache. It
    runs in the replica before any compile, so it also starts the log."""

    def params_fn():
        import jax

        from ray_tpu.models.llama import llama_init

        log_compiles()
        return jax.jit(llama_init, static_argnums=1)(
            jax.random.PRNGKey(seed), cfg)

    return params_fn


def seeded_prompts(cfg, n: int, length: int) -> list[list[int]]:
    import numpy as np

    rng = np.random.default_rng(SEED)
    return rng.integers(1, cfg.vocab_size, (n, length)).tolist()


def check_tokens(toks, n: int, cfg) -> None:
    assert len(toks) == n, f"asked for {n} tokens, got {len(toks)}"
    assert all(isinstance(t, int) and 0 <= t < cfg.vocab_size for t in toks), toks


def decode_compiles(pid: int) -> tuple[float, int, int]:
    """(seconds, read from the cache, compiled) over the decode programs of
    replica ``pid`` — one per fused block size it used."""
    with open(compile_log_path(pid)) as f:
        runs = [(secs, hit) for name, secs, hit in map(json.loads, f)
                if name == "jit(paged_decode_multi)"]
    hits = sum(1 for _, hit in runs if hit)
    return sum(secs for secs, _ in runs), hits, len(runs) - hits


def replica_report(handle) -> dict:
    """The replica's own account of its device, and the chip files this
    process sees it hold."""
    import ray_tpu

    rep = ray_tpu.get(handle.device_report.remote(), timeout=600)
    return {**rep, "chip_files": open_chip_files(rep["pid"])}


def deploy(sizes: Sizes, num_replicas: int):
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_engine_deployment

    app = build_llm_engine_deployment(
        sizes.serve_cfg, params_fn=make_params_fn(sizes.serve_cfg, SEED),
        num_replicas=num_replicas, num_tpus=1, **sizes.engine_kw)
    serve.run(app, name="smoke", timeout_s=600)
    return serve.get_deployment_handle("LLMEngineServer", "smoke")


def check_replica_device(rep: dict, on_chip: bool) -> None:
    if on_chip:
        assert rep["platform"] == "tpu" and rep["count"] == 1, rep
        assert rep["peak_bytes_in_use"][0] > 0, rep
    else:
        assert rep["platform"] == "cpu", rep


def serve_phase(sizes: Sizes, on_chip: bool) -> None:
    import ray_tpu
    from ray_tpu import serve

    cfg, n_gen = sizes.serve_cfg, sizes.gen_tokens
    say(f"serve: {cfg.n_layers} layers — {sizes.serve_why}")
    prompts = seeded_prompts(cfg, 4, sizes.prompt_len)
    req = {"prompt_tokens": prompts[0], "max_tokens": n_gen}

    handle = deploy(sizes, 1)
    first = ray_tpu.get(handle.remote(req), timeout=600)["completion_tokens"]
    check_tokens(first, n_gen, cfg)
    again = ray_tpu.get(handle.remote(req), timeout=300)["completion_tokens"]
    assert again == first, "greedy output for a repeated prompt differs"
    streamed, n_deltas, last = [], 0, {}
    for last in handle.stream_deltas.stream_chunks(dict(req)):
        streamed += last["tokens"]
        n_deltas += 1
    assert last.get("done") is True and streamed == first, (
        "the streaming path disagrees with the unary path")

    before = ray_tpu.get(handle.engine_stats.remote(), timeout=60)
    outs = ray_tpu.get(
        [handle.remote({"prompt_tokens": p, "max_tokens": n_gen})
         for p in prompts], timeout=600)
    for out in outs:
        check_tokens(out["completion_tokens"], n_gen, cfg)
    after = ray_tpu.get(handle.engine_stats.remote(), timeout=60)
    steps = after["steps"] - before["steps"]
    tokens = after["tokens_out"] - before["tokens_out"]
    assert tokens == len(prompts) * n_gen, (tokens, before, after)
    assert steps < tokens, (
        f"{len(prompts)} concurrent requests took {steps} decode steps for "
        f"{tokens} tokens: they never shared a batch")
    say(f"serve: {3 + len(prompts)} requests answered ({sizes.prompt_len}-token "
        f"prompts, {n_gen} tokens each, one streamed in {n_deltas - 1} "
        f"deltas); {len(prompts)} concurrent ones shared decode batches "
        f"({tokens} tokens in {steps} steps); repeated greedy prompt identical")

    cold = replica_report(handle)
    check_replica_device(cold, on_chip)
    say(f"serve: replica pid {cold['pid']} on {cold['platform']} "
        f"{cold['kind']!r} x{cold['count']}, chip files {cold['chip_files']}, "
        f"TPU_VISIBLE_CHIPS={cold['visible_chips']}, peak_bytes_in_use "
        f"{cold['peak_bytes_in_use'][0]} of {cold['bytes_limit']} (the parent "
        f"exports JAX_PLATFORMS={os.environ['JAX_PLATFORMS']})")

    # a worker leased no chip that unpickles a jax array stays on the CPU,
    # while the replica holds the chip
    @ray_tpu.remote(num_cpus=0.5)
    def make_array():
        import jax.numpy as jnp

        return jnp.arange(8.0)

    @ray_tpu.remote(num_cpus=0.5)
    def eat_array(x):
        from ray_tpu.utils.device import device_report, holds_tpu_backend

        return {**device_report(), "holds_tpu": holds_tpu_backend(),
                "chip_files": open_chip_files(), "sum": float(x.sum())}

    plain = ray_tpu.get(eat_array.remote(make_array.remote()), timeout=120)
    assert plain["platform"] == "cpu" and not plain["holds_tpu"], plain
    assert plain["sum"] == 28.0 and not plain["chip_files"], plain
    say(f"serve: num_tpus=0 worker pid {plain['pid']} unpickled a jax array "
        f"on {plain['platform']}, TPU backend initialised: {plain['holds_tpu']}")
    check_only_holders([cold], on_chip)

    # redeploy once: a second process, the same programs, from the cache
    serve.delete("smoke", timeout_s=120)
    released = wait_chips_released()
    handle = deploy(sizes, 1)
    warm_out = ray_tpu.get(handle.remote(req), timeout=600)["completion_tokens"]
    assert warm_out == first, "the redeployed replica answers differently"
    warm = replica_report(handle)
    check_replica_device(warm, on_chip)
    assert warm["pid"] != cold["pid"], "the redeploy reused a process"
    (cold_s, cold_hits, cold_built) = decode_compiles(cold["pid"])
    (warm_s, warm_hits, warm_built) = decode_compiles(warm["pid"])
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or "<checkout>/.jax_cache"
    say(f"serve: chip back {released:.1f}s after delete; compile cache {cache}; "
        f"decode programs, first replica (pid {cold['pid']}): {cold_s:.2f}s, "
        f"{cold_built} compiled, {cold_hits} read from the cache; second "
        f"replica (pid {warm['pid']}): {warm_s:.2f}s, {warm_built} compiled, "
        f"{warm_hits} read from the cache")
    assert warm_hits > 0 and warm_built == 0, (
        "the second replica compiled a decode program the first had cached")
    if cold_hits == 0:
        assert warm_s < 0.5 * cold_s, (
            f"warm decode {warm_s:.2f}s is not well under cold {cold_s:.2f}s")
    else:
        say("serve: the cache came warm with the machine — the first replica "
            "read decode programs from it too, so there is no cold figure")

    serve.shutdown()
    say(f"serve: shutdown, chip back after {wait_chips_released():.1f}s")


# ------------------------------------------------------------- train phase
def kernel_vs_reference(shape, rows: int = 512) -> float:
    """Largest error of the flash kernel's first rows against plain float32
    attention on the same inputs, both computed on this worker's device."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import attention
    from ray_tpu.parallel.ring_attention import reference_attention

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(SEED + 1), 3)
    q, k, v = (jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)
               for key in (kq, kk, kv))
    got = attention(q, k, v, causal=True, impl="flash")
    want = reference_attention(*(x.astype(jnp.float32) for x in (q, k, v)),
                               causal=True)
    err = jnp.abs(got[:, :rows].astype(jnp.float32) - want[:, :rows]).max()
    return float(err)


def train_loop(config: dict) -> None:
    """Runs in the TrainWorker that was leased the chip."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train
    from ray_tpu.models.llama import llama_init, make_train_step
    from ray_tpu.utils.device import device_report

    cfg = config["cfg"]
    kernel_err = kernel_vs_reference(config["kernel_shape"])
    params = jax.jit(llama_init, static_argnums=1)(
        jax.random.PRNGKey(config["seed"]), cfg)
    optimizer = optax.adamw(1e-3)
    opt_state = optimizer.init(params)
    tokens = jax.random.randint(
        jax.random.PRNGKey(config["seed"] + 2),
        (config["batch"], config["seq"] + 1), 0, cfg.vocab_size, jnp.int32)
    batch = {"tokens": tokens}
    step = make_train_step(cfg, optimizer, attn_impl="auto")
    t0 = time.monotonic()
    compiled = step.lower(params, opt_state, batch).compile()
    compile_s = time.monotonic() - t0
    has_kernel = "tpu_custom_call" in compiled.as_text()
    losses = []
    for _ in range(3):  # the same seeded batch: the loss must not rise
        params, opt_state, loss = compiled(params, opt_state, batch)
        losses.append(float(loss))
    train.report({"losses": losses, "kernel_err": kernel_err,
                  "has_kernel": has_kernel, "compile_s": compile_s,
                  "device": {**device_report(),
                             "chip_files": open_chip_files()}})


def check_losses(losses: list[float]) -> None:
    import math

    assert len(losses) == 3 and all(math.isfinite(x) for x in losses), losses
    assert losses[-1] <= losses[0] * 1.01, f"loss rose: {losses}"


def run_trainer(loop, config: dict, resources_per_worker: dict | None) -> dict:
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    result = JaxTrainer(
        loop, train_loop_config=config,
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=True,
            resources_per_worker=resources_per_worker),
        run_config=RunConfig(
            name="chip_smoke",
            storage_path=os.path.join(tempfile.gettempdir(), "chip_smoke_train")),
    ).fit()
    if result.error is not None:
        raise result.error
    return result.metrics


def train_phase(sizes: Sizes, on_chip: bool) -> dict:
    cfg = sizes.train_cfg
    say(f"train: {cfg.n_layers} layers — {sizes.train_why}")
    m = run_trainer(train_loop, {
        "cfg": cfg, "seed": SEED, "batch": sizes.train_batch,
        "seq": sizes.train_seq, "kernel_shape": sizes.kernel_shape}, None)
    dev = m["device"]
    say(f"train: worker pid {dev['pid']} on {dev['platform']} {dev['kind']!r} "
        f"x{dev['count']}, chip files {dev['chip_files']}; step compiled in "
        f"{m['compile_s']:.1f}s, tpu_custom_call in its HLO: {m['has_kernel']}; "
        f"batch {sizes.train_batch} x {sizes.train_seq} tokens; losses "
        f"{[round(x, 4) for x in m['losses']]}; flash kernel vs float32 "
        f"reference on {sizes.kernel_shape}, first 512 rows: max abs err "
        f"{m['kernel_err']:.4f}; peak_bytes_in_use {dev['peak_bytes_in_use'][0]}")
    check_losses(m["losses"])
    # bf16 inputs, float32 accumulation: outputs are O(1), bf16 keeps 8 bits
    assert m["kernel_err"] < 3e-2, f"flash kernel error {m['kernel_err']}"
    if on_chip:
        assert dev["platform"] == "tpu" and dev["count"] == 1, dev
        assert m["has_kernel"], "the compiled train step holds no Pallas kernel"
    else:
        assert dev["platform"] == "cpu", dev
    return dev


# ----------------------------------------------------------- --chips 4 phases
def replicas_phase(sizes: Sizes, on_chip: bool, n: int = 4) -> None:
    """Four one-chip replicas behind the router: four distinct chips, the
    requests spread over all of them, and — same seed, so same weights — the
    same greedy tokens from each."""
    import ray_tpu
    from ray_tpu import serve

    cfg, n_gen = sizes.serve_cfg, sizes.gen_tokens
    say(f"replicas: {n} x num_tpus=1, {cfg.n_layers} layers — {sizes.serve_why}")
    handle = deploy(sizes, n)
    # one routing hint per replica: the same hint always reaches the same one
    by_pid: dict[int, tuple[str, dict]] = {}
    for i in range(64 * n):
        hint = f"replica-probe-{i}"
        rep = replica_report(handle.options(routing_hint=hint))
        by_pid.setdefault(rep["pid"], (hint, rep))
        if len(by_pid) == n:
            break
    assert len(by_pid) == n, f"reached only {len(by_pid)} of {n} replicas"
    reports = [rep for _, rep in by_pid.values()]
    for rep in reports:
        check_replica_device(rep, on_chip)
        say(f"replicas: pid {rep['pid']} on {rep['platform']} {rep['kind']!r} "
            f"x{rep['count']}, chip files {rep['chip_files']}, "
            f"TPU_VISIBLE_CHIPS={rep['visible_chips']}")
    visible = sorted(rep["visible_chips"] for rep in reports)
    assert visible == [str(i) for i in range(n)], f"leases carried {visible}"
    if on_chip:
        files = sorted(f for rep in reports for f in rep["chip_files"])
        assert len(set(files)) == n, f"the replicas share chips: {files}"
    check_only_holders(reports, on_chip)

    prompts = seeded_prompts(cfg, 4 * n, sizes.prompt_len)
    outs = ray_tpu.get(
        [handle.remote({"prompt_tokens": p, "max_tokens": n_gen})
         for p in prompts], timeout=900)
    for out in outs:
        check_tokens(out["completion_tokens"], n_gen, cfg)
    pinned = {pid: handle.options(routing_hint=hint)
              for pid, (hint, _) in by_pid.items()}
    stats = ray_tpu.get([h.engine_stats.remote() for h in pinned.values()],
                        timeout=60)
    served = {pid: st["tokens_out"] for pid, st in zip(pinned, stats)}
    assert all(served.values()), f"a replica served nothing: {served}"
    say(f"replicas: {len(prompts)} concurrent requests spread as tokens per "
        f"replica {served}")

    req = {"prompt_tokens": prompts[0], "max_tokens": n_gen}
    outs = ray_tpu.get([h.remote(req) for h in pinned.values()], timeout=300)
    answers = {pid: out["completion_tokens"] for pid, out in zip(pinned, outs)}
    ref = next(iter(answers.values()))
    check_tokens(ref, n_gen, cfg)
    assert all(a == ref for a in answers.values()), (
        f"greedy tokens differ between replicas: {answers}")
    say(f"replicas: the same greedy prompt gives the same {n_gen} tokens from "
        f"each of the {n} replicas")
    serve.shutdown()
    say(f"replicas: shutdown, chips back after {wait_chips_released():.1f}s")


def mesh_train_loop(config: dict) -> None:
    """One worker, four devices: the same seed and batch on a one-device
    mesh and on an fsdp x tp mesh over all of them."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu import train
    from ray_tpu.models.llama import llama_init, llama_loss
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.parallel.sharding import PartitionRules, specs_for_pytree
    from ray_tpu.utils.device import device_report

    cfg = config["cfg"]
    devices = jax.devices()
    optimizer = optax.adamw(1e-3)
    host_params = jax.device_get(jax.jit(llama_init, static_argnums=1)(
        jax.random.PRNGKey(config["seed"]), cfg))
    host_tokens = jax.device_get(jax.random.randint(
        jax.random.PRNGKey(config["seed"] + 2),
        (config["batch"], config["seq"] + 1), 0, cfg.vocab_size, jnp.int32))
    specs = specs_for_pytree(host_params, PartitionRules.llama())

    def run(spec: MeshSpec) -> dict:
        mesh = spec.build(devices[: spec.size])
        param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
        batch_sh = NamedSharding(mesh, P(("dp", "fsdp"), None))
        params = jax.device_put(host_params, param_sh)
        opt_state = optimizer.init(params)
        batch = {"tokens": jax.device_put(host_tokens, batch_sh)}

        def step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(
                lambda p: llama_loss(p, batch, cfg, mesh=mesh))(params)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        jit_step = jax.jit(
            step, in_shardings=(param_sh, None, batch_sh),
            out_shardings=(param_sh, None, NamedSharding(mesh, P())),
            donate_argnums=(0, 1))
        losses = []
        for _ in range(3):
            params, opt_state, loss = jit_step(params, opt_state, batch)
            losses.append(float(loss))
        param_bytes = [0] * len(devices)
        for leaf in jax.tree.leaves(params):
            for shard in leaf.addressable_shards:
                param_bytes[devices.index(shard.device)] += shard.data.nbytes
        return {"mesh": {k: v for k, v in mesh.shape.items() if v > 1},
                "losses": losses, "param_bytes": param_bytes}

    one = run(MeshSpec())
    four = run(MeshSpec(fsdp=2, tp=2))
    train.report({"one": one, "four": four,
                  "device": {**device_report(),
                             "chip_files": open_chip_files()}})


def mesh_phase(sizes: Sizes, on_chip: bool) -> dict:
    cfg = sizes.train_cfg
    say(f"mesh: one worker leased 4 chips, {cfg.n_layers} layers — "
        f"{sizes.train_why}; sequence {sizes.mesh_seq} (plain attention: a "
        f"Pallas kernel is not partitioned by the compiler)")
    m = run_trainer(mesh_train_loop, {
        "cfg": cfg, "seed": SEED, "batch": 4, "seq": sizes.mesh_seq},
        {"TPU": 4})
    dev, one, four = m["device"], m["one"], m["four"]
    say(f"mesh: worker pid {dev['pid']} on {dev['platform']} {dev['kind']!r} "
        f"x{dev['count']}, chip files {dev['chip_files']}")
    say(f"mesh: one device   losses {one['losses']} parameter bytes per device "
        f"{one['param_bytes']}")
    say(f"mesh: {four['mesh']} losses {four['losses']} parameter bytes per "
        f"device {four['param_bytes']}; bytes_in_use per device "
        f"{dev['bytes_in_use']}")
    assert dev["count"] == 4, dev
    if on_chip:
        assert dev["platform"] == "tpu" and len(dev["chip_files"]) == 4, dev
    check_losses(one["losses"])
    check_losses(four["losses"])
    tol = 2e-2 if cfg.dtype == "bfloat16" else 1e-4
    for a, b in zip(one["losses"], four["losses"]):
        assert abs(a - b) <= tol * abs(a), (
            f"four-device losses {four['losses']} differ from one-device "
            f"{one['losses']} by more than {tol:g}")
    total = sum(one["param_bytes"])
    assert one["param_bytes"][1:] == [0, 0, 0], one
    assert sum(four["param_bytes"]) < 1.5 * total and all(
        0 < b < 0.5 * total for b in four["param_bytes"]), (
        f"parameters are not spread: {four['param_bytes']} of {total}")
    return dev


# -------------------------------------------------------------------- main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse at tiny sizes on the CPU backend")
    args = ap.parse_args()

    import ray_tpu
    from ray_tpu.accelerators.tpu import TPUAcceleratorManager
    from ray_tpu.config import get_config, set_config
    from ray_tpu.utils.device import holds_tpu_backend

    found = TPUAcceleratorManager.get_current_node_chip_files()
    if args.allow_cpu:
        # the workers take the virtual CPU mesh and their chips are pretend
        os.environ["RT_FORCE_CPU_DEVICES"] = str(args.chips)
        sizes = tiny_sizes()
    else:
        if len(found) < args.chips:
            say(f"FAILED: needs {args.chips} chip(s), found {found} — no "
                f"accelerator here (--allow-cpu rehearses on the CPU)")
            return 2
        os.environ.pop("RT_FORCE_CPU_DEVICES", None)
        sizes = real_sizes()
    on_chip = not args.allow_cpu
    say(f"chip device files: {found}; TPU_ACCELERATOR_TYPE="
        f"{os.environ.get('TPU_ACCELERATOR_TYPE')}; JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')}")

    cfg = get_config()
    # a replica at these widths takes longer to construct than the 60 s a
    # CPU actor is given; session files go under TMPDIR, not a fixed /tmp
    cfg.worker_start_timeout_s = 600.0
    cfg.temp_dir = os.path.join(tempfile.gettempdir(), "ray_tpu")
    set_config(cfg)
    shutil.rmtree(os.path.dirname(compile_log_path(0)), ignore_errors=True)
    ours = {os.getpid()}
    watch_family(ours)
    started = time.time()

    try:
        # no num_tpus on the chip: detection must find them
        ray_tpu.init(num_tpus=args.chips if args.allow_cpu else None)
        advertised = ray_tpu.cluster_resources().get("TPU", 0)
        say(f"ray_tpu.init() advertises TPU: {advertised:g}")
        assert advertised >= args.chips, (
            f"init() advertises {advertised:g} chips, the run needs {args.chips}")
        assert not holds_tpu_backend()
        if args.chips == 1:
            with phase("serve", 600):
                serve_phase(sizes, on_chip)
            with phase("train", 420):
                device = train_phase(sizes, on_chip)
        else:
            with phase("replicas", 900):
                replicas_phase(sizes, on_chip)
            with phase("mesh", 600):
                device = mesh_phase(sizes, on_chip)
        assert not holds_tpu_backend(), "the parent initialised a TPU backend"
    except BaseException:
        dump_worker_logs(started)
        raise
    finally:
        with contextlib.suppress(Exception):
            ray_tpu.shutdown()
        deadline = time.monotonic() + 20
        while descendants() and time.monotonic() < deadline:
            time.sleep(0.2)
        kill_descendants()
        # what a killed process of this run may have left in /dev/shm, and
        # nothing of anybody else's: the arenas of this process's cluster
        # (session "c<pid>_...") and the rings our processes made
        for pattern in [f"rt_c{os.getpid()}_*",
                        *(f"rt_fp_{pid}_*" for pid in sorted(ours))]:
            for leftover in glob.glob(f"/dev/shm/{pattern}"):
                with contextlib.suppress(OSError):
                    os.unlink(leftover)

    result = {"ok": True, "device": {"platform": device["platform"],
                                     "kind": device["kind"],
                                     "count": device["count"]}}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
