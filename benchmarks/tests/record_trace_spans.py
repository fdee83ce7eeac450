#!/usr/bin/env python3
"""Record the small trace that ``test_span_readers.py`` reads: a few decode
blocks of a tiny engine on the attached chip, with the engine's host phases
(``tracing.phase`` -> ``TraceAnnotation``) beside the device's own lines.

    python3 benchmarks/tests/record_trace_spans.py <output directory>

The Python tracer is off. What the test does not read is then cut from the
file — the ``/host:metadata`` plane (the programs' HLO, 860 KB), the device's
``XLA Ops`` lines and the metadata only they name — which leaves the host
plane whole and the device's ``XLA Modules``, under 100 KB. Cutting needs the
``XSpace`` proto classes that tensorflow ships; without them the file stays
whole and the script says so."""
import asyncio
import glob
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402

from ray_tpu.llm.engine import ContinuousBatchingEngine  # noqa: E402
from ray_tpu.models.llama import LlamaConfig, llama_init  # noqa: E402


async def record(out: str) -> None:
    cfg = LlamaConfig(vocab_size=256, d_model=64, n_layers=1, n_heads=4,
                      n_kv_heads=2, d_ff=128, max_seq_len=128, dtype="float32")
    eng = ContinuousBatchingEngine(
        llama_init(jax.random.PRNGKey(0), cfg), cfg, max_batch=4, page_size=8,
        n_pages=64, max_seq_len=64, eos_id=1000)  # eos: the reactive loop
    await eng.start()
    prompt = [1, 2, 3, 4, 5]
    await eng.generate(prompt, max_tokens=13)  # compiles: blocks of 8 and 4
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(out, profiler_options=options)
    for _ in range(2):  # each: one prefill, a block of 8, a block of 4
        assert len(await eng.generate(prompt, max_tokens=13)) == 13
    jax.profiler.stop_trace()
    await eng.stop()


def cut(out: str) -> None:
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError:
        print("no XSpace proto classes here: the trace is left whole", flush=True)
        return
    for extra in glob.glob(os.path.join(out, "**", "*.trace.json.gz"),
                           recursive=True):
        os.remove(extra)
    path, = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    kept = xplane_pb2.XSpace()
    for plane in space.planes:
        if plane.name == "/host:metadata":
            continue
        if plane.name.startswith("/device:TPU:"):
            lines = [ln for ln in plane.lines if ln.name == "XLA Modules"]
            del plane.lines[:]
            plane.lines.extend(lines)
            used = {ev.metadata_id for ln in lines for ev in ln.events}
            for key in [k for k in plane.event_metadata if k not in used]:
                del plane.event_metadata[key]
        kept.planes.append(plane)
    with open(path, "wb") as f:
        f.write(kept.SerializeToString())
    print(f"cut to {os.path.getsize(path)} bytes", flush=True)


def main() -> int:
    asyncio.run(record(sys.argv[1]))
    print(jax.devices()[0].device_kind, flush=True)
    cut(sys.argv[1])
    return 0


if __name__ == "__main__":
    sys.exit(main())
