"""``readers/prefill_roofline.py`` with the prompts taken from the trace
itself. That reader places a closed-loop unary request's prefill half-way
between its sending and its reply; where a request lives 10-25 s and a span
holds two or three 12,288-token prompts, that guess was 10-45 % off the
tokens the program counted (my chip runs, PR 31). The sure source is the
``engine.admit`` annotation of each prefill wave in the run's own profiler
trace (``readers/idle_by_phase.py`` reads the same file): ``prompts`` and
``tokens`` (true lengths, summed) of exactly the waves dispatched inside the
span, whose programs are the ones the trace timed. A wave's prompts share a
pad, so each counts as the wave's mean length. (A wave whose program
compiles inside the span is annotated twice, before and after the compile:
such a run is not ``correct`` anyway.)

The least time of those prefills (``roofline/<count>.py`` ``least_seconds``)
over the device time of ``program``, or of the operations whose names hold
every one of ``patterns`` (a kernel of the prefill program). A trace without
the annotation, the program or the kernel reads as nothing."""
from benchmarks.lib.configs import load_module

HOST_PLANE, ADMIT = "/host:CPU", "engine.admit"


def admitted_lens(planes) -> list[float]:
    """A length for every prompt of every prefill wave annotated on the host
    plane: the wave's true tokens over its prompts."""
    out = []
    for plane in planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != ADMIT:
                    continue
                stats = dict(ev.stats)
                prompts, tokens = stats.get("prompts"), stats.get("tokens")
                if stats.get("pad") and prompts and tokens:
                    out += [int(tokens) / int(prompts)] * int(prompts)
    return out


def _admitted(run: dict) -> list[float]:
    """The run's admitted prompts, read once and kept on the run itself."""
    if "admitted_lens" not in run:
        from benchmarks.readers.idle_by_phase import load_planes

        try:
            run["admitted_lens"] = admitted_lens(load_planes())
        except (FileNotFoundError, ImportError):
            run["admitted_lens"] = []
    return run["admitted_lens"]


def read(run: dict, count: str, program: str | None = None,
         patterns: list | None = None):
    trace = run.get("trace")
    if not trace:
        return None
    if program is not None:
        took = trace["programs"].get(program, {}).get("seconds")
    else:
        took = sum(s for name, s in trace["ops"]
                   if all(p in name for p in patterns))
    lens = _admitted(run) if took else None
    if not lens:
        return None
    least = load_module("roofline", count).least_seconds(
        run["cfg"], run["peaks"], lens)
    return 100.0 * least / took
