"""Device time of one decode step: the ``paged_decode_multi`` programs'
time in the trace over the steps they ran."""
from benchmarks.readers.decode_steps import steps_and_seconds
from benchmarks.readers.program_named import resolve


def read(run: dict, program: str):
    program = resolve(run, program)
    got = None if program is None else steps_and_seconds(run, program)
    return None if got is None else 1e3 * got[1] / got[0]
