"""Device time of one jitted program (named, or found by its pattern:
``readers/program_named.py``) as a share of the device's busy time."""
from benchmarks.readers.program_named import resolve


def read(run: dict, program: str):
    trace, program = run.get("trace"), resolve(run, program)
    if program is None or not trace["busy_s"]:
        return None
    return 100.0 * trace["programs"][program]["seconds"] / trace["busy_s"]
