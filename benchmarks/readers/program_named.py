"""The one program of the traced run that a metric's ``program`` argument
names: a pattern (``fnmatch``) over the names in ``run["trace"]["programs"]``.
A literal name matches itself alone. A cell serves one family, so
``jit_*_decode_multi`` finds that family's decode program and a metric of
every family is one entry. No match reads as nothing, and so do two: a share
or a step time is never summed over programs that only share a pattern."""
import fnmatch


def resolve(run: dict, program: str):
    trace = run.get("trace")
    found = fnmatch.filter(trace["programs"], program) if trace else ()
    return found[0] if len(found) == 1 else None
