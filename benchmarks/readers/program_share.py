"""Device time of one jitted program as a share of the device's busy time."""


def read(run: dict, program: str):
    trace = run.get("trace")
    if not trace or not trace["busy_s"] or program not in trace["programs"]:
        return None
    return 100.0 * trace["programs"][program]["seconds"] / trace["busy_s"]
