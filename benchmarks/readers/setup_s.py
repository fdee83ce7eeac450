"""Process start to the first measured instant, on the host's clock."""


def read(run: dict):
    return run["setup_s"]
