"""Tokens of every step sent inside the window over the time from its start to
the last step's fence, which is waited for once the window's time is up."""


def read(run: dict):
    t = run.get("train")
    if not t or not t["steps"]:
        return None
    return t["steps"] * t["tokens_per_step"] / t["span_s"]
