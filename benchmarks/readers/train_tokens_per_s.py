"""Tokens of the whole steps that ended inside the window over their span,
which the last step's fence closes."""


def read(run: dict):
    t = run.get("train")
    if not t or not t["steps"]:
        return None
    return t["steps"] * t["tokens_per_step"] / t["span_s"]
