"""Prompt and output tokens of the requests completed inside the window,
over the window's seconds."""


def read(run: dict):
    recs = run.get("recs")
    if not recs:
        return None
    done = [r for r in recs if "error" not in r]
    return sum(r["prompt_len"] + r["tokens"] for r in done) / run["seconds"]
