"""Sum over the sampled requests of (last less first token) over the sum of
(tokens - 1): the mean gap between tokens, in ms. Under a fixed multiset of
output lengths the denominator is the same in every run."""


def read(run: dict):
    recs = [r for r in run.get("recs") or [] if "first" in r and r["tokens"] > 1]
    if not recs:
        return None
    return 1e3 * sum(r["last"] - r["first"] for r in recs) / sum(
        r["tokens"] - 1 for r in recs)
