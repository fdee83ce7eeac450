"""Forward and backward operations a token requires (no recomputation
counted; ``roofline/train_step.py``) times the tokens per second of the
traced span, over the chip's peak."""
from benchmarks.lib.configs import load_module


def read(run: dict, count: str):
    t = run.get("train")
    if not t or not t.get("trace") or not t["trace"]["steps"]:
        return None
    rate = t["trace"]["steps"] * t["tokens_per_step"] / t["trace"]["span_s"]
    per_token = load_module("roofline", count).flops_per_token(
        run["cfg"], run["traffic"]["seq_len"])
    return 100.0 * rate * per_token / run["peaks"]["bf16_flops_per_s"]
