"""Every control of ``benchmarks/control_looped.py`` fails the comparison that
decides ``correct``: judged on ONE served request at the tiny size, the
control's mathematics standing in the reference's place against what the
program left in its planes and the tokens it emitted."""
import numpy as np
import pytest

from _looped_common import CFG, L, SEED, U, R, _engine, _serve_one, _slot_rows, rel
from benchmarks.control_looped import CONTROLS

PROMPT, TOKENS = 21, 24


@pytest.fixture(scope="module")
def served():
    eng = _engine()
    prompt = np.random.default_rng(1).integers(3, CFG.vocab_size, PROMPT).tolist()
    drawn = list(eng.free[0][:eng._pages_of(PROMPT + TOKENS)[0]])
    out = _serve_one(eng, prompt, TOKENS)
    return prompt, out, _slot_rows(eng, drawn, PROMPT + TOKENS - 1)


def _readings(served, variant=None, mode="float32"):
    """(the worst plane's relative error, the share of emitted tokens that
    are not the stand-in's own) of the program against a forward pass."""
    prompt, out, got = served
    if variant and variant.get("read_from") == "prompt":
        variant = {**variant, "read_from": PROMPT}
    fwd = R.forward(SEED, CFG, prompt + out[:-1], variant=variant, mode=mode,
                    logits_from=PROMPT - 1)
    rows = max(rel(got[n][p], fwd[n][p]) for n in "kv" for p in range(U * L))
    mine = np.argmax(np.asarray(fwd["logits"]), -1)
    return rows, float(np.mean(mine != np.asarray(out)))


def test_the_model_as_assumed_passes(served):
    rows, tokens = _readings(served)
    assert rows < 1e-5 and tokens == 0.0


@pytest.mark.parametrize("name", ["fp8", *CONTROLS])
def test_a_control_fails(served, name):
    rows, tokens = _readings(served, CONTROLS.get(name),
                             "fp8" if name == "fp8" else "float32")
    if name in ("threshold_half", "head_on_mean", "passes_5"):
        # the planes (of the first four passes) are the model's own: only
        # the state the head reads differs
        assert rows < 1e-5 and tokens > 0.2
    else:
        assert rows > 0.01, (rows, tokens)
