"""The looped family's planes and pages: a probe that poisons one pass's
planes, a dead slot beside live ones, admission when the pages run dry, and
what is refused by name. The tiny size: ``tests/_looped_common.py``."""
import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _looped_common import CFG, L, PS, SEED, U, W, _engine
from ray_tpu.llm.engine import UnsupportedByModel
from ray_tpu.llm.looped import (looped_decode_multi, looped_prefill_batch,
                                make_pools)
from ray_tpu.utils import metrics

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def prefilled():
    """Three slots' tables, two prompts prefilled into them (slot 1 stays
    dead): (params, K pool, V pool, tables, first tokens, lengths)."""
    params = W.make_params(W.seed_key(SEED), CFG)
    kp, vp = make_pools(CFG, PS, 12, None)
    lens = np.array([13, 6], np.int32)
    rng = np.random.default_rng(3)
    tokens = np.zeros((2, 16), np.int32)
    for i, n in enumerate(lens):
        tokens[i, :n] = rng.integers(3, 256, n)
    pages = np.array([[1, 2], [5, 6]], np.int32)
    first, kp, vp = looped_prefill_batch(
        params, None, jnp.zeros(2, jnp.int32), jnp.asarray(tokens),
        jnp.asarray(pages), kp, vp, jnp.asarray(lens), jnp.zeros(2), KEY,
        cfg=CFG)
    tables = np.zeros((3, 4), np.int32)
    tables[0, :3], tables[2, :2] = [1, 2, 3], [5, 6]
    # copies: a view of a buffer that a later call donates would alias it
    return (params, np.array(kp), np.array(vp), tables,
            np.array(first), lens)


def _step(prefilled, kp=None, vp=None, active=(True, False, True), dead_tok=0):
    """One decode step of slots 0 and 2 -> (next tokens, K pool, V pool)."""
    params, kp0, vp0, tables, first, lens = prefilled
    tok = np.array([first[0], dead_tok, first[1]], np.int32)
    pos = np.array([lens[0], 0, lens[1]], np.int32)
    rows, _, _, kp, vp = looped_decode_multi(
        params, None, jnp.zeros(3, jnp.int32), jnp.asarray(tok),
        jnp.asarray(pos), jnp.asarray(tables),
        # the pools are DONATED: each call gets buffers of its own
        jnp.array(kp0 if kp is None else kp),
        jnp.array(vp0 if vp is None else vp), jnp.asarray(active),
        jnp.zeros(3), KEY, cfg=CFG, n_steps=1)
    return np.array(rows[0]), np.array(kp), np.array(vp)


@pytest.mark.parametrize("u", range(U))
def test_poisoning_a_passes_planes_moves_that_pass_and_the_later_ones(
        prefilled, u):
    """Slot 0's rows at positions before the step's, in the planes of pass
    ``u`` alone, are replaced: the rows the step writes at its own position
    stay bit for bit in every plane of an earlier pass and in pass ``u``'s
    first layer (written before anything of pass ``u`` is attended), and
    change in pass ``u``'s later layers and, through ``x``, in every plane of
    every later pass."""
    _, kp0, vp0, tables, _, lens = prefilled
    _, kp_a, vp_a = _step(prefilled)
    kp, vp = kp0.copy(), vp0.copy()
    mine = slice(u * L, (u + 1) * L)
    kp[mine, 1:3] = kp[mine, 1:3] * -3.0 + 1.0
    vp[mine, 1:3] = vp[mine, 1:3] * -3.0 + 1.0
    _, kp_b, vp_b = _step(prefilled, kp, vp)
    page, off = tables[0, lens[0] // PS], lens[0] % PS
    for plane in range(CFG.planes):
        same = all((a[plane, page, off] == b[plane, page, off]).all()
                   for a, b in ((kp_a, kp_b), (vp_a, vp_b)))
        assert same == (plane <= u * L), plane
    # the other live slot attends none of it
    page, off = tables[2, lens[1] // PS], lens[1] % PS
    assert (kp_a[:, page, off] == kp_b[:, page, off]).all()


def test_a_dead_slot_leaves_every_live_row_of_every_plane(prefilled):
    """A step with a dead slot between two live ones changes, in all 12
    planes, the two rows the live slots write and the junk page — nothing
    else, bit for bit — and the live slots' tokens and rows do not depend on
    what the dead slot holds."""
    _, kp0, vp0, tables, _, lens = prefilled
    toks_a, kp_a, vp_a = _step(prefilled)
    toks_b, kp_b, vp_b = _step(prefilled, dead_tok=77)
    stats = toks_a[3:]
    assert stats.tolist() == [2, 2 * U]   # live slots, exit passes
    assert toks_a[1] == 0 and toks_a[[0, 2]].tolist() == toks_b[[0, 2]].tolist()
    for before, after, other in ((kp0, kp_a, kp_b), (vp0, vp_a, vp_b)):
        changed = np.argwhere((before != after).any(axis=(3, 4)))
        written = {(p, int(tables[s, n // PS]), int(n % PS))
                   for p in range(CFG.planes) for s, n in ((0, lens[0]), (2, lens[1]))}
        assert {tuple(c) for c in changed if c[1] != 0} == written
        assert (after[:, 1:] == other[:, 1:]).all()   # the junk page aside


# ------------------------------------------------------- the pages run dry
# (prompt length, max_tokens) -> pages of 8: 3, 3, 4, 2, 3, 3
_REQS = [(10, 14), (17, 7), (20, 12), (5, 9), (9, 10), (14, 10)]


def _serve_logged(eng, reqs):
    """Serve ``reqs`` submitted at once; every try of ``_reserve_slot`` is
    logged as (request id, admitted, pages free before, slots free before,
    whether the timeline of demand with the request in it fits the pool)."""
    log, reserve = [], eng._reserve_slot

    def logged(req):
        free, empty = len(eng.free[0]), sum(r is None for r in eng.slot_req)
        fits = bool((eng._timeline(req) <= eng.capacity).all())
        slot = reserve(req)
        assert len(eng.free[0]) >= 0
        log.append((req.req_id, slot is not None, free, empty, fits))
        return slot

    eng._reserve_slot = logged

    async def run():
        await eng.start()
        rng = np.random.default_rng(4)
        prompts = [rng.integers(3, 256, n).tolist() for n, _ in reqs]
        outs = await asyncio.wait_for(asyncio.gather(*(
            eng.generate(p, max_tokens=m) for p, (_, m) in zip(prompts, reqs))),
            timeout=280)
        await eng.stop()
        return outs

    return asyncio.run(run()), log


def _undrained():
    return metrics.stage_totals()["rt_llm_admit_waves_undrained_total"].get(
        "", {}).get("sum", 0)


@pytest.mark.parametrize("eos", [None, 1000], ids=["planned", "reactive"])
def test_admission_when_the_pages_run_dry(eos):
    """Four slots and pages for two whole requests: six requests are served in
    the order they came, a slot never holds a page that is not free, slots
    stand empty while the head of the queue waits for PAGES (the request
    behind it that would fit does not jump it), a refill happens behind a
    block as soon as the TIMELINE of demand has room and not before — which
    is not when the free pages cover the request whole: a slot holds the
    pages its positions have reached — and every reply is the unbounded
    engine's."""
    want, _ = _serve_logged(_engine(eos_id=eos, n_pages=49), _REQS)
    eng = _engine(eos_id=eos, n_pages=7)   # 6 pages to draw: two requests
    before = _undrained()
    got, log = _serve_logged(eng, _REQS)
    assert got == want
    assert len(eng.free[0]) == 6 and not any(t.any() for t in eng.tables)
    need = {i + 1: (-(-n // PS), -(-(n + m) // PS))      # at admission, at its end
            for i, (n, m) in enumerate(_REQS)}
    admitted = [rid for rid, ok, *_ in log if ok]
    assert admitted == sorted(need)                      # in order, each once
    for rid, ok, free, empty, fits in log:
        assert ok == (fits and empty > 0)
        assert not ok or free >= need[rid][0]            # never over-drawn
    # the head waited for pages beside empty slots, and nobody jumped it
    waited = [(rid, free, empty) for rid, ok, free, empty, _ in log if not ok]
    assert any(empty >= 2 for _, _, empty in waited)
    # request 3 (3 pages of prompt, 4 at its end) is refused beside 3 free
    # pages: request 1 holds the other 3 to an end that lies after the step
    # at which 3 would draw its fourth. Request 5 (2, then 3) enters on 2
    # free pages: request 3 ends before 5 reaches its third
    assert any(rid == 3 and free == 3 for rid, free, _ in waited)
    assert any(ok and rid == 5 and free == 2 < need[5][1]
               for rid, ok, free, *_ in log)
    assert _undrained() > before   # refilled behind a block in flight


# ---------------------------------------------------------------- refusals
@pytest.mark.parametrize("feature,make", [
    ("kv_dtype='int8'", lambda: _engine(kv_dtype="int8")),
    ("lora_adapters", lambda: _engine(lora_adapters={"a": {}})),
    ("spec_enable", lambda: _engine(spec_enable=True)),
    ("export_pages", lambda: _engine().export_pages(1)),
    ("submit_prefilled", lambda: _engine().submit_prefilled([1], None, None, 3)),
    ("a K or V pool", lambda: _engine().kpool),
])
def test_what_takes_a_plane_for_a_weight_layer_is_refused(feature, make):
    with pytest.raises(UnsupportedByModel, match=feature.split("(")[0]) as e:
        make()
    assert "'looped'" in str(e.value)
    assert "every layer at every pass" in str(e.value)
