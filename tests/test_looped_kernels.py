"""The looped family on the chip's branch without a chip: the paged decode
kernel at 16 KV heads, one query head a KV head, the plane a traced value;
and the engine's own loop over the interpreted kernels."""
import jax
import jax.numpy as jnp
import numpy as np

from _looped_common import CFG, PS, _engine, _serve, rel
from ray_tpu.llm import looped as programs
from ray_tpu.ops import paged_attention
from ray_tpu.ops.attention import gathered_attention
from ray_tpu.ops.paged_attention import paged_decode_attention, run_lengths


def test_the_walk_at_16_kv_heads_and_a_traced_plane_is_the_gathered_form():
    """H = KV = 16 (G = 1) heads of 128 lanes, 4 planes, tables of 5 pages
    (a block of the walk is 2 at the patched size): the plane to read is a
    value of a ``fori_loop``, as in the programs, and every plane's output is
    the plain gathered form's."""
    rng = np.random.default_rng(0)
    planes, P, KV, hd, B, MAXP = 4, 16, 16, 128, 3, 5
    kp, vp = (jnp.asarray(rng.standard_normal((planes, P, PS, KV, hd)),
                          jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((B, KV, hd)), jnp.float32)
    tables = jnp.asarray([[3, 4, 5, 9, 2], [7, 8, 1, 0, 0], [0] * 5], jnp.int32)
    lengths = jnp.asarray([37, 17, 0], jnp.int32)
    runs = run_lengths(tables)
    was = paged_attention._BLOCK_BYTES, paged_attention._RUN_PAGES
    paged_attention._BLOCK_BYTES = 2 * PS * KV * 2 * hd * 4
    paged_attention._RUN_PAGES = 2
    try:
        assert paged_attention.kv_block(kp, MAXP) == (2, 2)

        @jax.jit
        def every_plane(q, kp, vp):
            def one(plane, out):
                att = paged_decode_attention(q, kp, vp, plane, tables, lengths,
                                             runs=runs, interpret=True)
                return out.at[plane].set(att)

            return jax.lax.fori_loop(0, planes, one,
                                     jnp.zeros((planes, B, KV, hd)))

        got = every_plane(q, kp, vp)
    finally:
        paged_attention._BLOCK_BYTES, paged_attention._RUN_PAGES = was
    for plane in range(planes):
        want = gathered_attention(q[:2, None], kp[plane], vp[plane], tables[:2],
                                  lengths[:2] - 1)[:, 0].reshape(2, KV, hd)
        assert rel(got[plane, :2], want) < 1e-5
        assert not np.asarray(got[plane, 2]).any()   # a slot with no tokens


def test_the_kernel_interpreted_under_the_engine_gives_the_same_tokens(monkeypatch):
    """The K/V planes attended by ``paged_decode_attention``, interpreted,
    under the engine's own loop, a block of the walk two pages: the plain
    form's tokens and rows, the read counters saying which path ran."""
    cases = [(13, 9), (21, 6)]
    plain = _engine(block_buckets=(4,))
    _, want = _serve(plain, cases)
    assert not plain._kv_in_place
    monkeypatch.setattr(programs, "_reads_in_place", lambda: True)
    monkeypatch.setattr(paged_attention, "_BLOCK_BYTES",
                        2 * PS * CFG.n_kv_heads * 256 * 4)
    monkeypatch.setattr(paged_attention, "_RUN_PAGES", 2)
    programs.looped_decode_multi.clear_cache()
    try:
        eng = _engine(block_buckets=(4,))
        assert eng._kv_in_place and eng.programs.decode_in_place(eng.cache)
        _, got = _serve(eng, cases)
    finally:
        programs.looped_decode_multi.clear_cache()
    assert got == want
    for mine, theirs in zip(eng.cache, plain.cache):
        assert rel(mine[:, 1:], theirs[:, 1:]) < 1e-5
    assert eng._last_kv["kv_read"] < 1.7 * eng._last_kv["kv_live"]
    assert plain._last_kv["kv_read"] > 3 * plain._last_kv["kv_live"]


def test_both_programs_name_every_part():
    """Every instruction of both programs stands under a name of
    ``tracing.PARTS`` as it was (or is the loops' own)."""
    from ray_tpu.utils import tracing

    eng = _engine()
    _serve(eng, [(20, 6)])
    parts = eng.program_parts()
    for program in ("jit_looped_decode_multi", "jit_looped_prefill_batch"):
        found = set(parts[program]["parts"].values())
        assert {"embed", "project", "kv_write", "attention", "attn_out", "ffn",
                "head", "sample"} <= found, (program, sorted(found))
        assert found <= set(tracing.PARTS) | {tracing.SCAN, tracing.AMBIGUOUS}

