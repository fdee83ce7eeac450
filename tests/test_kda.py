"""The gated delta rule (``ops/kda.py``) and its two kernels
(``ops/kda_pool.py``, ``ops/kda_chunk.py``): the chunked form against the
one-step form scanned, at lengths under a sub-block, under a chunk, at a
chunk and one either side and at several chunks + 5, with every lane's
log-decay at the lower bound (-5 a position: the overflow case the sub-blocks
exist for), at 0 and in between; the pool kernel in the interpreter against
``kda_step`` + ``.at[].set``; the chunk kernel in the interpreter against
both forms, at one, two and three chunks and at three + 5 (padded), and under
``models/kda_moe.py`` ``kda_mixer``. CPU, float32."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda, kda_chunk
from ray_tpu.ops.kda_chunk import kda_chunk_scan
from ray_tpu.ops.kda_pool import kda_pool_step

LOWER = -5.0


def rel(a, b):
    return float(jnp.linalg.norm(jnp.asarray(a) - jnp.asarray(b))
                 / jnp.linalg.norm(jnp.asarray(b)))


def _inputs(T, decay="random", seed=0, N=2, H=3, dk=16, dv=8):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = kda.l2_normalise(jax.random.normal(k[0], (N, T, H, dk))) * dk ** -0.5
    kk = kda.l2_normalise(jax.random.normal(k[1], (N, T, H, dk)))
    v = jax.random.normal(k[2], (N, T, H, dv))
    g = {"random": LOWER * jax.random.uniform(k[3], (N, T, H, dk)),
         "lower_bound": jnp.full((N, T, H, dk), LOWER),
         "zero": jnp.zeros((N, T, H, dk))}[decay]
    return q, kk, v, g, jax.random.uniform(k[4], (N, T, H))


def _one_step_scan(q, k, v, g, beta):
    N, T, H, dk = k.shape

    def step(S, x):
        return kda.kda_step(S, *x)

    S, o = jax.lax.scan(step, jnp.zeros((N, H, dk, v.shape[-1])),
                        tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), S


# chunk 64 in sub-blocks of 16: under a sub-block, under a chunk, a chunk and
# one either side, several chunks + 5
@pytest.mark.parametrize("decay", ["random", "lower_bound", "zero"])
@pytest.mark.parametrize("T", [3, 15, 40, 63, 64, 65, 197])
def test_the_chunked_form_is_the_one_step_rule(T, decay):
    args = _inputs(T, decay, seed=T)
    want_o, want_S = _one_step_scan(*args)
    got_o, got_S = kda.kda_chunked(*args, chunk=64, sub=16)
    assert got_o.shape == want_o.shape and got_S.dtype == jnp.float32
    assert bool(jnp.isfinite(got_o).all()) and bool(jnp.isfinite(got_S).all())
    assert rel(got_o, want_o) < 1e-5 and rel(got_S, want_S) < 1e-5


@pytest.mark.parametrize("chunk,sub", [(16, 16), (16, 4), (8, 2), (32, 8)])
def test_the_tiling_changes_no_result(chunk, sub):
    args = _inputs(45, "random", seed=1)
    want_o, want_S = _one_step_scan(*args)
    got_o, got_S = kda.kda_chunked(*args, chunk=chunk, sub=sub)
    assert rel(got_o, want_o) < 1e-5 and rel(got_S, want_S) < 1e-5
    with pytest.raises(ValueError, match="sub-blocks"):
        kda.kda_chunked(*args, chunk=16, sub=5)


def test_a_position_with_beta_zero_and_no_decay_moves_no_state():
    """How padding is kept out: positions 11.. of a sequence of 19 with
    ``g = 0`` and ``beta = 0`` leave the state of the first 11."""
    q, k, v, g, beta = _inputs(19, seed=2)
    _, want = _one_step_scan(*(a[:, :11] for a in (q, k, v, g, beta)))
    g, beta = g.at[:, 11:].set(0.0), beta.at[:, 11:].set(0.0)
    _, got = kda.kda_chunked(q, k, v, g, beta, chunk=8, sub=4)
    assert rel(got, want) < 1e-5


def test_a_long_wave_goes_through_the_scan_a_group_at_a_time(monkeypatch):
    """Past ``_SCAN_TOKENS`` a wave's prompts are mapped over in groups: the
    same results, prompt for prompt."""
    args = _inputs(16, seed=3, N=4)
    want_o, want_S = kda.kda_chunked(*args, chunk=8, sub=4)
    monkeypatch.setattr(kda, "_SCAN_TOKENS", 32)   # two prompts a group
    got_o, got_S = kda.kda_chunked(*args, chunk=8, sub=4)
    assert rel(got_o, want_o) < 1e-6 and rel(got_S, want_S) < 1e-6


def test_the_gate_lies_between_the_lower_bound_and_zero():
    a = jnp.asarray([[-50.0, -1.0, 0.0, 1.0, 50.0]] * 2)      # [H, dk]
    g = kda.kda_gate(a, jnp.log(jnp.asarray([0.5, 2.0])), LOWER)
    assert g.dtype == jnp.float32 and g.shape == (2, 5)
    assert float(g.min()) >= LOWER and float(g.max()) <= 0.0
    np.testing.assert_allclose(g[:, 2], LOWER / 2, rtol=1e-6)
    # exp(A_log) scales the argument a head: the second head's is steeper
    assert float(g[1, 3]) < float(g[0, 3]) < LOWER / 2


def test_the_one_step_rule_is_its_closed_form():
    """``S_t = (I - beta k k^T) Diag(alpha) S + beta k v^T`` and ``o = S_t^T
    q`` a head, written with matrices."""
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    dk, dv = 8, 5
    S = jax.random.normal(ks[0], (1, 1, dk, dv))
    q, k = jax.random.normal(ks[1], (1, 1, dk)), jax.random.normal(ks[2], (1, 1, dk))
    v = jax.random.normal(ks[3], (1, 1, dv))
    g = -jax.random.uniform(ks[4], (1, 1, dk))
    beta = jnp.asarray([[0.7]])
    got_S, got_o = kda.kda_step(S, q, k, v, g, beta)
    kc = k[0, 0][:, None]
    want = ((jnp.eye(dk) - 0.7 * kc @ kc.T) @ jnp.diag(jnp.exp(g[0, 0])) @ S[0, 0]
            + 0.7 * kc @ v[0, 0][None, :])
    assert rel(got_S[0, 0], want) < 1e-6
    assert rel(got_o[0, 0], want.T @ q[0, 0]) < 1e-6


# ------------------------------------------------------------------ the kernel
def _pool_inputs(L=3, R=5, H=4, dk=16, dv=128, seed=7):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    pool = jax.random.normal(ks[0], (L, R, H, dk, dv))
    q = jax.random.normal(ks[1], (R, H, dk))
    k = kda.l2_normalise(jax.random.normal(ks[2], (R, H, dk)))
    v = jax.random.normal(ks[3], (R, H, dv))
    g = LOWER * jax.random.uniform(ks[4], (R, H, dk))
    beta = jax.random.uniform(ks[5], (R, H))
    return pool, q, k, v, g, beta


@pytest.mark.parametrize("j", [0, 1, 2])
def test_the_pool_kernel_is_the_one_step_rule_in_place(j):
    pool, *step = _pool_inputs()
    got_pool, got_o = kda_pool_step(pool, j, *step, interpret=True)
    S, o = kda.kda_step(pool[j], *step)
    want_pool = pool.at[j].set(S)
    assert got_pool.dtype == jnp.float32 and got_o.shape == o.shape
    assert rel(got_pool[j], S) < 1e-6 and rel(got_o, o) < 1e-5
    # the other layers of the pool leave bit for bit
    others = [i for i in range(pool.shape[0]) if i != j]
    np.testing.assert_array_equal(got_pool[jnp.asarray(others)],
                                  want_pool[jnp.asarray(others)])


def test_a_row_with_beta_zero_and_decay_one_leaves_bit_for_bit():
    """The junk row and the rows of no live slot: ``g`` 0, ``beta`` 0 and
    zeros for q, k, v, as ``llm/kda_moe.py`` lays a dead row out — and with
    whatever q, k, v."""
    pool, q, k, v, g, beta = _pool_inputs()
    g, beta = g.at[0].set(0.0).at[3].set(0.0), beta.at[0].set(0.0).at[3].set(0.0)
    q, k, v = q.at[0].set(0.0), k.at[0].set(0.0), v.at[0].set(0.0)
    got, o = kda_pool_step(pool, 1, q, k, v, g, beta, interpret=True)
    np.testing.assert_array_equal(got[1, 0], pool[1, 0])
    np.testing.assert_array_equal(got[1, 3], pool[1, 3])
    assert not np.asarray(o[0]).any()
    assert not np.array_equal(got[1, 2], pool[1, 2])
    # the plain form, every other backend's, keeps them too
    S, _ = kda.kda_step(pool[1], q, k, v, g, beta)
    np.testing.assert_array_equal(S[0], pool[1, 0])
    np.testing.assert_array_equal(S[3], pool[1, 3])


def test_the_pool_kernel_takes_a_traced_layer_index():
    pool, *step = _pool_inputs(L=2)
    got, _ = jax.jit(lambda p, j: kda_pool_step(p, j, *step, interpret=True))(
        pool, jnp.int32(1))
    want, _ = kda.kda_step(pool[1], *step)
    assert rel(got[1], want) < 1e-6
    np.testing.assert_array_equal(got[0], pool[0])


# ------------------------------------------------------------ the chunk kernel
# chunks of 64 in sub-blocks of 16 at 128 key and value lanes, the kernel's
# shapes: one chunk, two (one program of two), three (three programs: the
# state carried in VMEM), and three + 5 (padded to four)
@pytest.mark.parametrize("decay", ["random", "lower_bound", "zero"])
@pytest.mark.parametrize("T", [64, 128, 192, 197])
def test_the_chunk_kernel_is_the_one_step_rule_and_the_plain_form(T, decay):
    args = _inputs(T, decay, seed=T, N=2, H=2, dk=128, dv=128)
    got_o, got_S = kda_chunk_scan(*args, interpret=True)
    want_o, want_S = _one_step_scan(*args)
    plain_o, plain_S = kda.kda_chunked(*args)
    assert got_o.shape == want_o.shape and got_o.dtype == jnp.float32
    assert got_S.shape == want_S.shape and got_S.dtype == jnp.float32
    assert bool(jnp.isfinite(got_o).all()) and bool(jnp.isfinite(got_S).all())
    assert rel(got_o, want_o) < 1e-5 and rel(got_S, want_S) < 1e-5
    assert rel(got_o, plain_o) < 1e-5 and rel(got_S, plain_S) < 1e-5


@pytest.mark.parametrize("N,T,H", [(3, 70, 3), (1, 64, 16)])
def test_the_chunk_kernel_at_other_heads_and_sequences(N, T, H):
    """Three heads are one program's (a block takes all of them); sixteen
    are two programs of eight, and each picks its own heads' beta."""
    args = _inputs(T, "random", seed=11, N=N, H=H, dk=128, dv=128)
    got_o, got_S = kda_chunk_scan(*args, interpret=True)
    want_o, want_S = kda.kda_chunked(*args)
    assert rel(got_o, want_o) < 1e-5 and rel(got_S, want_S) < 1e-5
    for h in range(H):  # a head at a time: no head reads another's beta
        assert rel(got_S[:, h], want_S[:, h]) < 1e-5


def test_the_chunk_kernel_keeps_padding_out_of_the_state_and_takes_bf16_values():
    """Positions 100.. of 197 with ``g = 0`` and ``beta = 0`` leave the state
    of the first 100, as the plain form's test says of it; v comes as the
    convolution leaves it, bf16."""
    q, k, v, g, beta = _inputs(197, seed=5, N=2, H=2, dk=128, dv=128)
    v = v.astype(jnp.bfloat16)
    _, want = _one_step_scan(*(a[:, :100] for a in (q, k, v, g, beta)))
    g, beta = g.at[:, 100:].set(0.0), beta.at[:, 100:].set(0.0)
    got_o, got = kda_chunk_scan(q, k, v, g, beta, interpret=True)
    assert rel(got, want) < 1e-5
    plain_o, _ = kda.kda_chunked(q, k, v, g, beta)
    assert rel(got_o, plain_o) < 1e-5


@pytest.mark.parametrize("dk,dv,chunk,sub,fits", [
    (128, 128, 64, 16, True), (256, 128, 64, 8, True), (128, 128, 32, 32, True),
    (16, 8, 64, 16, False),      # lanes that are no whole tile
    (128, 128, 48, 16, False),   # three sub-blocks: not two neighbours at a time
    (128, 128, 64, 4, False),    # sub-blocks under a sublane tile
    (128, 128, 64, 24, False)])  # a chunk that is not whole sub-blocks
def test_which_shapes_are_the_chunk_kernels(dk, dv, chunk, sub, fits):
    assert kda_chunk.fits(dk, dv, chunk, sub) is fits
    if not fits:
        args = _inputs(8, N=1, H=1, dk=dk, dv=dv)
        with pytest.raises(ValueError, match="kernel's shapes"):
            kda_chunk_scan(*args, chunk=chunk, sub=sub, interpret=True)


def test_the_mixer_takes_the_form_it_is_told_and_the_two_agree(monkeypatch):
    """``kda_mixer`` asks no platform: told ``kernel`` it runs the chunk
    kernel where the shapes are its own, told nothing (or at other shapes)
    the plain form — and the two give one result, padded prompts and all."""
    from ray_tpu.models import kda_moe as M

    cfg = M.KdaMoeConfig.tiny(n_heads=2, head_dim=128, chunk_size=64,
                              sub_chunk=16, max_seq_len=256)
    layer = M.kda_moe_layer_init(jax.random.PRNGKey(0), cfg, 0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 150, cfg.d_model))
    lens = jnp.asarray([150, 70], jnp.int32)
    valid = jnp.arange(150)[None, :] < lens[:, None]
    calls = []
    real = kda_chunk.kda_chunk_scan
    monkeypatch.setattr(kda_chunk, "kda_chunk_scan",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    want = M.kda_mixer(layer, x, cfg, valid, tails=lens)
    assert not calls
    got = M.kda_mixer(layer, x, cfg, valid, tails=lens, kernel=True)
    assert calls == [1]
    for a, b in zip(got, want):
        assert a.shape == b.shape and rel(a, b) < 1e-5
    # the tiny shape's 16 lanes are not the kernel's: the plain form, told or not
    tiny = M.KdaMoeConfig.tiny()
    M.kda_mixer(M.kda_moe_layer_init(jax.random.PRNGKey(0), tiny, 0),
                x[:, :20], tiny, kernel=True)
    assert calls == [1]
