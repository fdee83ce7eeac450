"""``ops/cca.py``: Compressed Convolutional Attention's mixing in its two
forms — whole sequences, and one position of every slot from its row — and
``ops/basic.py`` ``rope_lanes``. CPU, float32, hand-built and seeded cases."""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.cca_moe import (CcaMoeConfig, cca_in, cca_moe_layer_init,
                                    cca_rope)
from ray_tpu.ops import basic, cca

CFG = CcaMoeConfig.tiny()
PS = 8


def _layer(seed=0):
    return cca_moe_layer_init(jax.random.PRNGKey(seed), CFG)


def _z(layer, T, seed=1, N=2):
    x = jax.random.normal(jax.random.PRNGKey(seed), (N, T, CFG.d_model))
    return cca_in(layer, x, CFG)


def _scanned(layer, z):
    """The step form from a zero row, a position at a time."""
    N, T, _ = z.shape
    cos, sin = cca_rope(CFG)
    row = jnp.zeros((N, cca.row_width(CFG)))
    out, rows = [], []
    for t in range(T):
        q, k, v, row = cca.cca_mix_step(layer, z[:, t:t + 1], row, cos, sin,
                                        jnp.full((N,), t), CFG)
        out.append((q, k, v))
        rows.append(row)
    return [jnp.concatenate(a, axis=1) for a in zip(*out)], rows


@pytest.mark.parametrize("T", [1, 2, 3, PS - 1, PS, PS + 1])
def test_the_sequence_form_is_the_step_form_scanned_from_a_zero_row(T):
    """Two taps after two taps reach back TWO positions: position 0 sees
    zeros twice, position 1 one of them. The row after every position is
    what the sequence form leaves at that true length."""
    layer, cos_sin = _layer(), cca_rope(CFG)
    z = _z(layer, T)
    positions = jnp.broadcast_to(jnp.arange(T)[None], (2, T))
    q, k, v, none = cca.cca_mix(layer, z, *cos_sin, positions, CFG)
    assert none is None
    (qs, ks, vs), rows = _scanned(layer, z)
    for got, want in ((q, qs), (k, ks), (v, vs)):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, atol=2e-6)
    for n in range(1, T + 1):  # the row AT each true length, pad behind it
        left = cca.cca_mix(layer, z, *cos_sin, positions, CFG,
                           tails=jnp.full((2,), n))[3]
        np.testing.assert_allclose(left, rows[n - 1], atol=2e-6)


def test_the_row_holds_u_c0_and_the_values_late_half():
    assert cca.row_width(CcaMoeConfig()) == 1280 + 1280 + 128 == 2688
    assert cca.row_width(CFG) == 2 * CFG.conv_width + CFG.v_half == 208
    layer = _layer()
    z = _z(layer, 5)
    _, rows = _scanned(layer, z)
    C, half = CFG.conv_width, CFG.v_half
    np.testing.assert_array_equal(rows[4][:, :C], z[:, 4, :C])           # u
    np.testing.assert_array_equal(rows[4][:, 2 * C:], z[:, 4, C + half:])  # v2
    w, b = layer["conv0"]["kernel"], layer["conv0"]["bias"]
    np.testing.assert_allclose(
        rows[4][:, C:2 * C], w[1] * z[:, 4, :C] + w[0] * z[:, 3, :C] + b,
        atol=1e-6)


def test_the_mean_on_a_hand_built_case():
    """4 query heads on 2 key heads, one lane: m_q[j] = (q[j] + k[j // 2]) /
    2; m_k[i] the mean of its two query heads' m_q."""
    qt = jnp.asarray([[1.0], [3.0], [5.0], [7.0]])
    kt = jnp.asarray([[10.0], [20.0]])
    m_q, m_k = cca.cca_mean(qt, kt)
    assert m_q[:, 0].tolist() == [5.5, 6.5, 12.5, 13.5]
    assert m_k[:, 0].tolist() == [6.0, 13.0]


def test_half_a_head_is_rotated_and_the_other_half_passes_bit_for_bit():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 16))
    cos, sin = basic.rope_freqs(8, 32, 5e6)
    positions = jnp.asarray([[0, 1, 2, 3, 4], [7, 8, 9, 10, 11]])
    out = basic.rope_lanes(x, cos, sin, positions, 8)
    np.testing.assert_array_equal(out[..., 8:], x[..., 8:])
    np.testing.assert_array_equal(
        out[..., :8], basic.rope(x[..., :8], cos, sin, positions))
    np.testing.assert_array_equal(out[0, 0], x[0, 0])   # position 0: no turn
    assert float(jnp.abs(out[1, :, :, :8] - x[1, :, :, :8]).max()) > 1e-3
    # every lane of the head: ``rope`` itself
    cos, sin = basic.rope_freqs(16, 32, 5e6)
    np.testing.assert_array_equal(basic.rope_lanes(x, cos, sin, positions, 16),
                                  basic.rope(x, cos, sin, positions))


def test_the_mixing_rotates_half_a_head_of_q_and_k_and_nothing_of_v():
    """The same inputs at positions shifted by 5: the unrotated lanes of q
    and k and every lane of v are bit for bit the same."""
    layer, (cos, sin) = _layer(), cca_rope(CFG)
    z = _z(layer, 4)
    at0 = jnp.broadcast_to(jnp.arange(4)[None], (2, 4))
    a = cca.cca_mix(layer, z, cos, sin, at0, CFG)
    b = cca.cca_mix(layer, z, cos, sin, at0 + 5, CFG)
    rot = CFG.rotary_dim
    for i in (0, 1):
        np.testing.assert_array_equal(a[i][..., rot:], b[i][..., rot:])
        assert float(jnp.abs(a[i][..., :rot] - b[i][..., :rot]).max()) > 1e-3
    np.testing.assert_array_equal(a[2], b[2])
    # and the norm of every head: sqrt(hd), times tau on k
    hd = CFG.head_dim
    np.testing.assert_allclose(jnp.linalg.norm(a[0], axis=-1), hd ** 0.5,
                               rtol=1e-4)
    np.testing.assert_allclose(
        jnp.linalg.norm(a[1], axis=-1),
        jnp.broadcast_to(hd ** 0.5 * layer["temp"], a[1].shape[:-1]), rtol=1e-4)


def test_a_position_reaches_back_two_and_the_value_one():
    """A change of position 2's input moves q and k of positions 2, 3 and 4
    (the second convolution reads c0 of 3, which read u of 2) and no other;
    the value's late half of position 3 and its early half of position 2."""
    layer, (cos, sin) = _layer(), cca_rope(CFG)
    z = _z(layer, 7, N=1)
    z2 = z.at[:, 2].add(1.0)
    positions = jnp.arange(7)[None]
    a = cca.cca_mix(layer, z, cos, sin, positions, CFG)
    b = cca.cca_mix(layer, z2, cos, sin, positions, CFG)
    for i in (0, 1):
        moved = np.asarray(jnp.abs(a[i] - b[i]).max(axis=(0, 2, 3)) > 1e-6)
        assert moved.tolist() == [False, False, True, True, True, False, False]
    dv = np.asarray(jnp.abs(a[2] - b[2]).max(axis=(0, 3)))      # [T, KV]
    assert (dv > 0).tolist() == [[False, False]] * 2 + [
        [True, False], [False, True]] + [[False, False]] * 3
    # position 0's value has a zero late half
    assert not np.asarray(a[2][:, 0, 1]).any()


def test_the_tail_is_read_at_the_true_length_whatever_the_pad_holds():
    layer, (cos, sin) = _layer(), cca_rope(CFG)
    z = _z(layer, 8)
    positions = jnp.broadcast_to(jnp.arange(8)[None], (2, 8))
    lens = jnp.asarray([3, 8])
    left = cca.cca_mix(layer, z, cos, sin, positions, CFG, tails=lens)[3]
    noisy = z.at[0, 3:].set(99.0)   # the pad behind prompt 0's true length
    again = cca.cca_mix(layer, noisy, cos, sin, positions, CFG, tails=lens)[3]
    np.testing.assert_array_equal(left, again)
    short = cca.cca_mix(layer, z[:, :3], cos, sin, positions[:, :3], CFG,
                        tails=jnp.asarray([3, 3]))[3]
    np.testing.assert_array_equal(left[0], short[0])


def test_the_op_imports_no_family():
    source = inspect.getsource(cca)
    assert "ray_tpu.models" not in source and "ray_tpu.llm" not in source
