"""``ops/grouped_swiglu.py`` interpreted on the CPU against the three
``jax.lax.ragged_dot`` calls it replaces under ``routed_experts`` for a
decode step's few rows: the kernel alone over given loads, then the whole
routed sum with the branch steered (``parallel/moe.py`` ``_streams_experts``
asks the backend; the test answers for it)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import grouped_swiglu as gs
from ray_tpu.parallel import moe
from ray_tpu.parallel.moe import routed_experts


def _ragged(xs, wg, wu, wd, load):
    """The other branch of ``routed_experts``, line for line."""
    hid = jax.nn.silu(jax.lax.ragged_dot(xs, wg, load)) * (
        jax.lax.ragged_dot(xs, wu, load))
    return jax.lax.ragged_dot(hid, wd, load)


def _experts(n, D, F, dtype, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"w_gate": (jax.random.normal(k[0], (n, D, F)) * D ** -0.5).astype(dtype),
            "w_up": (jax.random.normal(k[1], (n, D, F)) * D ** -0.5).astype(dtype),
            "w_down": (jax.random.normal(k[2], (n, F, D)) * F ** -0.5).astype(dtype)}


def _close(got, want, dtype):
    """Within the rounding of ``dtype``: the kernel keeps gate, up and their
    product in float32 where the reference rounds each, so in bf16 they
    differ by a few last places of the largest entries."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = max(float(np.abs(want).max(initial=0.0)), 1e-6)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    assert float(np.abs(got - want).max(initial=0.0)) <= tol * scale


# name: rows, D, F, the tile of F, the row chunk, the loads, the passes
KERNEL_CASES = {
    # 128 experts of 2048 x 768 in small: a whole expert a step, few rows each
    "kanana_like_whole_expert": (48, 64, 96, 96, 32,
                                 [3, 0, 1, 5, 0, 0, 2, 7, 1, 0, 4, 2], 8),
    # 16 experts of 4096 x 4096 in small: the hidden width in tiles
    "cohere_like_f_tiled": (96, 128, 512, 128, 64, [20, 31, 0, 45], 3),
    "no_rows_first": (32, 64, 128, 128, 32, [0, 4, 9, 3], 3),
    "no_rows_last": (32, 64, 128, 128, 32, [4, 9, 3, 0], 3),
    "no_rows_in_the_middle": (32, 64, 128, 128, 32, [4, 0, 0, 9], 2),
    # 40 rows from row 2: chunks [0, 32) and [32, 64)
    "larger_than_a_chunk": (64, 64, 128, 128, 32, [2, 40, 6], 4),
    # 17 rows from row 14: over the sublane tile at 16, one chunk
    "straddles_a_sublane_tile": (48, 64, 128, 128, 32, [14, 17, 3], 3),
    # 30 rows from row 14 reach past the chunk that starts at row 0
    "straddles_a_chunk": (48, 64, 128, 128, 32, [14, 30], 3),
    "every_row_one_expert": (32, 64, 256, 128, 32, [0, 32, 0], 1),
    "no_rows_at_all": (32, 64, 128, 128, 32, [0, 0, 0, 0], 0),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_matches_ragged_dot(case, dtype):
    R, D, F, tile, chunk, load, passes = KERNEL_CASES[case]
    ex = _experts(len(load), D, F, dtype)
    xs = jax.random.normal(jax.random.PRNGKey(7), (R, D)).astype(dtype)
    load = jnp.asarray(load, jnp.int32)
    got = gs._grouped_swiglu(xs, ex["w_gate"], ex["w_up"], ex["w_down"], load,
                             tile=tile, chunk=chunk, interpret=True)
    want = _ragged(xs, ex["w_gate"], ex["w_up"], ex["w_down"], load)
    total = int(load.sum())
    assert got.shape == want.shape and got.dtype == want.dtype
    _close(got[:total], want[:total], dtype)
    # rows past the last group belong to nobody: the kernel leaves zeros
    assert float(jnp.abs(got[total:].astype(jnp.float32)).max(initial=0.0)) == 0.0
    assert int(gs.expert_passes(load, chunk)) == passes


def test_the_tile_is_derived_from_the_shapes():
    # both cells' experts at their published widths, bf16
    assert gs.f_tile(2048, 768, 2) == 768      # a whole expert a step
    assert gs.f_tile(4096, 4096, 2) == 1024
    assert gs.f_tile(64, 96, 4) == 96          # small experts: whole
    with pytest.raises(ValueError):
        gs.f_tile(2 ** 20, 4096, 2)


def test_passes_are_summed_over_layers():
    load = jnp.asarray([[3, 0, 70, 1], [0, 0, 0, 0]], jnp.int32)
    # 70 rows from row 3 are two chunks of 64; an untouched layer adds none
    assert int(gs.expert_passes(load)) == 4
    assert int(moe.expert_passes(load, rows=74)) == 3  # not on a TPU: touched


# name: tokens, k, experts routed over, held, how many rows are live
ROUTED_CASES = {
    "all_held": (8, 3, 12, (0, 12), 8),
    "held_a_middle_share": (8, 3, 12, (4, 8), 8),
    "held_the_last_share": (8, 3, 12, (8, 12), 8),
    "dead_rows": (8, 3, 12, (0, 12), 5),
    "dead_rows_and_a_share": (8, 3, 12, (2, 9), 3),
    "all_rows_dead": (8, 3, 12, (0, 12), 0),
}


@pytest.mark.parametrize("case", sorted(ROUTED_CASES))
def test_routed_sum_is_the_same_through_either_branch(case, monkeypatch):
    T, k, E, held, live = ROUTED_CASES[case]
    D, F = 64, 128
    key = jax.random.split(jax.random.PRNGKey(3), 3)
    h = jax.random.normal(key[0], (T, D)).astype(jnp.bfloat16)
    idx = jnp.stack([jax.random.permutation(kk, E)[:k]
                     for kk in jax.random.split(key[1], T)]).astype(jnp.int32)
    w = jax.random.uniform(key[2], (T, k), jnp.float32, 0.1, 1.0)
    # dead rows between live ones
    valid = jnp.asarray(np.random.default_rng(1).permutation(T) < live)
    ex = _experts(held[1] - held[0], D, F, jnp.bfloat16)
    want, want_load = routed_experts(h, idx, w, ex, held, valid)
    monkeypatch.setattr(moe, "_streams_experts", lambda rows: True)
    got, load = routed_experts(h, idx, w, ex, held, valid)
    assert (np.asarray(load) == np.asarray(want_load)).all()
    inside = (idx >= held[0]) & (idx < held[1]) & valid[:, None]
    assert int(load.sum()) == int(inside.sum())
    _close(got, want, jnp.bfloat16)
    assert float(jnp.abs(got[~valid].astype(jnp.float32)).max(initial=0.0)) == 0.0
    # the kernel's count: every group here is a few rows, one chunk each
    assert int(moe.expert_passes(load, T * k)) == int((load > 0).sum())


def test_the_branch_is_on_backend_and_rows(monkeypatch):
    assert not moe._streams_experts(192)  # the CPU keeps ragged_dot
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe._streams_experts(192) and moe._streams_experts(384)
    assert not moe._streams_experts(3072)  # the smallest prefill program
