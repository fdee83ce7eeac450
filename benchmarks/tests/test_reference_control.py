"""The comparison that decides ``correct`` and its control, at a size a test
run can hold: the program agrees with the float32 reference, and the
reference computed in the nearest precision below the configuration's stands
out from it — for serving (the last layer's keys and values and the logits)
and for training (loss, gradient norm, gradient vectors)."""
import jax
import jax.numpy as jnp
import pytest

from benchmarks.lib import weights as W
from benchmarks.lib.configs import llama_config, load_json
from benchmarks.reference import dense_gqa as R

SEEDS = [3, 2**31 + 7, 99]


def tiny(name):
    cf = load_json("configs", name + ".json")
    return llama_config({**cf, **cf["tiny"]})


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("seed", SEEDS)
def test_program_forward_agrees_and_lower_precision_does_not(seed):
    from ray_tpu.models.llama import llama_forward

    cfg = tiny("mistral-7b-v0.3")
    tokens = jax.random.randint(jax.random.PRNGKey(seed % 1000), (1, 48), 3,
                                cfg.vocab_size)
    params = W.make_params(W.seed_key(seed), cfg, 2)
    want = R.forward(seed, cfg, tokens, zero_col=2)
    got, _ = llama_forward(params, tokens, cfg)
    assert rel(got, want["logits"]) < 1e-5
    assert float(jnp.abs(want["logits"][..., 2]).max()) == 0.0  # the eos column
    errs = {}
    for mode in ("bfloat16", "fp8"):
        low = R.forward(seed, cfg, tokens, mode=mode, zero_col=2)
        errs[mode] = max(rel(low["k"], want["k"]), rel(low["v"], want["v"]))
    assert errs["fp8"] > 3 * errs["bfloat16"] > 1e-4


@pytest.mark.parametrize("seed", SEEDS)
def test_program_gradients_agree_and_lower_precision_does_not(seed):
    from benchmarks.drivers.train import reference_numbers

    cfg = tiny("yi-6b")
    rc = {"batch": 1, "seq_len": 64}
    params = W.make_params(W.seed_key(seed), cfg)
    got = reference_numbers(seed, cfg, params, rc, "dense_gqa")
    vectors = [k for k in got if k.startswith("grad_vec_rel_err.")]
    assert {"grad_vec_rel_err." + n for n in R.picked_vectors(cfg.n_layers)
            } | {"grad_vec_rel_err.final_norm"} == set(vectors)
    assert got["loss_rel_err"] < 1e-4 and got["grad_norm_rel_err"] < 1e-4
    assert all(got[k] < 1e-4 for k in vectors), got
    low = {mode: reference_numbers(seed, cfg, None, rc, "dense_gqa", mode)
           for mode in ("bfloat16", "fp8")}
    for k in vectors:  # every vector that could be judged tells fp8 from bf16
        assert low["fp8"][k] > 3 * low["bfloat16"][k] > 1e-4, k


def test_the_first_layers_vectors_pass_through_attention_backward():
    """A fault in attention's backward alone (here: dq, dk and dv of every
    layer doubled) moves the first layer's vectors and leaves the last
    layer's ffn_norm and the final norm where they were: those two cannot
    stand for the flash backward kernels."""
    cfg = tiny("yi-6b")
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, 65), 0, cfg.vocab_size)
    want = R.loss_and_grads(11, cfg, tokens)["vectors"]

    @jax.custom_vjp
    def twice_back(x):
        return x

    twice_back.defvjp(lambda x: (x, None), lambda _, g: (2.0 * g,))
    plain = R._rope
    try:
        # q and k pass through _rope on their way into attention
        R._rope = lambda x, theta: twice_back(plain(x, theta))
        R._layer_vjp.clear_cache()
        got = R.loss_and_grads(11, cfg, tokens)["vectors"]
    finally:
        R._rope = plain
        R._layer_vjp.clear_cache()
    for name in ("first_attn_norm", "first_wq", "first_wk"):
        assert rel(got[name], want[name]) > 0.1, name
    for name in ("final_norm", "last_ffn_norm"):
        assert rel(got[name], want[name]) < 1e-6, name


def test_weights_are_the_seeds_alone():
    cfg = tiny("yi-6b")
    a = W.make_params(W.seed_key(2**31 + 1), cfg)
    b = W.make_params(W.seed_key(2**31 + 1), cfg)
    c = W.make_params(W.seed_key(2**31 + 2), cfg)
    same = jax.tree.map(lambda x, y: bool(jnp.array_equal(x, y)), a, b)
    assert all(jax.tree.leaves(same))
    assert not jnp.array_equal(a["layers_0"]["wq"]["kernel"],
                               c["layers_0"]["wq"]["kernel"])
    one = W.layer_weights(W.layer_key(W.seed_key(2**31 + 1), 1), cfg)
    assert jnp.array_equal(one["w_up"]["kernel"], a["layers_1"]["w_up"]["kernel"])
