"""The serving layout of a parameter tree (``ServePrograms.prepare``): derived
once, when ``ContinuousBatchingEngine`` takes a tree (its ``params`` setter),
and read by the family's programs as it lies. The Llama family joins wq|wk|wv
and w_gate|w_up (``models/llama.py`` ``llama_serving_layout``) and its halves
take one product where a layer holds the joined kernel; the five other
families prepare nothing. CPU, tiny configs."""
import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import ContinuousBatchingEngine
from ray_tpu.llm.programs import serving_programs
from ray_tpu.llm.serving import LLMEngineServer
from ray_tpu.models.cohere2_moe import Cohere2MoeConfig
from ray_tpu.models.eva import EvaConfig
from ray_tpu.models.llama import (
    LlamaConfig, llama_attn_out, llama_ffn, llama_init, llama_project,
    llama_serving_layout)
from ray_tpu.models.mla_moe import MlaMoeConfig
from ray_tpu.models.sparse_moe import SparseMoeConfig
from ray_tpu.models.ssm_moe import SsmMoeConfig
from ray_tpu.ops.basic import rope_freqs

CFG = LlamaConfig.tiny()
ENGINE = dict(max_batch=3, page_size=8, n_pages=64, max_seq_len=96,
              eos_id=None, block_buckets=(4, 8))


def _tree(seed: int = 0, cfg=CFG):
    return llama_init(jax.random.PRNGKey(seed), cfg)


def _nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _generate(eng, prompt, adapter=None, max_tokens=14):
    async def go():
        await eng.start()
        try:
            return await eng.generate(prompt, max_tokens=max_tokens,
                                      adapter=adapter)
        finally:
            await eng.stop()

    return asyncio.run(go())


def _adapters(cfg, rank=4):
    rng = np.random.default_rng(0)
    D, hd = cfg.d_model, cfg.head_dim
    return {"alpha": {
        "wq_a": rng.normal(0, 0.3, (D, rank)),
        "wq_b": rng.normal(0, 0.3, (rank, cfg.n_heads * hd)),
        "wv_a": rng.normal(0, 0.3, (D, rank)),
        "wv_b": rng.normal(0, 0.3, (rank, cfg.n_kv_heads * hd))}}


@pytest.mark.parametrize("prompt,adapter", [
    (list(range(3, 8)), None),            # one page, the smallest pad bucket
    (list(range(5, 45)), None),           # five pages, another pad bucket
    (list(range(3, 8)), "alpha"),         # q and v carry a LoRA slot's delta
], ids=["short", "long", "lora"])
def test_the_engine_on_its_layout_gives_the_plain_trees_tokens(prompt, adapter):
    """The same engine loop and the same programs, once on the tree as the
    engine lays it out and once on ``llama_init``'s (put past the setter):
    prefill, every decode block and the LoRA deltas on the q and v slices."""
    kw = dict(ENGINE, lora_adapters=_adapters(CFG), lora_rank=4)
    joined = ContinuousBatchingEngine(_tree(), CFG, **kw)
    plain = ContinuousBatchingEngine(_tree(), CFG, **kw)
    plain._params = _tree()
    assert "wqkv" in joined.params["layers_0"]
    assert "wq" in plain.params["layers_0"]
    want = _generate(plain, prompt, adapter)
    assert _generate(joined, prompt, adapter) == want
    if adapter:  # the delta is no zero: the slot's tokens are its own
        assert want != _generate(plain, prompt)


def test_assigning_a_tree_prepares_it_again():
    """``engine.params = tree`` (``benchmarks/lib/replica.py`` ``reseed``)
    goes through the hook: counted, laid out, and the next tokens are the
    new weights' under the programs compiled for the old ones."""
    eng = ContinuousBatchingEngine(_tree(0), CFG, **ENGINE)
    assert eng.weights_prepared == 1
    prompt = list(range(3, 12))
    first = _generate(eng, prompt)
    compiled = len(eng._compiled)
    eng.params = None                      # room for the next tree
    assert eng.params is None and eng.weights_prepared == 1
    eng.params = _tree(1)
    assert eng.weights_prepared == 2
    assert "wqkv" in eng.params["layers_0"] and "wq" not in eng.params["layers_0"]
    second = _generate(eng, prompt)
    assert len(eng._compiled) == compiled  # same shapes, same programs
    assert second != first
    assert second == _generate(
        ContinuousBatchingEngine(_tree(1), CFG, **ENGINE), prompt)


def test_the_deployment_counts_the_trees_its_engine_took():
    server = LLMEngineServer(CFG, _tree(), None, max_batch=2, page_size=8,
                             n_pages=32, max_seq_len=64)
    assert server.engine_stats()["weights_prepared"] == 1
    server.engine.params = _tree(1)
    assert server.engine_stats()["weights_prepared"] == 2


@pytest.mark.parametrize("cfg", [CFG, LlamaConfig.tiny(n_experts=2)],
                         ids=["dense", "moe_every_2nd"])
def test_the_hook_joins_in_place_and_keeps_nothing_twice(cfg):
    plain, tree = _tree(cfg=cfg), _tree(cfg=cfg)
    out = llama_serving_layout(tree, cfg)
    assert out is tree and _nbytes(out) == _nbytes(plain)
    for i in range(cfg.n_layers):
        was, layer = plain[f"layers_{i}"], out[f"layers_{i}"]
        assert not {"wq", "wk", "wv", "w_gate", "w_up"} & set(layer)
        assert jnp.array_equal(layer["wqkv"]["kernel"], jnp.concatenate(
            [was[n]["kernel"] for n in ("wq", "wk", "wv")], axis=1))
        if "moe" in was:   # an expert layer has no gate and up to join
            assert "w_gate_up" not in layer and layer["moe"] is not None
        else:
            assert jnp.array_equal(layer["w_gate_up"]["kernel"], jnp.concatenate(
                [was["w_gate"]["kernel"], was["w_up"]["kernel"]], axis=1))
        for name in set(was) - {"wq", "wk", "wv", "w_gate", "w_up"}:
            assert jax.tree.all(jax.tree.map(
                lambda a, b: a is b or bool(jnp.array_equal(a, b)),
                layer[name], was[name])), name
    for name in ("tok", "norm", "lm_head"):
        assert jax.tree.all(jax.tree.map(jnp.array_equal, out[name], plain[name]))
    # a tree already laid out is left alone (one engine's tree given to another)
    kernels = [out["layers_0"]["wqkv"]["kernel"]]
    assert llama_serving_layout(out, cfg)["layers_0"]["wqkv"]["kernel"] is kernels[0]


def test_the_hook_works_on_shapes_alone():
    """``tests/test_chip_compile.py`` lowers the engine's programs on the
    prepared tree's shapes."""
    shapes = jax.eval_shape(lambda: llama_serving_layout(_tree(), CFG))
    D, hd = CFG.d_model, CFG.head_dim
    assert shapes["layers_1"]["wqkv"]["kernel"].shape == (
        D, (CFG.n_heads + 2 * CFG.n_kv_heads) * hd)
    assert shapes["layers_1"]["w_gate_up"]["kernel"].shape == (D, 2 * CFG.d_ff)
    assert _nbytes(shapes) == _nbytes(_tree())


@pytest.mark.parametrize("half", ["project", "ffn"])
def test_the_halves_on_a_plain_tree_concatenate_no_weight(half):
    """The train step's path (``_block`` on ``llama_init``'s tree): three
    (two) products on the kernels as they lie. ``rope`` joins its two rotated
    halves of q and of k, activations: the only concatenations there are."""
    layer = _tree()["layers_0"]
    x = jnp.ones((2, 8, CFG.d_model), jnp.float32)
    cos, sin = rope_freqs(CFG.head_dim, CFG.max_seq_len, CFG.rope_theta)
    if half == "project":
        text = jax.jit(lambda l, x: llama_project(l, x, cos, sin, None, CFG)
                       ).lower(layer, x).as_text()
        assert text.count("stablehlo.concatenate") == 2
        assert text.count("stablehlo.dot_general") == 3
    else:
        att = jnp.ones((2, 8, CFG.n_heads, CFG.head_dim), jnp.float32)
        text = jax.jit(lambda l, x: llama_ffn(l, llama_attn_out(l, x, att))
                       ).lower(layer, x).as_text()
        assert "concatenate" not in text
        assert text.count("stablehlo.dot_general") == 4  # wo, gate, up, down
    assert "weights_concat" not in text


def test_the_halves_on_the_layout_take_one_product_and_agree():
    plain = _tree()["layers_0"]
    joined = llama_serving_layout(_tree(), CFG)["layers_0"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, CFG.d_model))
    cos, sin = rope_freqs(CFG.head_dim, CFG.max_seq_len, CFG.rope_theta)

    def project(layer, x):
        return llama_project(layer, x, cos, sin, None, CFG)

    text = jax.jit(project).lower(joined, x).as_text()
    assert text.count("stablehlo.dot_general") == 1
    for got, want in zip(project(joined, x), project(plain, x)):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    text = jax.jit(llama_ffn).lower(joined, x).as_text()
    assert text.count("stablehlo.dot_general") == 2 and "concatenate" not in text
    np.testing.assert_allclose(llama_ffn(joined, x), llama_ffn(plain, x),
                               rtol=1e-5, atol=1e-5)


OTHERS = {
    "mla_moe": (MlaMoeConfig.tiny(), {"n_pages": 64}),
    "cohere2_moe": (Cohere2MoeConfig.tiny(),
                    {"n_pages": {"full": 61, "window": 16}}),
    "sparse_moe": (SparseMoeConfig.tiny(), {"n_pages": 41}),
    "ssm_moe": (SsmMoeConfig.tiny(), {"n_pages": {"kv": 41, "state": 4}}),
    "eva": (EvaConfig.tiny(), {"n_pages": {"window": 13, "summary": 13}}),
}


@pytest.mark.parametrize("family", list(OTHERS))
def test_the_other_families_prepare_nothing(family):
    cfg, kw = OTHERS[family]
    programs = serving_programs(cfg)
    assert programs.family == family and programs.prepare is None
    tree = programs.init(jax.random.PRNGKey(0), cfg)
    eng = ContinuousBatchingEngine(tree, cfg, max_batch=3, page_size=8,
                                   max_seq_len=96, eos_id=None, **kw)
    assert eng.params is tree and eng.weights_prepared == 1
    eng.params = None   # their ``reference_check`` makes room this way
    assert eng.params is None and eng.weights_prepared == 1
    eng.params = tree
    assert eng.params is tree and eng.weights_prepared == 2
