"""The vocabulary of layer parts (``utils/tracing.py`` ``PARTS``, ``part``)
inside the serve programs of the four families, and the table that joins a
profiler trace's device events to them: read in a thread from what
``llm/engine.py`` ``_call`` compiled, merged over a program's shape variants,
sent with ``engine_stats()`` only while a ``jax.profiler`` trace is on. CPU,
tiny configs."""
import asyncio
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.llm.engine import ContinuousBatchingEngine
from ray_tpu.llm.serving import LLMEngineServer
from ray_tpu.models.cohere2_moe import Cohere2MoeConfig, cohere2_moe_init
from ray_tpu.models.llama import LlamaConfig, llama_init
from ray_tpu.models.mla_moe import MlaMoeConfig, mla_moe_init
from ray_tpu.models.sparse_moe import SparseMoeConfig, sparse_moe_init
from ray_tpu.utils import tracing

DENSE = {"embed", "project", "kv_write", "attention", "attn_out", "ffn",
         "head", "sample"}
EXPERTS = (DENSE - {"ffn"}) | {"router", "experts"}
FAMILIES = {
    # family: (config, init, engine options, decode program, prefill
    # program, the parts every one of its programs must name)
    "llama": (LlamaConfig.tiny(), llama_init, {"n_pages": 64},
              "jit_paged_decode_multi", "jit_paged_prefill_batch", DENSE),
    "mla_moe": (MlaMoeConfig.tiny(), mla_moe_init, {"n_pages": 64},
                "jit_mla_moe_decode_multi", "jit_mla_moe_prefill_batch",
                EXPERTS | {"ffn"}),
    "cohere2_moe": (Cohere2MoeConfig.tiny(), cohere2_moe_init,
                    {"n_pages": {"full": 61, "window": 16}},
                    "jit_cohere2_moe_decode_multi",
                    "jit_cohere2_moe_prefill_batch", EXPERTS | {"ffn"}),
    "sparse_moe": (SparseMoeConfig.tiny(), sparse_moe_init, {"n_pages": 41},
                   "jit_sparse_moe_decode_multi",
                   "jit_sparse_moe_prefill_batch",
                   EXPERTS | {"indexer", "select"}),
}


def _serve(family: str):
    """A tiny engine of the family after two requests (two pad buckets, a few
    decode blocks): its engine, and the tokens."""
    cfg, init, kw, *_ = FAMILIES[family]
    eng = ContinuousBatchingEngine(
        init(jax.random.PRNGKey(0), cfg), cfg, max_batch=3, page_size=8,
        max_seq_len=96, eos_id=None, block_buckets=(4, 8), **kw)

    async def go():
        await eng.start()
        outs = await asyncio.gather(
            eng.generate(list(range(3, 9)), max_tokens=10),
            eng.generate(list(range(5, 25)), max_tokens=6))
        await eng.stop()
        return outs

    return eng, asyncio.run(go())


@pytest.fixture(scope="module")
def served():
    cache = {}

    def get(family):
        if family not in cache:
            cache[family] = _serve(family)
        return cache[family]

    return get


@pytest.mark.parametrize("phase", ["decode", "prefill"])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_every_program_names_its_familys_parts(served, family, phase):
    eng, _ = served(family)
    *_, decode, prefill, want = FAMILIES[family]
    program = eng.program_parts()[decode if phase == "decode" else prefill]
    assert not program["stale"] and program["variants"] >= 1
    found = set(program["parts"].values())
    assert found <= set(tracing.PARTS) | {tracing.SCAN, tracing.AMBIGUOUS}
    assert want <= found, (family, phase, sorted(want - found))
    # a guard that names nothing: no program may lay a weight out (PR 42)
    assert "weights_concat" not in found
    for key in program["parts"]:
        assert re.fullmatch(r"[\w.\-]+\|((pred|[a-z]+\d+)\[[\d,]*\])?", key), key


@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_no_program_of_an_engine_lays_a_weight_out(served, phase):
    """The tiny ``paged_decode_multi`` and ``paged_prefill_batch`` on the tree
    an engine holds (the serving layout: ``wqkv``, ``w_gate_up``): no
    instruction of part ``weights_concat``, and no ``concatenate`` — alone or
    inside a fusion — whose result has the shape of a joined kernel. On
    ``llama_init``'s tree the decode program had both before PR 42."""
    from ray_tpu.llm.llama import (
        make_kv_pools, paged_decode_multi, paged_prefill_batch)

    eng, _ = served("llama")
    cfg, params, B = eng.cfg, eng.params, 3
    assert {"wqkv", "w_gate_up"} <= set(params["layers_0"])
    kpool, vpool = make_kv_pools(cfg, 8, 16, None)
    i32, temps = jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.float32)
    if phase == "decode":
        lowered = paged_decode_multi.lower(
            params, None, i32, i32, i32 + 1, jnp.zeros((B, 4), jnp.int32),
            kpool, vpool, jnp.ones(B, bool), temps, jax.random.PRNGKey(0),
            cfg=cfg, n_steps=4)
    else:
        lowered = paged_prefill_batch.lower(
            params, None, i32, jnp.zeros((B, 16), jnp.int32),
            jnp.zeros((B, 2), jnp.int32), kpool, vpool, i32 + 5, temps,
            jax.random.PRNGKey(0), cfg=cfg)
    text = lowered.compile().as_text()
    assert "weights_concat" not in set(
        tracing.instruction_parts(text)[1].values())
    assert "weights_concat" not in text
    joined = {str(tuple(params["layers_0"][n]["kernel"].shape)).replace(
        " ", "")[1:-1] for n in ("wqkv", "w_gate_up")}
    made = re.findall(r"= \w+\[([\d,]+)\]\S* concatenate\(", text)
    assert not joined & set(made), made


def test_most_named_instructions_of_the_decode_program_get_a_part():
    """Of the tiny ``paged_decode_multi``'s instructions that can be a device
    event and carry an ``op_name``, at least 85 % get a part or ``scan``."""
    from ray_tpu.llm.llama import make_kv_pools, paged_decode_multi

    cfg = LlamaConfig.tiny()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    kpool, vpool = make_kv_pools(cfg, 8, 16, None)
    B = 3
    compiled = paged_decode_multi.lower(
        params, None, jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.int32),
        jnp.ones(B, jnp.int32), jnp.zeros((B, 4), jnp.int32), kpool, vpool,
        jnp.ones(B, bool), jnp.zeros(B, jnp.float32), jax.random.PRNGKey(0),
        cfg=cfg, n_steps=4).compile()
    text = compiled.as_text()
    module, parts = tracing.instruction_parts(text)
    assert module == "jit_paged_decode_multi"
    events = [row for rows in tracing.program_instructions(text)[1]
              for row in rows if row[2] not in tracing._FREE]
    named = {key for _, key, _, op, _ in events if op}
    assert len(named) > 50 and set(parts) <= {row[1] for row in events}
    assert len(named & set(parts)) >= 0.85 * len(named)
    assert sum(p in tracing.PARTS for p in parts.values()) >= 0.6 * len(events)
    table = tracing.compiled_parts(compiled)
    assert table["parts"] == parts and not table["stale"]
    assert 0 < table["seconds"] < 5


def test_an_unknown_part_raises_where_it_is_written():
    with pytest.raises(ValueError, match="nonsense"):
        tracing.part("nonsense")
    with pytest.raises(ValueError, match="scan"):
        tracing.part(tracing.SCAN)  # the reader's pseudo-part is no scope


@pytest.mark.parametrize("op_name,part", [
    ("jit(f)/jit(main)/while/body/closed_call/ffn/dot_general", "ffn"),
    ("jit(f)/while/body/ffn/weights_concat/concatenate", "weights_concat"),
    ("jit(f)/while/body/select/while/body/add", "select"),
    ("jit(f)/while/body/dynamic_slice", "scan"),
    ("jit(step)/transpose(jvp(ffn))/mul", "ffn"),
    ("jit(f)/jit(select)/select_n", None),   # a jitted function's name
    ("jit(f)/mul", None),
    ("jit(f)/project/head", "project"),      # the last component: the primitive
])
def test_the_innermost_part_of_an_op_name(op_name, part):
    assert tracing.op_part(op_name) == part


def test_variants_that_disagree_name_neither():
    def variant(parts):
        return {"module": "jit_p", "parts": parts, "stale": False,
                "seconds": 0.25}

    a = variant({"fusion.1|f32[8]": "ffn", "fusion.2|f32[8]": "project"})
    b = variant({"fusion.1|f32[8]": "attention", "fusion.3|f32[8]": "head"})
    merged = tracing.merged_parts([a, b])["jit_p"]
    assert merged["parts"] == {"fusion.1|f32[8]": "?", "fusion.2|f32[8]":
                               "project", "fusion.3|f32[8]": "head"}
    assert merged["variants"] == 2 and merged["seconds"] == 0.5
    assert not merged["stale"]
    # a scan's own instruction, under no scope of PARTS
    text = ("HloModule jit_p, is_scheduled=true\n\nENTRY %main (a: f32[8]) -> "
            "f32[8] {\n  %a = f32[8]{0} parameter(0)\n  ROOT %fusion.1 = f32[8]"
            "{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="
            "\"jit(p)/while/body/mul\"}\n}\n")
    module, parts = tracing.instruction_parts(text)
    assert (module, parts) == ("jit_p", {"fusion.1|f32[8]": "scan"})
    assert tracing.program_instructions(text)[1] == [[
        ("a", "a|f32[8]", "parameter", None, []),
        ("fusion.1", "fusion.1|f32[8]", "fusion", "jit(p)/while/body/mul",
         ["a", "fc"])]]


def test_a_program_with_no_part_of_its_own_is_not_stale():
    """``merge_carry`` is two scatters under no scope: its table is empty and
    NOT stale, so the benchmark's ``part_share`` readers, which answer
    nothing for a run with a stale program, answer for every cell that
    admits through it (every serve cell since PR 56). Nothing can be stale:
    the compile cache keys on the scopes (``tests/test_compile_key.py``)."""
    from ray_tpu.llm.programs import merge_carry

    i32 = jnp.zeros((4,), jnp.int32)
    table = tracing.compiled_parts(
        merge_carry.lower(i32, i32, i32[:2], i32[:2], i32[:2]).compile())
    assert table["module"] == "jit_merge_carry"
    assert not set(table["parts"].values()) & set(tracing.PARTS)
    assert table["stale"] is False
    merged = tracing.merged_parts([table, table])["jit_merge_carry"]
    assert merged["stale"] is False and merged["variants"] == 2


def test_the_compilers_own_instructions_take_their_neighbours_part():
    """A weight fetched in slices ahead of its matmul has no ``op_name``: it
    belongs to what it feeds; a copy nobody names after a named producer to
    that producer; users that disagree and no operands name nothing."""
    def line(name, opcode, operands, op=None, shape="bf16[8,128]"):
        meta = f', metadata={{op_name="jit(p)/{op}/x"}}' if op else ""
        args = ", ".join("%" + o for o in operands)
        return f"  %{name} = {shape}{{1,0}} {opcode}({args}){meta}\n"

    text = ("HloModule jit_p, is_scheduled=true\n\nENTRY %main (w: bf16[8,128]) "
            "-> bf16[8,128] {\n"
            + line("w", "parameter", [])
            + line("slice-start.1", "slice-start", ["w"])
            + line("slice-done.1", "slice-done", ["slice-start.1"])
            + line("glue.2", "custom-call", ["slice-done.1"])
            + line("fusion.3", "fusion", ["glue.2"], op="ffn")
            + line("copy.4", "copy", ["fusion.3"])
            + line("both.5", "copy", ["w"])
            + line("fusion.6", "fusion", ["both.5"], op="project")
            + line("fusion.7", "fusion", ["both.5", "copy.4"], op="head")
            + "}\n")
    _, parts = tracing.instruction_parts(text)
    key = "{}|bf16[8,128]".format
    assert {parts[key(n)] for n in ("slice-start.1", "slice-done.1", "glue.2",
                                    "fusion.3")} == {"ffn"}
    assert parts[key("copy.4")] == "head"        # its one user's
    assert key("both.5") not in parts            # project or head: neither
    assert key("w") not in parts                 # a parameter is no event


def test_compiled_still_iterates_as_keys_and_counts_them(served):
    """``benchmarks/lib/replica*.py`` read ``engine._compiled`` both ways."""
    eng, _ = served("llama")
    keys = list(eng._compiled)
    assert len(eng._compiled) == len(keys) >= 3
    for key in keys:
        assert callable(key[0]) and key[0].__name__ in (
            "paged_decode_multi", "paged_prefill_batch", "merge_carry")
        assert (fn := key[0]) and (fn, *key[1:]) in eng._compiled
    names = {k[0].__name__ for k in keys}
    # the family's two, and the seam's merge that every admission takes
    assert names == {"paged_decode_multi", "paged_prefill_batch", "merge_carry"}
    by_program = eng.program_parts()
    assert sum(p["variants"] for p in by_program.values()) == len(keys)


def test_engine_stats_carries_the_table_only_under_a_profiler_trace(tmp_path):
    cfg = LlamaConfig.tiny()
    server = LLMEngineServer(cfg, llama_init(jax.random.PRNGKey(0), cfg),
                             None, max_batch=2, page_size=8, n_pages=32,
                             max_seq_len=64)

    async def go():
        out = await server({"prompt_tokens": [3, 4, 5], "max_tokens": 4})
        plain = server.engine_stats()
        jax.profiler.start_trace(str(tmp_path))
        try:
            traced = server.engine_stats()
        finally:
            jax.profiler.stop_trace()
        await server.engine.stop()
        return out, plain, traced, server.engine_stats()

    out, plain, traced, after = asyncio.run(go())
    assert len(out["completion_tokens"]) == 4
    assert set(plain) == set(after) == {
        "steps", "tokens_out", "waiting", "free_pages", "free_pages_now",
        "weights_prepared", "program_builds", "stages"}
    assert len(plain["program_builds"]) == len(server.engine._compiled)
    assert plain["weights_prepared"] == 1
    assert set(traced) == set(plain) | {"program_parts"}
    assert traced["program_parts"] == server.program_parts()
    assert "attention" in traced["program_parts"][
        "jit_paged_decode_multi"]["parts"].values()
