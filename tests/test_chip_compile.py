"""The main path's programs, compiled by the TPU's own compiler for a chip
that is described and not attached (a v5e 2x2 host), at the widths
``chip_smoke.py`` runs them: what interpret mode and the CPU backend cannot
refuse — tiling, fast-memory limits, device memory — is refused here, at no
chip time. Nothing runs, so these say nothing about results or speed.

All in this one file, compiled in the test's own process: only one process
may load the TPU's library, and it keeps it until it exits. The topology is
described inside a fixture, after a test of this file has started — never
while a module is imported."""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.models.llama import LlamaConfig, llama_init, make_train_step
from ray_tpu.utils import tracing


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # whatever the compiler raises where it cannot
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Shapes placed on the first described chip. The persistent cache is
    off around these compiles: an entry written without a chip cannot be
    read back without one, and the next run would warn and recompile."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    sharding = SingleDeviceSharding(topo.devices[0])

    def placed(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
            tree)

    yield placed
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _main_and_body(text):
    """(instructions of the computation that holds the loop over the steps,
    the program's only loop; instructions of every other computation: the
    loop's body and what it calls — its condition, the sampling branches) of
    a compiled decode program, rows of ``tracing.program_instructions``."""
    computations = tracing.program_instructions(text)[1]
    main, = [rows for rows in computations
             if any(opcode == "while" for _, _, opcode, *_ in rows)]
    return main, [row for rows in computations if rows is not main
                  for row in rows]


# chip_smoke's train phase: Llama-2-7B heads, sequence 2048
_QKV = _shape((1, 2048, 32, 128), jnp.bfloat16)


def test_flash_forward_compiles(one_chip):
    from ray_tpu.ops.flash_attention import flash_attention

    q = one_chip(_QKV)
    compiled = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, interpret=False)
    ).lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_backward_compiles(one_chip):
    from ray_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        out = flash_attention(q, k, v, interpret=False)
        return out.astype(jnp.float32).sum()

    q = one_chip(_QKV)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q).compile()
    # forward, dq and dkv kernels
    assert compiled.as_text().count("tpu_custom_call") >= 3


def _engine_args(one_chip, n_layers: int, n_kv_heads: int = 8,
                 vocab_size: int = 128256):
    """chip_smoke's serve phase: Llama-3-8B widths, batch 16, a 32k-token
    bf16 pool in 16-token pages, 512-token sequences — depth cut to two
    layers, which is what keeps the compile to seconds. 32 query heads on 8
    KV heads is Mistral's ratio too (with ``vocab_size`` 32,768 they are
    Mistral's widths); on 4 it is Yi's. The tree is the one an engine holds:
    in the family's serving layout (``PROGRAMS.prepare``, here on shapes
    alone)."""
    from ray_tpu.llm.llama import PROGRAMS

    cfg = dataclasses.replace(LlamaConfig.llama3_8b(), n_layers=n_layers,
                              n_kv_heads=n_kv_heads, vocab_size=vocab_size)
    params = jax.eval_shape(lambda: PROGRAMS.prepare(
        llama_init(jax.random.PRNGKey(0), cfg), cfg))
    assert "wqkv" in params["layers_0"] and "wq" not in params["layers_0"]
    pool = _shape((n_layers, 2048, 16, cfg.n_kv_heads, cfg.head_dim),
                  jnp.bfloat16)
    return cfg, one_chip(params), one_chip(pool), one_chip(_shape((2,), jnp.uint32))


@pytest.mark.parametrize("seq,kv_heads,temporaries", [
    (512, 8, 3_979_776), (2048, 8, 3_883_008), (2048, 4, 3_414_016)])
def test_paged_decode_multi_compiles(one_chip, monkeypatch, seq, kv_heads,
                                     temporaries):
    """At chip_smoke's 512-token window and at the benchmark cells' 2048, at
    Mistral's head ratio and at Yi's. On a TPU a plain pool is read in place
    by the paged kernel (``_reads_in_place`` asks ``jax.default_backend()``,
    which here is the CPU: the test answers for it, as for the flash
    kernels below). ``temporaries`` pins the program to the byte — a step's
    activations and the table's ``run_lengths``, 3.4-4.0 MB: the kernel's
    walk has other callers (the latent pool, the ring, the selected walk)
    that must not move this one, and the tree is the engine's (``wqkv``,
    ``w_gate_up``: PR 42), so no weight is laid out inside the program. On
    ``llama_init``'s tree the same program held 0.572 GB: wq|wk|wv and
    w_gate|w_up concatenated before the scan, 0.285 GB a layer."""
    from ray_tpu.llm.llama import paged_decode_multi

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    paged_decode_multi.clear_cache()
    cfg, params, pool, key = _engine_args(one_chip, 2, kv_heads)
    B, max_pages = 16, seq // 16
    i32 = one_chip(_shape((B,), jnp.int32))
    try:
        compiled = paged_decode_multi.lower(
            params, None, i32, i32, i32,
            one_chip(_shape((B, max_pages), jnp.int32)), pool, pool,
            one_chip(_shape((B,), jnp.bool_)),
            one_chip(_shape((B,), jnp.float32)), key, cfg=cfg,
            n_steps=8).compile()
    finally:
        paged_decode_multi.clear_cache()
    mem = compiled.memory_analysis()
    # embedding + head 2.1 GB, two layers 0.87 GB (0.84 at 4 KV heads), two
    # pools 0.27 GB each (0.13)
    assert 2.7e9 < mem.argument_size_in_bytes < 4.0e9
    # nothing of the weights and nothing of the window (the gathered one
    # added 67 MB at 2048)
    assert mem.temp_size_in_bytes == temporaries < 5e6
    text = compiled.as_text()
    # the joined kernels are read as they lie: nothing makes an array of their
    # shape — on the plain tree two ``pad_maximum_fusion`` a layer did (the
    # concatenations). The compiler's own fetch of a weight in slices ahead
    # of its product (``slice-start``, as for ``wo``) is no such thing.
    joined = re.compile(rf"bf16\[4096,(?:28672|{(32 + 2 * kv_heads) * 128})\]")
    assert not [row for rows in tracing.program_instructions(text)[1]
                for row in rows if joined.search(row[1]) and row[2] in (
                    "fusion", "concatenate", "pad", "copy", "copy-start",
                    "transpose", "convert", "dynamic-update-slice")]
    # one Mosaic kernel a layer, reading the pools where they lie
    assert text.count("tpu_custom_call") == cfg.n_layers
    # ... so nothing but the in-place row writes touches a pool: no layer's
    # pool sliced out of it, no gathered window in either order of the axes
    # (nor repeated to 32 heads), and no copy of a whole pool, which is what
    # a custom call reading a buffer the same loop updates could cost
    shapes = (rf"bf16\[(?:2048,16,{kv_heads}|16,{seq},{kv_heads}"
              rf"|16,{kv_heads},{seq}|16,{seq},32|16,32,{seq}),128\]")
    assert not re.findall(shapes, text), sorted(set(re.findall(shapes, text)))
    pool_ops = set(re.findall(
        rf"= bf16\[2,2048,16,{kv_heads},128\]\S* ([\w-]+)\(", text))
    assert {"parameter", "scatter"} <= pool_ops, pool_ops
    assert not pool_ops & {"copy", "slice", "dynamic-slice", "gather",
                           "transpose", "convert"}, pool_ops


@pytest.mark.parametrize("backend,N,Tp,vocab,temporaries", [
    ("cpu", 4, 256, 128256, 2.0e9), ("tpu", 8, 1792, 32768, 1.2e9)],
    ids=["plain-4x256", "blocked-8x1792"])
def test_paged_prefill_batch_compiles(one_chip, monkeypatch, backend, N, Tp,
                                      vocab, temporaries):
    """A wave of four 256-token prompts as this backend's rule has it (the
    plain form: ``_gqa_attn`` over the masked square), and the largest wave
    of `mistral7b_batch_closed` — eight prompts of 1,792 at Mistral's widths
    — as a TPU has it: one blocked attention kernel a layer
    (``ops/prefill_attention.py``) and no ``[N, KV, G * Tp, Tp]`` float32
    scores. The 8 x 1,792 wave's temporaries read 1,059,922,432 bytes
    blocked and 3,599,460,864 in the plain form (two layers, PR 47)."""
    from ray_tpu.llm.llama import paged_prefill_batch

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    paged_prefill_batch.clear_cache()
    cfg, params, pool, key = _engine_args(one_chip, 2, vocab_size=vocab)
    try:
        compiled = paged_prefill_batch.lower(
            params, None, one_chip(_shape((N,), jnp.int32)),
            one_chip(_shape((N, Tp), jnp.int32)),
            one_chip(_shape((N, Tp // 16), jnp.int32)), pool, pool,
            one_chip(_shape((N,), jnp.int32)),
            one_chip(_shape((N,), jnp.float32)), key, cfg=cfg).compile()
    finally:
        paged_prefill_batch.clear_cache()
    assert compiled.memory_analysis().temp_size_in_bytes < temporaries
    text = compiled.as_text()
    blocked = backend == "tpu"
    assert len(re.findall(r"%gqa_prefill_attention\S* = \S+ custom-call\(",
                          text)) == (cfg.n_layers if blocked else 0)
    KV, G = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    assert (f"f32[{N},{KV},{G * Tp},{Tp}]" in text) == (not blocked)


def test_train_step_takes_the_flash_kernels(one_chip, monkeypatch):
    """``attn_impl="auto"`` at sequence 2048 puts the Pallas forward and
    backward kernels into the compiled step when the backend is a TPU. The
    dispatch asks ``jax.default_backend()``, which here is the CPU: the test
    answers for it, the program gains no option."""
    import optax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(LlamaConfig.llama2_7b(), n_layers=1)
    optimizer = optax.adamw(1e-3)
    params = jax.eval_shape(lambda: llama_init(jax.random.PRNGKey(0), cfg))
    opt_state = jax.eval_shape(optimizer.init, params)
    step = make_train_step(cfg, optimizer, attn_impl="auto")
    compiled = step.lower(
        one_chip(params), one_chip(opt_state),
        {"tokens": one_chip(_shape((1, 2048 + 1), jnp.int32))}).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3


# the routed experts' grouped product in a compiled program: the decode
# step's kernel (ops/grouped_swiglu.py) and ragged_dot's Mosaic kernels
_SWIGLU = r"%ragged-dot-swiglu\S* = \S+ custom-call\("
_RAGGED_DOT = r"%ragged-dot-none\S* = \S+ custom-call\("


def _mla_moe_args(one_chip, n_layers: int):
    """The `kanana2_gen_closed` cell's programs at published widths (128
    experts of 768, 576-wide latent rows, 128,256 vocabulary rows), 32 slots
    of 2048 positions — depth cut to the dense layer and one expert layer."""
    from ray_tpu.models.mla_moe import MlaMoeConfig, mla_moe_init

    cfg = MlaMoeConfig(n_layers=n_layers, max_seq_len=2048)
    params = jax.eval_shape(lambda: mla_moe_init(jax.random.PRNGKey(0), cfg))
    pool = _shape((n_layers, 4097, 16, cfg.latent_width), jnp.bfloat16)
    return cfg, one_chip(params), one_chip(pool), one_chip(_shape((2,), jnp.uint32))


def test_mla_moe_decode_multi_compiles(one_chip, monkeypatch):
    """On a TPU the latent pool is attended in place by the paged kernel
    (``llm/mla_moe.py`` ``_reads_in_place`` asks ``jax.default_backend()``:
    the test answers for it, as above)."""
    from ray_tpu.llm.mla_moe import mla_moe_decode_multi
    from ray_tpu.llm.programs import MOE_STATS as STATS

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mla_moe_decode_multi.clear_cache()
    cfg, params, pool, key = _mla_moe_args(one_chip, 2)
    B = 32
    i32 = one_chip(_shape((B,), jnp.int32))
    try:
        lowered = mla_moe_decode_multi.lower(
            params, None, i32, i32, i32, one_chip(_shape((B, 128), jnp.int32)),
            pool, one_chip(_shape((B,), jnp.bool_)),
            one_chip(_shape((B,), jnp.float32)), key, cfg=cfg, n_steps=8)
        compiled = lowered.compile()
    finally:
        mla_moe_decode_multi.clear_cache()
    assert lowered.out_info[0].shape == (8, 32 + len(STATS))
    mem = compiled.memory_analysis()
    # embedding + head 1.05 GB, the dense layer 0.13, one expert layer 1.28,
    # the pool 0.15
    assert 2.5e9 < mem.argument_size_in_bytes < 2.8e9
    # the pool in the kernel's layout (below) is 0.17 GB of it
    assert mem.temp_size_in_bytes < 0.5e9
    text = compiled.as_text()
    # the attention kernel is one Mosaic call a layer, and a step's few rows
    # an expert go through the grouped SwiGLU kernel, one call an expert
    # layer in place of ragged_dot's three (parallel/moe.py _streams_experts)
    kernel = len(re.findall(r"%_paged_latent_attention\S* = \S+ custom-call\(",
                            text))
    assert kernel == cfg.n_layers
    assert len(re.findall(_SWIGLU, text)) == cfg.n_moe_layers == 1
    assert not re.findall(_RAGGED_DOT, text)
    assert text.count("tpu_custom_call") == kernel + cfg.n_moe_layers
    # ... reading the pool where it lies: no layer's pool sliced out of it, no
    # window gathered from that in either shape, none expanded to 32 heads of
    # keys or values
    shapes = (r"bf16\[(?:4097,16,576|4096,16,576|32,128,16,576|32,2048,576"
              r"|32,2048,32,(?:128|192|256))\]")
    assert not re.findall(shapes, text), sorted(set(re.findall(shapes, text)))
    pool_ops = set(re.findall(r"= bf16\[2,4097,16,576\]\S* ([\w-]+)\(", text))
    assert {"parameter", "scatter"} <= pool_ops, pool_ops
    assert not pool_ops & {"slice", "dynamic-slice", "gather", "transpose",
                           "convert"}, pool_ops
    # Two copies of the whole pool stay, and neither is the scan's: the
    # device's own layout of a [L, P, 16, 576] bf16 array puts the PAGE axis
    # minor-most ({1,3,2,0}: 576 is 4.5 lane tiles, 4,097 pads less), so any
    # program that wants a page's rows together, the gathered one too, turns
    # the pool row-major on entry and back for the donated output.
    copies = re.findall(r"= bf16\[2,4097,16,576\](\{[\d,]+)\S* copy\(", text)
    assert sorted(copies) == ["{1,3,2,0", "{3,2,1,0"], copies


@pytest.mark.parametrize("N,Tp", [(8, 1536), (1, 512)])
def test_mla_moe_prefill_batch_compiles(one_chip, monkeypatch, N, Tp):
    """The largest and the smallest wave the cell warms, as on a TPU (the
    routed product's branch asks the backend)."""
    from ray_tpu.llm.mla_moe import mla_moe_prefill_batch

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mla_moe_prefill_batch.clear_cache()
    cfg, params, pool, key = _mla_moe_args(one_chip, 2)
    try:
        compiled = mla_moe_prefill_batch.lower(
            params, None, one_chip(_shape((N,), jnp.int32)),
            one_chip(_shape((N, Tp), jnp.int32)),
            one_chip(_shape((N, Tp // 16), jnp.int32)), pool,
            one_chip(_shape((N,), jnp.int32)),
            one_chip(_shape((N,), jnp.float32)), key, cfg=cfg).compile()
    finally:
        mla_moe_prefill_batch.clear_cache()
    # heads run a group at a time (models/mla_moe.py _head_groups): all 32
    # at once wrote 5.2 GB of scores and probabilities
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9
    # thousands of rows a group: the grouped product stays ragged_dot's three
    text = compiled.as_text()
    assert len(re.findall(_RAGGED_DOT, text)) == 3 * cfg.n_moe_layers
    assert not re.findall(_SWIGLU, text)


# ------------------------------------------- window + full attention experts
def _cohere2_args(one_chip, layer_types):
    """command-a-plus-05-2026 at its published widths (128 query heads on 8
    KV heads, 16 held experts of 4096), a layer a kind given, the cell's
    pools cut to 2,049 pages a kind."""
    from ray_tpu.llm.cohere2_moe import make_pools
    from ray_tpu.models.cohere2_moe import Cohere2MoeConfig, cohere2_moe_init

    cfg = Cohere2MoeConfig(vocab_size=32768, n_layers=len(layer_types),
                           layer_types=layer_types, max_seq_len=13312,
                           experts_held=(0, 16), vocab_held=(0, 32768))
    params = one_chip(jax.eval_shape(
        lambda: cohere2_moe_init(jax.random.PRNGKey(0), cfg)))
    cache = one_chip(jax.eval_shape(lambda: make_pools(cfg, 16, 2049, None)))
    return cfg, params, cache, one_chip(_shape((2,), jnp.uint32))


@pytest.fixture(scope="module")
def cohere2_decode(one_chip):
    """(cfg, lowered, compiled) decode program of one window and one full
    layer for 48 slots and 8 steps, as a TPU's backend would choose its
    forms: compiled once for the two tests that read it."""
    from ray_tpu.llm.cohere2_moe import cohere2_moe_decode_multi

    cfg, params, cache, key = _cohere2_args(
        one_chip, ("sliding_attention", "full_attention"))
    B = 48
    i32 = one_chip(_shape((B,), jnp.int32))
    tables = (one_chip(_shape((B, 832), jnp.int32)),
              one_chip(_shape((B, 257), jnp.int32)))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        cohere2_moe_decode_multi.clear_cache()
        try:
            lowered = cohere2_moe_decode_multi.lower(
                params, None, i32, i32, i32, tables, *cache,
                one_chip(_shape((B,), jnp.bool_)),
                one_chip(_shape((B,), jnp.float32)), key, cfg=cfg, n_steps=8)
            return cfg, lowered, lowered.compile()
        finally:
            cohere2_moe_decode_multi.clear_cache()


def test_cohere2_moe_decode_multi_compiles(cohere2_decode):
    """One window and one full layer: each attends its own kind of pool in
    place, the window layer through the ring table's 257 entries from its
    first live page, 16 query heads a KV head in a 256-token block."""
    from ray_tpu.llm.programs import MOE_STATS

    cfg, lowered, compiled = cohere2_decode
    assert lowered.out_info[0].shape == (8, 48 + len(MOE_STATS))
    text = compiled.as_text()
    assert len(re.findall(r"%_paged_window_attention\S* = \S+ custom-call\(",
                          text)) == 1
    assert len(re.findall(r"%_paged_decode_attention\S* = \S+ custom-call\(",
                          text)) == 1
    # 384 rows over 16 held experts: one grouped SwiGLU kernel a layer, none
    # of ragged_dot's
    assert len(re.findall(_SWIGLU, text)) == cfg.n_layers
    assert not re.findall(_RAGGED_DOT, text)
    # no table gathered out of a pool: neither [B, entries * PS, ...] shape
    gathered = r"bf16\[48,(?:257|832|4112|13312),(?:16,)?8,128\]"
    assert not re.findall(gathered, text)
    # the temporaries are a step's activations, not a copy of a pool
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


def test_cohere2_moe_decode_lays_no_weight_out(cohere2_decode):
    """Inside the step loop's body nothing re-lays out a projection's weight:
    no ``reshape``, ``copy`` or ``transpose`` whose result has the extent of
    ``wq`` (128 heads x 128 lanes x 4096) or of ``wk`` / ``wv`` (8 heads).
    Until PR 51 ``rope_pairs`` took the even and the odd lanes of ``q`` and
    ``k`` apart, the compiler moved that strided slice through the product
    onto its weight, and the body held ``reshape bf16[128,64,2,4096]`` of
    ``wq`` (134 MB a window layer a step) and ``bf16[8,64,2,4096]`` of
    ``wk``. What the program does once — ``wq`` turned column-major in
    ``main``, a layer a program — is outside the loop and stays."""
    cfg, _, compiled = cohere2_decode
    extents = {heads * cfg.head_dim * cfg.d_model
               for heads in (cfg.n_heads, cfg.n_kv_heads)}

    def lays_out(rows):
        out = []
        for _, key, opcode, *_ in rows:
            if opcode not in ("reshape", "copy", "transpose"):
                continue
            dims = re.fullmatch(r"bf16\[([\d,]+)\]", key.split("|")[1])
            if dims and math.prod(map(int, dims[1].split(","))) in extents:
                out.append(f"{opcode} {key}")
        return out

    main, body = _main_and_body(compiled.as_text())
    # the walk sees a weight's layout change where there is one
    assert any("copy" in found and "[4096,16384]" in found
               for found in lays_out(main))
    assert len(body) > 300
    assert lays_out(body) == []


def test_cohere2_moe_prefill_batch_compiles(one_chip, monkeypatch):
    """The longest prompt of the cell as one program: blocked attention a
    layer (no [T, T] scores: 128 heads x 12,288 squared would be 77 GB),
    the expert layer a chunk of tokens at a time."""
    from ray_tpu.llm.cohere2_moe import cohere2_moe_prefill_batch

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cohere2_moe_prefill_batch.clear_cache()
    cfg, params, cache, key = _cohere2_args(
        one_chip, ("sliding_attention", "full_attention"))
    N, Tp = 1, 12288
    pages = (one_chip(_shape((N, Tp // 16), jnp.int32)),
             one_chip(_shape((N, 257), jnp.int32)))
    try:
        compiled = cohere2_moe_prefill_batch.lower(
            params, None, one_chip(_shape((N,), jnp.int32)),
            one_chip(_shape((N, Tp), jnp.int32)), pages, *cache,
            one_chip(_shape((N,), jnp.int32)),
            one_chip(_shape((N,), jnp.float32)), key, cfg=cfg).compile()
    finally:
        cohere2_moe_prefill_batch.clear_cache()
    text = compiled.as_text()
    assert len(re.findall(r"%gqa_prefill_attention\S* = \S+ custom-call\(",
                          text)) == 2
    assert not re.findall(r"\[(?:\d+,)*12288,12288\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2.6e9
    # 2,048 tokens x 8 choices a chunk: ragged_dot's three a layer, mapped
    # over the chunks, and no decode kernel
    assert len(re.findall(_RAGGED_DOT, text)) == 3 * cfg.n_layers
    assert not re.findall(_SWIGLU, text)


# --------------------------------------- learned sparse attention over experts
def _sparse_moe_args(one_chip, n_layers: int = 2):
    """Keye-VL-2.0-30B-A3B's language model at its published widths (32 query
    heads on 4 KV heads, a 16 x 64 indexer, 16 held experts of 768), two
    layers, the cell's three pools cut to 2,049 pages."""
    from ray_tpu.llm.sparse_moe import make_pools
    from ray_tpu.models.sparse_moe import SparseMoeConfig, sparse_moe_init

    cfg = SparseMoeConfig(vocab_size=18992, n_layers=n_layers,
                          max_seq_len=16384, experts_held=(0, 16),
                          vocab_held=(0, 18992))
    params = one_chip(jax.eval_shape(
        lambda: sparse_moe_init(jax.random.PRNGKey(0), cfg)))
    cache = one_chip(jax.eval_shape(lambda: make_pools(cfg, 16, 2049, None)))
    assert cache[2].shape == (n_layers, 2049, 8, 128)   # two keys a row
    return cfg, params, cache, one_chip(_shape((2,), jnp.uint32))


def test_sparse_moe_decode_multi_compiles(one_chip, monkeypatch):
    """A layer scores its slots' indexer keys in the packed pool, selects in
    XLA and attends K and V in place under the picks: two Mosaic calls of the
    attention's a layer and the grouped SwiGLU's one, 8 query heads a KV
    head, a table of 1,024 pages."""
    from ray_tpu.llm.programs import MOE_STATS
    from ray_tpu.llm.sparse_moe import SPARSE_STATS, sparse_moe_decode_multi

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sparse_moe_decode_multi.clear_cache()
    cfg, params, cache, key = _sparse_moe_args(one_chip)
    B = 32
    i32 = one_chip(_shape((B,), jnp.int32))
    try:
        lowered = sparse_moe_decode_multi.lower(
            params, None, i32, i32, i32, one_chip(_shape((B, 1024), jnp.int32)),
            *cache, one_chip(_shape((B,), jnp.bool_)),
            one_chip(_shape((B,), jnp.float32)), key, cfg=cfg, n_steps=8)
        compiled = lowered.compile()
    finally:
        sparse_moe_decode_multi.clear_cache()
    assert lowered.out_info[0].shape == (
        8, B + len(MOE_STATS) + len(SPARSE_STATS))
    text = compiled.as_text()
    assert len(re.findall(r"%_paged_selected_attention\S* = \S+ custom-call\(",
                          text)) == cfg.n_layers
    assert len(re.findall(r"%paged_index_scores\S* = \S+ custom-call\(",
                          text)) == cfg.n_layers
    # the selection between them is one kernel a layer too: no 32-pass loop
    # of XLA operations (the scan over the steps is the program's one while)
    assert len(re.findall(r"%topk_prefix_mask\S* = \S+ custom-call\(",
                          text)) == cfg.n_layers
    assert len(re.findall(r" while\(", text)) == 1
    # 256 rows over 16 held experts: one grouped SwiGLU kernel a layer
    assert len(re.findall(_SWIGLU, text)) == cfg.n_layers
    assert not re.findall(_RAGGED_DOT, text)
    # no table gathered out of a pool: neither K/V rows nor indexer keys
    assert not re.findall(r"bf16\[32,(?:1024|16384),(?:16,)?4,128\]", text)
    assert not re.findall(r"bf16\[32,(?:1024,8,128|16384,64)\]", text)
    # the temporaries are a step's activations and its [32, 16384] scores,
    # not a copy of a pool
    assert compiled.memory_analysis().temp_size_in_bytes < 0.3e9


def test_sparse_moe_prefill_batch_compiles(one_chip, monkeypatch):
    """The longest prompt of the cell as one program: the picks of a layer
    are ONE kernel that scores and selects a tile of 32 queries against the
    keys it can see, one byte a (query, key) pair, and the picked kernel
    attends them — no float [T, T] array (14,336 squared in float32 would be
    822 MB a layer, the indexer's 16 heads of it 13 GB), no block of float
    scores and no loop of XLA operations over blocks of queries either."""
    from ray_tpu.llm.sparse_moe import sparse_moe_prefill_batch

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sparse_moe_prefill_batch.clear_cache()
    cfg, params, cache, key = _sparse_moe_args(one_chip)
    N, Tp = 1, 14336
    try:
        compiled = sparse_moe_prefill_batch.lower(
            params, None, one_chip(_shape((N,), jnp.int32)),
            one_chip(_shape((N, Tp), jnp.int32)),
            one_chip(_shape((N, Tp // 16), jnp.int32)), *cache,
            one_chip(_shape((N,), jnp.int32)),
            one_chip(_shape((N,), jnp.float32)), key, cfg=cfg).compile()
    finally:
        sparse_moe_prefill_batch.clear_cache()
    text = compiled.as_text()
    assert len(re.findall(r"%gqa_picked_attention\S* = \S+ custom-call\(",
                          text)) == cfg.n_layers
    assert not re.findall(r"(?:f32|bf16)\[(?:\d+,)*14336,14336\]", text)
    assert re.findall(r"s8\[(?:1,)?14336,14336\]", text)   # the picks: a byte
    assert len(re.findall(r"%prefill_picks\S* = \S+ custom-call\(",
                          text)) == cfg.n_layers
    assert not re.findall(r"%topk_prefix_mask", text)
    assert not re.findall(r"f32\[(?:\d+,)*512,(?:16,)?14336\]", text)
    # the experts' chunks are the one loop a layer left
    assert len(re.findall(r" while\(", text)) == cfg.n_layers
    # 0.93 GB, of which two layers' bytes are 0.41: the scores' 16-head
    # float32 block and the stack of blocks of bytes took it to 3.07
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2e9
    assert len(re.findall(_RAGGED_DOT, text)) == 3 * cfg.n_layers
    assert not re.findall(_SWIGLU, text)


# ------------------------------ state-space blocks beside attention and experts
_POOL_COPY = (r"= (?:f32\[8,129,64,64,128\]|"
              r"bf16\[2,20000,16,2,128\])\S* copy\(")
_CONV_POOL = "bf16[8,129,18432]"
_CONV_POOL_COPY = rf"= {re.escape(_CONV_POOL)}\S* copy\("


def _ssm_moe_args(one_chip, pattern="MEMEM*EMEMEM*EMEME", kv=20000, state=129):
    """NVIDIA-Nemotron-3-Nano-30B-A3B at its published widths (64 x 64
    Mamba-2 heads on 8 groups of state 128, 32 query heads on 2 KV heads, 16
    held two-matrix experts of 1856); by default the cell's 18 blocks, 129
    state rows and 20,000 K/V pages."""
    from ray_tpu.llm.ssm_moe import make_pools
    from ray_tpu.models.ssm_moe import MAMBA, SsmMoeConfig, ssm_moe_init

    cfg = SsmMoeConfig(vocab_size=16384, pattern=pattern, max_seq_len=4096,
                       experts_held=(0, 16), vocab_held=(0, 16384))
    n_m = len(cfg.blocks_of(MAMBA))
    params = one_chip(jax.eval_shape(
        lambda: ssm_moe_init(jax.random.PRNGKey(0), cfg)))
    cache = one_chip(jax.eval_shape(
        lambda: make_pools(cfg, 16, {"kv": kv, "state": state}, None)))
    assert cache[2].shape == (n_m, state, 64, 64, 128)
    assert cache[2].dtype == jnp.float32
    assert cache[3].shape == (n_m, state, 3 * 6144)   # the conv rows lie flat
    return cfg, params, cache, one_chip(_shape((2,), jnp.uint32))


def _mosaic_modules(lowered):
    """The Mosaic module of every Pallas call of a lowered program, as text:
    the custom call's payload is the kernel's MLIR in bytecode."""
    import base64

    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jaxlib.mlir import ir

    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True  # serialised as `stable_mosaic`
    bodies = re.findall(r'\\22body\\22: *\\22([^\\]*)\\22',
                        lowered.as_text())
    return [str(ir.Module.parse(base64.b64decode(b), ctx)) for b in bodies]


def _ssm_moe_decode(one_chip, monkeypatch, args, B, n_steps):
    """(lowered, compiled) decode program of ``args`` for ``B`` slots, as a
    TPU's backend would choose its forms."""
    from ray_tpu.llm.ssm_moe import ssm_moe_decode_multi

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ssm_moe_decode_multi.clear_cache()
    cfg, params, cache, key = args
    i32 = one_chip(_shape((B,), jnp.int32))
    tables = (one_chip(_shape((B, 256), jnp.int32)),
              one_chip(_shape((B, 1), jnp.int32)))
    try:
        lowered = ssm_moe_decode_multi.lower(
            params, None, i32, i32, i32, tables, *cache,
            one_chip(_shape((B,), jnp.bool_)),
            one_chip(_shape((B,), jnp.float32)), key, cfg=cfg,
            n_steps=n_steps)
        return lowered, lowered.compile()
    finally:
        ssm_moe_decode_multi.clear_cache()


@pytest.fixture(scope="module")
def ssm_moe_decode(one_chip):
    """(lowered, compiled) decode program of the cell — 128 slots, 129 state
    rows, 8 steps, published widths: compiled once for the two tests that
    read it."""
    with pytest.MonkeyPatch.context() as patch:
        return _ssm_moe_decode(one_chip, patch, _ssm_moe_args(one_chip), 128, 8)


def test_ssm_moe_decode_multi_compiles(ssm_moe_decode):
    """128 slots a step: the two attention blocks read their pages where
    they lie (2 KV heads, 16 query heads each), every expert block applies
    its 16 held two-matrix experts to all 128 tokens in one batched product
    (no sort, no ``ragged_dot``, no streamed kernel), and each Mamba-2
    block's state pool is updated where it lies — one ``ssm_pool_step`` call
    a block (the test below), no gathered ``[128, 64, 64, 128]`` rows, no
    loop over slots — with no copy of a whole state or K/V pool on entry or
    exit. The conv pool alone is copied, once on entry and once on exit: the
    device keeps ``[8, 129, 18432]`` with the 8 blocks in the sublanes (129
    rows would pad a tile), the step updates one block's slab in place with
    the rows there, and the compiler turns the pool once a PROGRAM, outside
    the loop over the steps. What matters is that no step pays for it."""
    from ray_tpu.llm.programs import MOE_STATS

    B = 128
    lowered, compiled = ssm_moe_decode
    # tokens | MOE_STATS | ssm_updates, walk_blocks, walk_run_blocks
    assert lowered.out_info[0].shape == (8, B + len(MOE_STATS) + 3)
    text = compiled.as_text()
    assert len(re.findall(r"%_paged_decode_attention\S* = \S+ custom-call\(",
                          text)) == 2
    # the walk's Mosaic module holds the one-copy path at a 1,024-token
    # block: two blocks of 1,024 x 2 KV heads rows a pool with no page axis,
    # and DMA starts (2 pools x the 2 places a block's copies start) of a
    # whole block, of its 8 sub-runs of 8 pages, and of its 64 pages
    # (the two blocks' calls are sites of ONE lowered kernel)
    walk, = [m for m in _mosaic_modules(lowered) if "enqueue_dma" in m]
    assert re.findall(r"memref<2x2048x128xbf16, [^>]*vmem>", walk)
    assert not re.findall(r"memref<2x\d+x16x2x128xbf16, [^>]*vmem>", walk)
    starts = re.findall(r"enqueue_dma.*?memref<(\d+)x128xbf16", walk)
    assert {n: starts.count(n) for n in set(starts)} == {
        "2048": 4, "256": 4 * 8, "32": 4 * 64}
    assert not re.findall(_RAGGED_DOT, text)
    assert not re.findall(_SWIGLU, text)
    assert not re.findall(_POOL_COPY, text)
    assert not [key for _, key, opcode, *_ in _main_and_body(text)[1]
                if opcode == "copy" and key.endswith("|" + _CONV_POOL)]
    assert len(re.findall(_CONV_POOL_COPY, text)) <= 2
    # no table of K/V rows gathered out of a pool, no state rows gathered
    # by slot, and the scan over the steps is the program's only loop
    assert not re.findall(r"bf16\[128,(?:256|4096),(?:16,)?2,128\]", text)
    assert not re.findall(r"f32\[128,64,64,128\]", text)
    assert len(re.findall(r" while\(", text)) == 1
    # 75 MB: the step's activations; no state row leaves its pool
    assert compiled.memory_analysis().temp_size_in_bytes < 0.3e9


def test_ssm_moe_decode_shifts_the_conv_rows_where_they_lie(ssm_moe_decode):
    """The cell's decode program: inside the loop over the steps what writes
    the conv pool is, a Mamba-2 block, ONE update in place of that block's
    ``[1, 129, 18432]`` slab (a fusion rooted in a ``dynamic-update-slice``,
    part ``conv``), and no ``scatter`` takes the pool anywhere. Until PR 60
    the step scattered the slots' 128 rows into the pool, which the device
    keeps with the BLOCK axis in the sublanes: eight ``conv/scatter``
    fusions a step, each with the whole pool of all blocks as its result,
    76 MB moved to change 4.7. What else hands the pool on there is the
    compiler's own staging through fast memory, which has no ``op_name``."""
    text = ssm_moe_decode[1].as_text()
    pool = re.escape(_CONV_POOL)
    assert not re.findall(rf"= {pool}\S* scatter\(", text)
    roots = dict(re.findall(
        r"^%?([\w.\-]+) \(.*\{\n(?:  (?!ROOT ).*\n)*  ROOT (.*)$", text, re.M))
    writes = []
    for name, key, opcode, op, _ in _main_and_body(text)[1]:
        if not key.endswith("|" + _CONV_POOL) or opcode in (
                "parameter", "get-tuple-element", "bitcast"):
            continue
        if op is None:
            assert opcode in ("copy-start", "copy-done", "slice-start",
                              "custom-call"), (name, opcode)
            continue
        assert opcode == "fusion" and tracing.op_part(op) == "conv", (name, op)
        line, = re.findall(rf"^\s*%{re.escape(name)} = .*$", text, re.M)
        root = roots[re.search(r"calls=%?([\w.\-]+)", line)[1]]
        slab, = re.findall(
            rf"= {pool}\S* dynamic-update-slice\(%\S+, %([\w.\-]+),", root)
        assert re.search(
            rf"%{re.escape(slab)} = bf16\[1,129,18432\]\S* ", text), root
        writes.append(name)
    assert len(writes) == 8, writes


def test_ssm_moe_decode_moves_the_state_in_one_pass(one_chip, monkeypatch):
    """The decode program at a reduced pool (33 rows, 32 slots, one period
    of the pattern): inside the part ``ssm`` ONE operation a Mamba-2 block
    takes the state pool — the ``ssm_pool_step`` kernel, which hands the
    pool back in the buffer it came in — with no plain in-place update or
    read-out fusion beside it and no copy of the pool around the calls: an
    alias that did not hold would show as one."""

    args = _ssm_moe_args(one_chip, pattern="MEMEM*EME", kv=2000, state=33)
    text = _ssm_moe_decode(one_chip, monkeypatch, args, 32, 4)[1].as_text()
    rows = [r for c in tracing.program_instructions(text)[1] for r in c]
    shape = {name: key.split("|")[1] for name, key, *_ in rows}
    pool = "f32[%d,%d,%d,%d,%d]" % args[2][2].shape
    # whatever reads or writes a pool: a kernel call, a fusion, a copy
    moves = [(name, opcode, op) for name, _, opcode, op, operands in rows
             if opcode not in ("get-tuple-element", "tuple", "while", "bitcast")
             and any(shape.get(o) == pool for o in operands)]
    assert len(moves) == args[2][2].shape[0], moves
    for name, opcode, op in moves:
        assert opcode == "custom-call" and name.startswith("ssm_pool_step")
        assert tracing.op_part(op) == "ssm", op
        call, = re.findall(rf"%{re.escape(name)} = .*", text)
        assert "output_to_operand_aliasing={{0}: (2, {})}" in call


def test_ssm_moe_prefill_batch_compiles(one_chip, monkeypatch):
    """The cell's largest wave, 8 prompts of 2,048: sixteen chunks of 128 a
    Mamba-2 block, the blocked attention kernel in the two attention blocks,
    two ``ragged_dot`` calls an expert block, one state row a prompt a
    Mamba-2 block written in place."""
    from ray_tpu.llm.ssm_moe import ssm_moe_prefill_batch

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ssm_moe_prefill_batch.clear_cache()
    cfg, params, cache, key = _ssm_moe_args(one_chip)
    N, Tp = 8, 2048
    pages = (one_chip(_shape((N, Tp // 16), jnp.int32)),
             one_chip(_shape((N, 1), jnp.int32)))
    try:
        compiled = ssm_moe_prefill_batch.lower(
            params, None, one_chip(_shape((N,), jnp.int32)),
            one_chip(_shape((N, Tp), jnp.int32)), pages, *cache,
            one_chip(_shape((N,), jnp.int32)),
            one_chip(_shape((N,), jnp.float32)), key, cfg=cfg).compile()
    finally:
        ssm_moe_prefill_batch.clear_cache()
    text = compiled.as_text()
    assert len(re.findall(r"%gqa_prefill_attention\S* = \S+ custom-call\(",
                          text)) == 2
    assert len(re.findall(_RAGGED_DOT, text)) == 2 * 8
    assert not re.findall(_SWIGLU, text)
    assert not re.findall(_POOL_COPY, text)
    assert not re.findall(_CONV_POOL_COPY, text)
    # the in-projection of 16,384 tokens, the chunks' decay blocks and
    # states in float32: 2.94 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 3.4e9


# ------------------------------ exact window rows beside pooled pairs, 32 KV heads
_EVA_POOL_COPY = r"= bf16\[8,(?:3073|1300),16,32,128\]\S* copy\("


def _eva_args(one_chip):
    """EvaByte at its published widths (32 heads of 128 on as many KV heads,
    SwiGLU 11008, 320 byte ids, 8 next-byte heads), the cell's 8 layers, 24
    slots, 3,073 window pages and 1,300 pages of pairs of one page shape."""
    from ray_tpu.llm.eva import make_pools, page_kinds
    from ray_tpu.models.eva import EvaConfig, eva_init

    cfg = EvaConfig(n_layers=8)
    params = one_chip(jax.eval_shape(lambda: eva_init(jax.random.PRNGKey(0), cfg)))
    cache = one_chip(jax.eval_shape(
        lambda: make_pools(cfg, 16, {"window": 3073, "summary": 1300}, None)))
    assert {c.shape for c in cache} == {(8, 3073, 16, 32, 128),
                                        (8, 1300, 16, 32, 128)}
    kinds = page_kinds(cfg, 16, 32768)
    assert [(k.table, k.stride, k.aligned) for k in kinds] == [
        (128, 1, True), (128, 16, True)]
    return cfg, params, cache, kinds, one_chip(_shape((2,), jnp.uint32))


def test_eva_decode_multi_compiles(one_chip, monkeypatch):
    """24 slots a step: every layer walks the slot's ring of window pages and
    its pages of pairs where they lie — two calls of the paged walk a layer,
    each given out with its maximum and sum, at G = 1 on 32 KV heads — with
    no table of rows gathered out of a pool and no copy of a whole pool on
    entry or exit; the scan over the steps is the program's only loop."""
    from ray_tpu.llm.eva import eva_decode_multi

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eva_decode_multi.clear_cache()
    cfg, params, cache, kinds, key = _eva_args(one_chip)
    B = 24
    i32 = one_chip(_shape((B,), jnp.int32))
    tables = tuple(one_chip(_shape((B, k.table), jnp.int32)) for k in kinds)
    try:
        lowered = eva_decode_multi.lower(
            params, None, i32, i32, i32, tables, *cache,
            one_chip(_shape((B,), jnp.bool_)),
            one_chip(_shape((B,), jnp.float32)), key, cfg=cfg, n_steps=8)
        compiled = lowered.compile()
    finally:
        eva_decode_multi.clear_cache()
    assert lowered.out_info[0].shape == (8, B + 1)   # tokens | eva_pairs
    text = compiled.as_text()
    for walk in ("paged_window_part", "paged_attention_part"):  # (o, m, l)
        assert len(re.findall(rf"%{walk}\S* = \(f32\[24,32,128\]", text)) == 8
    assert not re.findall(_EVA_POOL_COPY, text)
    assert not re.findall(r"bf16\[24,2048,32,128\]", text)   # a gathered ring
    assert len(re.findall(r" while\(", text)) == 1
    # 0.83 GB: wq, wk, wv of every layer laid out once a program for 24 rows
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


def test_eva_prefill_batch_compiles(one_chip, monkeypatch):
    """The cell's longest prompt, 1 x 15,360: the blocked kernel over exact
    rows and pairs in every layer, no ``[T, T]`` array, the rows and pairs
    written in place a layer at a time (every layer's scatter put off to the
    program's end kept 2 GB of keys and values alive: 3.66 GB of temporaries
    where 1.32)."""
    from ray_tpu.llm.eva import eva_prefill_batch

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eva_prefill_batch.clear_cache()
    cfg, params, cache, kinds, key = _eva_args(one_chip)
    N, Tp = 1, 15360
    pages = tuple(one_chip(_shape(
        (N, min(-(-Tp // (16 * k.stride)), k.table)), jnp.int32)) for k in kinds)
    assert [p.shape for p in pages] == [(1, 128), (1, 60)]
    try:
        compiled = eva_prefill_batch.lower(
            params, None, one_chip(_shape((N,), jnp.int32)),
            one_chip(_shape((N, Tp), jnp.int32)), pages, *cache,
            one_chip(_shape((N,), jnp.int32)),
            one_chip(_shape((N,), jnp.float32)), key, cfg=cfg).compile()
    finally:
        eva_prefill_batch.clear_cache()
    text = compiled.as_text()
    assert len(re.findall(r"%eva_prefill_attention\S* = \S+ custom-call\(",
                          text)) == 8
    assert not re.findall(_EVA_POOL_COPY, text)
    assert not re.findall(r"f32\[(?:1,)?(?:32,)?15360,15360\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.6e9


# ------------------- delta-rule state rows beside a latent pool, group-routed experts
_KDA_STATE_COPY = r"= f32\[10,97,32,128,128\]\S* (?:copy|transpose)\("


def _kda_moe_args(one_chip):
    """Ling-3.0-flash's language model at its published widths (32 KDA heads
    of 128 x 128 state, 576-wide latent rows, 64 held experts of 768 with a
    512-wide router in 8 groups), the cell's 12 layers, 97 state rows and
    24,600 latent pages."""
    from ray_tpu.llm.kda_moe import make_pools
    from ray_tpu.models.kda_moe import KdaMoeConfig, kda_moe_init

    cfg = KdaMoeConfig(vocab_size=19648, n_layers=12, max_seq_len=6144,
                       experts_held=(0, 64), vocab_held=(0, 19648))
    params = one_chip(jax.eval_shape(
        lambda: kda_moe_init(jax.random.PRNGKey(0), cfg)))
    cache = one_chip(jax.eval_shape(
        lambda: make_pools(cfg, 16, {"latent": 24600, "state": 97}, None)))
    assert [c.shape for c in cache] == [
        (2, 24600, 16, 576), (10, 97, 32, 128, 128), (10, 97, 3 * 12288)]
    assert cache[1].dtype == jnp.float32   # 2,097,152 B a row a layer
    return cfg, params, cache, one_chip(_shape((2,), jnp.uint32))


def test_kda_moe_decode_multi_compiles(one_chip, monkeypatch):
    """96 slots a step: each of the 10 KDA layers advances its state pool
    where it lies — ONE ``kda_pool_step`` call a layer, which hands the pool
    back in the buffer it came in — the two MLA layers attend the latent pool
    in place, every expert layer streams its touched held experts through the
    grouped SwiGLU kernel (768 rows handed: ``parallel/moe.py``
    ``_streams_experts``), and no whole state pool (2.1 GB) is copied on
    entry or exit. The latent pool's two copies are the device layout's
    (``test_mla_moe_decode_multi_compiles`` says why)."""
    from ray_tpu.llm.kda_moe import STATS, kda_moe_decode_multi

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kda_moe_decode_multi.clear_cache()
    cfg, params, cache, key = _kda_moe_args(one_chip)
    B = 96
    i32 = one_chip(_shape((B,), jnp.int32))
    tables = (one_chip(_shape((B, 384), jnp.int32)),
              one_chip(_shape((B, 1), jnp.int32)))
    try:
        lowered = kda_moe_decode_multi.lower(
            params, None, i32, i32, i32, tables, *cache,
            one_chip(_shape((B,), jnp.bool_)),
            one_chip(_shape((B,), jnp.float32)), key, cfg=cfg, n_steps=8)
        compiled = lowered.compile()
    finally:
        kda_moe_decode_multi.clear_cache()
    assert lowered.out_info[0].shape == (8, B + len(STATS))
    mem = compiled.memory_analysis()
    # weights 9.27 GB, state rows 2.11, latent pool 0.91
    assert 12.2e9 < mem.argument_size_in_bytes < 12.4e9
    # 0.91 GB of it is the latent pool in the kernel's layout
    assert mem.temp_size_in_bytes < 1.4e9
    text = compiled.as_text()
    steps = re.findall(r"%kda_pool_step\S* = .* custom-call\(.*", text)
    assert len(steps) == 10
    assert all("output_to_operand_aliasing={{0}: (1, {})}" in s for s in steps)
    assert len(re.findall(r"%_paged_latent_attention\S* = \S+ custom-call\(",
                          text)) == 2
    assert len(re.findall(_SWIGLU, text)) == cfg.n_moe_layers == 10
    assert not re.findall(_RAGGED_DOT, text)
    assert not re.findall(_KDA_STATE_COPY, text)
    # no state rows gathered by slot, and the scan over the steps is the
    # program's only loop
    assert not re.findall(r"f32\[96,32,128,128\]", text)
    assert len(re.findall(r" while\(", text)) == 1


def test_kda_moe_prefill_batch_compiles(one_chip, monkeypatch):
    """The cell's largest wave by tokens, 4 prompts of 4,096: the chunked
    delta rule as ONE ``kda_chunk_scan`` call a KDA layer (``ops/
    kda_chunk.py``: no float32 copy of the keys a row sub-block, nothing of
    ``ops/kda.py``'s scan), the expanded MLA a group of heads at a time,
    three ``ragged_dot`` calls an expert layer, one state row a prompt a KDA
    layer written in place — and the whole beside 12.3 GB of arguments inside
    the chip's 16.9."""
    from ray_tpu.llm.kda_moe import kda_moe_prefill_batch

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kda_moe_prefill_batch.clear_cache()
    cfg, params, cache, key = _kda_moe_args(one_chip)
    N, Tp = 4, 4096
    pages = (one_chip(_shape((N, Tp // 16), jnp.int32)),
             one_chip(_shape((N, 1), jnp.int32)))
    try:
        compiled = kda_moe_prefill_batch.lower(
            params, None, one_chip(_shape((N,), jnp.int32)),
            one_chip(_shape((N, Tp), jnp.int32)), pages, *cache,
            one_chip(_shape((N,), jnp.int32)),
            one_chip(_shape((N,), jnp.float32)), key, cfg=cfg).compile()
    finally:
        kda_moe_prefill_batch.clear_cache()
    text = compiled.as_text()
    # 16,384 tokens are eight chunks of the expert layer's 2,048
    assert len(re.findall(_RAGGED_DOT, text)) == 3 * cfg.n_moe_layers
    assert not re.findall(_SWIGLU, text)
    assert not re.findall(_KDA_STATE_COPY, text)
    scans = re.findall(r"%kda_chunk_scan\S* = .* custom-call\(", text)
    assert len(scans) == len(cfg.layers_of("kda")) == 10
    # the plain form's keys a row sub-block, [N, c, H, nb, C, dk] float32, of
    # a group of prompts or of all four
    assert not re.findall(r"f32\[\d+,64,32,4,64,128\]", text)
    mem = compiled.memory_analysis()
    # every layer's conv input kept to the program's end was 5.29 GB
    # (models/kda_moe.py kda_mixer's barrier), the plain form's scan 3.31;
    # 3.21 now, and the bound 10 % over it
    assert mem.temp_size_in_bytes < 3.54e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.2e9


# ------- a K/V page AND a row a slot in every layer, one wide expert a token
_CCA_POOL_COPY = (r"= bf16\[(?:20,9400,16,2,128|20,81,2688)\]\S* "
                  r"(?:copy|transpose)\(")
# the tied table, 1.07 GB: a copy of it, transposed or not, or one in float32
_CCA_TABLE_COPY = (r"= (?:bf16|f32)\[(?:262272,2048|2048,262272)\]\S* "
                   r"(?:copy|transpose)\(")


def _cca_moe_args(one_chip):
    """ZAYA1-8B at its published widths (8 query heads on 2 key heads of 128
    inside a 2,048-wide model, a router of 256, all 16 experts of 2048 x
    2048, the whole tied table of 262,272 rows), the cell's 20 layers, 9,400
    K/V pages and 81 rows."""
    from ray_tpu.llm.cca_moe import make_pools
    from ray_tpu.models.cca_moe import CcaMoeConfig, cca_moe_init

    cfg = CcaMoeConfig(n_layers=20, max_seq_len=3072)
    params = one_chip(jax.eval_shape(
        lambda: cca_moe_init(jax.random.PRNGKey(0), cfg)))
    cache = one_chip(jax.eval_shape(
        lambda: make_pools(cfg, 16, {"kv": 9400, "row": 81}, None)))
    assert [c.shape for c in cache] == [
        (20, 9400, 16, 2, 128), (20, 9400, 16, 2, 128), (20, 81, 2688)]
    assert all(c.dtype == jnp.bfloat16 for c in cache)   # 5,376 B a row
    return cfg, params, cache, one_chip(_shape((2,), jnp.uint32))


def test_cca_moe_decode_multi_compiles(one_chip, monkeypatch):
    """80 slots a step: every layer advances its rows where they lie and
    attends its K/V pages in place, 80 rows stream their experts through the
    grouped SwiGLU kernel (``parallel/moe.py`` ``_streams_experts`` at one
    expert a token), the head reads the tied table as it lies — no whole
    pool and no table is copied, transposed or not — and the whole is the
    arguments and little more."""
    from ray_tpu.llm.cca_moe import STATS, cca_moe_decode_multi

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cca_moe_decode_multi.clear_cache()
    cfg, params, cache, key = _cca_moe_args(one_chip)
    B = 80
    i32 = one_chip(_shape((B,), jnp.int32))
    tables = (one_chip(_shape((B, 192), jnp.int32)),
              one_chip(_shape((B, 1), jnp.int32)))
    try:
        lowered = cca_moe_decode_multi.lower(
            params, None, i32, i32, i32, tables, *cache,
            one_chip(_shape((B,), jnp.bool_)),
            one_chip(_shape((B,), jnp.float32)), key, cfg=cfg, n_steps=8)
        compiled = lowered.compile()
    finally:
        cca_moe_decode_multi.clear_cache()
    assert lowered.out_info[0].shape == (8, B + len(STATS))
    mem = compiled.memory_analysis()
    # weights 9.38 GB, K and V pools 3.08, rows 0.009
    assert 12.4e9 < mem.argument_size_in_bytes < 12.55e9
    assert mem.temp_size_in_bytes < 0.2e9
    text = compiled.as_text()
    assert len(re.findall(r"%_paged_decode_attention\S* = \S+ custom-call\(",
                          text)) == cfg.n_layers == 20
    assert len(re.findall(_SWIGLU, text)) == 20
    assert not re.findall(_RAGGED_DOT, text)
    assert not re.findall(_CCA_POOL_COPY, text)
    assert not re.findall(_CCA_TABLE_COPY, text)
    # the scan over the steps is the program's only loop: no row scattered
    # slot by slot
    assert len(re.findall(r" while\(", text)) == 1


def test_cca_moe_prefill_batch_compiles(one_chip, monkeypatch):
    """The cell's largest wave, 8 prompts of 1,024: blocked attention over
    the fresh keys (``gqa_prefill_attention`` at 2 key heads, G = 4), 8,192
    rows through three ``ragged_dot`` calls a layer with no chunking, the
    rows and pages written in place — and the whole beside 12.5 GB of
    arguments inside the chip's 16.9."""
    from ray_tpu.llm.cca_moe import cca_moe_prefill_batch

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cca_moe_prefill_batch.clear_cache()
    cfg, params, cache, key = _cca_moe_args(one_chip)
    N, Tp = 8, 1024
    pages = (one_chip(_shape((N, Tp // 16), jnp.int32)),
             one_chip(_shape((N, 1), jnp.int32)))
    try:
        compiled = cca_moe_prefill_batch.lower(
            params, None, one_chip(_shape((N,), jnp.int32)),
            one_chip(_shape((N, Tp), jnp.int32)), pages, *cache,
            one_chip(_shape((N,), jnp.int32)),
            one_chip(_shape((N,), jnp.float32)), key, cfg=cfg).compile()
    finally:
        cca_moe_prefill_batch.clear_cache()
    text = compiled.as_text()
    assert len(re.findall(r"%gqa_prefill_attention\S* = \S+ custom-call\(",
                          text)) == 20
    assert len(re.findall(_RAGGED_DOT, text)) == 3 * 20
    assert not re.findall(_SWIGLU, text)
    assert not re.findall(r"f32\[8,(?:8,)?1024,1024\]", text)   # no scores written
    assert not re.findall(_CCA_POOL_COPY, text)
    assert not re.findall(_CCA_TABLE_COPY, text)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 0.6e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13.2e9


# ------------------------- window layers with a sink, keys wider than values
def _sink_moe_args(one_chip):
    """MiMo-V2-Flash at its published widths (64 query heads of 192 | 128
    lanes on 4 KV heads in a full layer and 8 in a window layer, 16 held
    experts of 2048), the dense layer, a window and a full expert layer, the
    cell's pools cut to 2,049 and 601 pages."""
    from ray_tpu.llm.sink_moe import make_pools
    from ray_tpu.models.sink_moe import SinkMoeConfig, sink_moe_init

    cfg = SinkMoeConfig(vocab_size=19072, n_layers=3,
                        layer_window=(False, True, False),
                        layer_moe=(False, True, True), max_seq_len=18432,
                        experts_held=(0, 16), vocab_held=(0, 19072))
    params = one_chip(jax.eval_shape(
        lambda: sink_moe_init(jax.random.PRNGKey(0), cfg)))
    cache = one_chip(jax.eval_shape(lambda: make_pools(
        cfg, 16, {"full": 2049, "window": 601}, None)))
    assert [c.shape[3:] for c in cache] == [(4, 256), (4, 128), (8, 256), (8, 128)]
    return cfg, params, cache, one_chip(_shape((2,), jnp.uint32))


def test_sink_moe_decode_multi_compiles(one_chip):
    """Each kind of layer attends its own pools in place — the full layers
    the plain walk over keys of 256 lanes beside values of 128, the window
    layer the ring's walk given out as a part (its sink joins outside the
    kernel) — nothing copies a pool, and inside the step loop nothing
    re-lays out ``wq`` (64 heads x 192 lanes x 4096: 100 MB a layer)."""
    from ray_tpu.llm.programs import MOE_STATS
    from ray_tpu.llm.sink_moe import sink_moe_decode_multi

    cfg, params, cache, key = _sink_moe_args(one_chip)
    B = 64
    i32 = one_chip(_shape((B,), jnp.int32))
    tables = (one_chip(_shape((B, 1152), jnp.int32)),
              one_chip(_shape((B, 9), jnp.int32)))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        sink_moe_decode_multi.clear_cache()
        try:
            lowered = sink_moe_decode_multi.lower(
                params, None, i32, i32, i32, tables, *cache,
                one_chip(_shape((B,), jnp.bool_)),
                one_chip(_shape((B,), jnp.float32)), key, cfg=cfg, n_steps=8)
            compiled = lowered.compile()
        finally:
            sink_moe_decode_multi.clear_cache()
    assert lowered.out_info[0].shape == (8, 64 + len(MOE_STATS))
    text = compiled.as_text()
    # three results (output, running maximum and sum): a tuple's type
    assert len(re.findall(r"%paged_window_part\S* = \([^=]*custom-call\(", text)) == 1
    assert len(re.findall(r"%_paged_decode_attention\S* = \S+ custom-call\(",
                          text)) == 2
    # 512 rows over 16 held experts: one grouped SwiGLU kernel an expert layer
    assert len(re.findall(_SWIGLU, text)) == 2
    assert not re.findall(_RAGGED_DOT, text)
    # no table gathered out of a pool, and the temporaries are a step's
    # activations and the weights turned once a program, not a copy of a pool
    assert not re.findall(r"bf16\[64,(?:9|1152|144|18432),(?:16,)?[48],(?:128|256)\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9
    extents = {heads * lanes * cfg.d_model for heads, lanes in (
        (64, 192), (64, 128), (4, 192), (8, 192), (4, 128), (8, 128))}
    computations = tracing.program_instructions(text)[1]
    main = [rows for rows in computations
            if any(opcode == "while" for _, _, opcode, *_ in rows)]
    assert len(main) == 1  # the scan over the steps is the only loop
    body = [row for rows in computations if rows is not main[0] for row in rows]
    assert len(body) > 300
    for _, key_, opcode, *_ in body:
        if opcode in ("reshape", "copy", "transpose"):
            dims = re.fullmatch(r"bf16\[([\d,]+)\]", key_.split("|")[1])
            assert not (dims and math.prod(map(int, dims[1].split(","))) in extents), key_


def test_sink_moe_prefill_batch_compiles(one_chip, monkeypatch):
    """Two 4,096-token prompts as one program: blocked attention a layer —
    the window layer's from its sink, heads of 192 padded to 256 lanes
    against values of 128 — and no [T, T] scores."""
    from ray_tpu.llm.sink_moe import sink_moe_prefill_batch

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sink_moe_prefill_batch.clear_cache()
    cfg, params, cache, key = _sink_moe_args(one_chip)
    N, Tp = 2, 4096
    pages = (one_chip(_shape((N, Tp // 16), jnp.int32)),
             one_chip(_shape((N, 9), jnp.int32)))
    try:
        compiled = sink_moe_prefill_batch.lower(
            params, None, one_chip(_shape((N,), jnp.int32)),
            one_chip(_shape((N, Tp), jnp.int32)), pages, *cache,
            one_chip(_shape((N,), jnp.int32)),
            one_chip(_shape((N,), jnp.float32)), key, cfg=cfg).compile()
    finally:
        sink_moe_prefill_batch.clear_cache()
    text = compiled.as_text()
    assert len(re.findall(r"%gqa_sink_prefill_attention\S* = \S+ custom-call\(",
                          text)) == 1
    assert len(re.findall(r"%gqa_prefill_attention\S* = \S+ custom-call\(",
                          text)) == 2
    assert not re.findall(r"\[2,(?:64|8,8|4,16),4096,4096\]", text)  # no scores
    assert compiled.memory_analysis().temp_size_in_bytes < 2.0e9
    assert len(re.findall(_RAGGED_DOT, text)) == 3 * 2


# ---- a stack run four times a token: a plane a pass a layer, the passes a loop
_LOOPED_POOL = r"bf16\[192,352,16,16,128\]"
_LOOPED_POOL_COPY = rf"= {_LOOPED_POOL}\S* (?:copy|transpose)\("
# the embedding or the head, 201 MB each: a copy, transposed or not
_LOOPED_TABLE_COPY = (r"= (?:bf16|f32)\[(?:49152,2048|2048,49152)\]\S* "
                      r"(?:copy|transpose)\(")


def _looped_args(one_chip):
    """Ouro-2.6B as the cell serves it: every published width and count (48
    layers, 4 passes, 16 heads on 16 of 128, the SwiGLU at 5,632, the whole
    vocabulary), 352 pages of 16 positions in 192 planes."""
    from ray_tpu.llm.looped import make_pools
    from ray_tpu.models.looped import LoopedConfig, looped_init

    cfg = LoopedConfig(max_seq_len=640)
    params = one_chip(jax.eval_shape(
        lambda: looped_init(jax.random.PRNGKey(0), cfg)))
    cache = one_chip(jax.eval_shape(lambda: make_pools(cfg, 16, 352, None)))
    assert [c.shape for c in cache] == [(192, 352, 16, 16, 128)] * 2
    assert all(c.dtype == jnp.bfloat16 for c in cache)   # 1.5 MiB a position
    return cfg, params, cache, one_chip(_shape((2,), jnp.uint32))


@pytest.fixture(scope="module")
def looped_decode(one_chip):
    """(cfg, lowered, compiled) decode program for 24 slots and 8 steps, as a
    TPU's backend would choose its forms: compiled once for the tests that
    read it."""
    from ray_tpu.llm.looped import looped_decode_multi

    was = jax.default_backend
    jax.default_backend = lambda: "tpu"
    looped_decode_multi.clear_cache()
    cfg, params, cache, key = _looped_args(one_chip)
    B = 24
    i32 = one_chip(_shape((B,), jnp.int32))
    try:
        lowered = looped_decode_multi.lower(
            params, None, i32, i32, i32, one_chip(_shape((B, 40), jnp.int32)),
            *cache, one_chip(_shape((B,), jnp.bool_)),
            one_chip(_shape((B,), jnp.float32)), key, cfg=cfg, n_steps=8)
        compiled = lowered.compile()
    finally:
        jax.default_backend = was
        looped_decode_multi.clear_cache()
    return cfg, lowered, compiled


def test_looped_decode_multi_compiles(looped_decode):
    """24 slots a step at the published widths: the four passes are ONE
    traced body — each layer's walk stands in the compiled text once, not
    four times —, the pools are updated in place through both loops (no copy
    of a pool on entry, on exit or between passes), neither table is copied,
    and the whole is the arguments and little more."""
    from ray_tpu.llm.looped import LOOPED_STATS

    cfg, lowered, compiled = looped_decode
    assert lowered.out_info[0].shape == (8, 24 + len(LOOPED_STATS))
    mem = compiled.memory_analysis()
    # weights 5.34 GB, K and V pools 8.86
    assert 14.1e9 < mem.argument_size_in_bytes < 14.3e9
    assert mem.temp_size_in_bytes < 0.2e9
    text = compiled.as_text()
    assert len(re.findall(r"%_paged_decode_attention\S* = \S+ custom-call\(",
                          text)) == cfg.n_layers == 48
    assert not re.findall(_LOOPED_POOL_COPY, text)
    assert not re.findall(_LOOPED_TABLE_COPY, text)
    # the steps' scan and, inside it, the passes' loop: no third
    assert len(re.findall(r" while\(", text)) == 2


def test_looped_decode_lays_no_weight_out(looped_decode):
    """The same 51 M-parameter layer is read four times a step: inside the
    loops nothing re-lays out one of its kernels — no ``reshape``, ``copy``
    or ``transpose`` whose result has the extent of ``wqkv``, ``wo``,
    ``w_gate_up`` or ``w_down`` — and no pool is converted or sliced whole."""
    cfg, _, compiled = looped_decode
    D, hd = cfg.d_model, cfg.head_dim
    extents = {D * (cfg.n_heads + 2 * cfg.n_kv_heads) * hd,
               cfg.n_heads * hd * D, D * 2 * cfg.d_ff, cfg.d_ff * D}

    def lays_out(rows):
        out = []
        for _, key, opcode, *_ in rows:
            if opcode not in ("reshape", "copy", "transpose"):
                continue
            dims = re.fullmatch(r"bf16\[([\d,]+)\]", key.split("|")[1])
            if dims and math.prod(map(int, dims[1].split(","))) in extents:
                out.append(f"{opcode} {key}")
        return out

    computations = tracing.program_instructions(compiled.as_text())[1]
    assert sum(opcode == "while" for rows in computations
               for _, _, opcode, *_ in rows) == 2
    everything = [row for rows in computations for row in rows]
    assert len(everything) > 1000
    assert lays_out(everything) == []


def test_looped_prefill_batch_compiles(one_chip, monkeypatch):
    """The cell's largest wave, 4 prompts of 384: blocked attention over the
    fresh keys (``gqa_prefill_attention`` at 16 KV heads, G = 1) once a layer
    in the passes' one body, the planes written in place, and the whole
    beside 14.2 GB of arguments inside the chip's 16.9."""
    from ray_tpu.llm.looped import looped_prefill_batch

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    looped_prefill_batch.clear_cache()
    cfg, params, cache, key = _looped_args(one_chip)
    N, Tp = 4, 384
    try:
        compiled = looped_prefill_batch.lower(
            params, None, one_chip(_shape((N,), jnp.int32)),
            one_chip(_shape((N, Tp), jnp.int32)),
            one_chip(_shape((N, Tp // 16), jnp.int32)), *cache,
            one_chip(_shape((N,), jnp.int32)),
            one_chip(_shape((N,), jnp.float32)), key, cfg=cfg).compile()
    finally:
        looped_prefill_batch.clear_cache()
    text = compiled.as_text()
    assert len(re.findall(r"%gqa_prefill_attention\S* = \S+ custom-call\(",
                          text)) == 48
    assert not re.findall(r"f32\[4,(?:16,)?384,384\]", text)   # no scores written
    assert not re.findall(_LOOPED_POOL_COPY, text)
    assert not re.findall(_LOOPED_TABLE_COPY, text)
    assert len(re.findall(r" while\(", text)) == 1   # the passes' loop
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9
