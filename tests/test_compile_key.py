"""What jax's persistent compile cache keys a program on, as ``configure_jax()``
leaves it (``ray_tpu/utils/device.py``): a program's operations, shapes and
``tracing.part`` scopes, and nothing of where its source lies.

A Pallas kernel reaches XLA as a ``tpu_custom_call`` whose ``backend_config``
carries the kernel's Mosaic module, and the key reads it byte for byte. With
jax's default of ten Python frames a location that module names the file, line
and columns of every frame that reached the lowering, so an edit above a
kernel, or the same tree in another directory, gives another key for the same
operations. These tests lower each kernel file's public entry for the TPU from
this CPU process (no chip, no compile) and hold the payload to carrying no
path, and to being byte-equal when the module is imported from a copy in
another directory with five more lines above everything in it.

Run alone: ``pytest tests/test_compile_key.py`` (a quarter of a minute). A new
kernel file gets a case in ``KERNELS``."""
import base64
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.utils import device, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_F32, _BF16, _I32 = jnp.float32, jnp.bfloat16, jnp.int32


def _s(shape, dtype=_F32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _ssm_pool(m):
    return (lambda *a: m.ssm_pool_step(*a, interpret=False)), (
        _s((2, 4, 8, 64, 128)), _s((), _I32), _s((4, 8, 64)), _s((4, 8)),
        _s((8,)), _s((4, 2, 128)), _s((4, 2, 128)), _s((8,)))


def _kda_pool(m):
    return (lambda *a: m.kda_pool_step(*a, interpret=False)), (
        _s((2, 4, 4, 128, 128)), _s((), _I32), _s((4, 4, 128)),
        _s((4, 4, 128)), _s((4, 4, 128)), _s((4, 4, 128)), _s((4, 4)))


def _paged_attention(m):
    pool = _s((2, 33, 16, 2, 128), _BF16)
    return (lambda *a: m.paged_decode_attention(*a, interpret=False)), (
        _s((4, 8, 128), _BF16), pool, pool, _s((), _I32), _s((4, 8), _I32),
        _s((4,), _I32))


def _prefill_attention(m):
    return (lambda q, k, v: m.gqa_prefill_attention(
        q, k, v, n_kv_heads=2, interpret=False)), (
        _s((2, 256, 4 * 128), _BF16), _s((2, 256, 2 * 128), _BF16),
        _s((2, 256, 2 * 128), _BF16))


def _grouped_swiglu(m):
    return (lambda *a: m.grouped_swiglu(*a, interpret=False)), (
        _s((64, 256), _BF16), _s((4, 256, 512), _BF16),
        _s((4, 256, 512), _BF16), _s((4, 512, 256), _BF16), _s((4,), _I32))


def _flash_attention(m):
    """Forward and both backward kernels, as the train step takes them."""
    def loss(q, k, v):
        return m.flash_attention(q, k, v, interpret=False).astype(_F32).sum()

    x = _s((1, 256, 2, 128), _BF16)
    return jax.grad(loss, argnums=(0, 1, 2)), (x, x, x)


#: kernel file under ``ray_tpu/ops`` -> (its module) -> (function, shapes)
KERNELS = {
    "ssm_pool": _ssm_pool,
    "kda_pool": _kda_pool,
    "paged_attention": _paged_attention,
    "prefill_attention": _prefill_attention,
    "grouped_swiglu": _grouped_swiglu,
    "flash_attention": _flash_attention,
}


@pytest.fixture(autouse=True)
def configured():
    """Every chip process goes through ``configure_jax()`` before its first
    program; so does every test here."""
    device.configure_jax()


def _payloads(case, module) -> list[bytes]:
    """The Mosaic module of every Pallas call ``case`` lowers for the TPU out
    of ``module``, as the bytes the compile cache's key reads."""
    fn, shapes = case(module)
    text = jax.jit(fn).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text()
    bodies = re.findall(r'\\22body\\22: *\\22([^\\]*)\\22', text)
    assert bodies, "no tpu_custom_call in the lowered program"
    return [base64.b64decode(b) for b in bodies]


def _moved(name: str, tmp_path):
    """``ray_tpu/ops/<name>.py`` imported from a copy under ``tmp_path`` with
    five blank lines above everything in it."""
    with open(os.path.join(REPO, "ray_tpu", "ops", name + ".py")) as f:
        source = f.read()
    path = tmp_path / (name + ".py")
    path.write_text("\n" * 5 + source)
    spec = importlib.util.spec_from_file_location("moved_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("name", KERNELS)
def test_a_kernel_payload_does_not_know_where_its_source_lies(name, tmp_path):
    here = _payloads(KERNELS[name], importlib.import_module(
        "ray_tpu.ops." + name))
    for payload in here:
        assert b".py" not in payload
        assert REPO.encode() not in payload
    there = _payloads(KERNELS[name], _moved(name, tmp_path))
    assert str(tmp_path).encode() not in b"".join(there)
    assert here == there


def test_configure_jax_writes_no_frames_and_keys_on_scopes():
    assert jax.config.jax_traceback_in_locations_limit == 0
    assert jax.config.jax_compilation_cache_include_metadata_in_key is True


def _cache_key(scope: str) -> str:
    """The persistent cache's key of one tiny program whose only difference
    from its like is the ``tracing.part`` its product stands under."""
    from jax._src import cache_key, compiler

    def program(x):
        with tracing.part(scope):
            return x * 2.0

    lowered = jax.jit(program).lower(_s((8, 128)))
    devices = np.array(jax.devices()[:1])
    return cache_key.get(
        lowered.compiler_ir("stablehlo"), devices,
        compiler.get_compile_options(num_replicas=1, num_partitions=1),
        devices[0].client)


def test_the_key_reads_a_programs_scopes_and_nothing_of_its_lines():
    """Two programs that differ only in a scope's name have two keys, so an
    executable from the cache carries the scopes of the tree that asked for
    it; the same program traced twice (two function objects, two lines of
    ``_cache_key``'s caller) has one."""
    ffn = _cache_key("ffn")
    assert _cache_key("head") != ffn
    again = _cache_key("ffn")
    assert again == ffn
