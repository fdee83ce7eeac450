"""Flagship model: Llama-family decoder-only transformer, TPU-native.

Functional pytree implementation (no framework Module state): params are a
dict keyed so `parallel.sharding.PartitionRules.llama()` maps every weight
to its TP/FSDP axes by path regex, attention dispatches to
plain/flash/ring/ulysses by mesh (ops/attention.py), each block is wrapped
in jax.checkpoint (remat) to trade FLOPs for HBM, and optional MoE layers
(``n_experts`` > 0, every ``moe_every``-th layer) use the Switch top-1,
capacity-dropping, two-matrix branch of parallel/moe.py (``moe_ffn``) —
reachable from ``llama_forward`` in training only: the serving programs of
llm/llama.py have no expert path for this family. The expert layer that
IS served (sigmoid top-k, no capacity, SwiGLU experts, shared experts) is
the other family's: models/mla_moe.py. Matches the model families the
reference serves through vLLM (Llama-2/3) but as a native JAX program.

The dense layer is written ONCE (``llama_project``, ``llama_attn_out``,
``llama_ffn``), as models/mla_moe.py writes its own. Where K and V are
written and what is attended lies between them and is each path's own:
``_block``, ``_block_tp``, llm/generation.py, the programs of llm/llama.py.
Nothing else reads a dense layer's seven kernels (tests/test_llm.py).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name as _checkpoint_name

from ray_tpu.ops.attention import attention
from ray_tpu.ops.basic import dense_init, rms_norm, rope, rope_freqs, swiglu
from ray_tpu.utils import tracing


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    family = "llama"   # whose programs serve it: ray_tpu.llm.<family>
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    dtype: str = "bfloat16"
    # MoE: 0 experts = dense; else every `moe_every`-th layer is MoE
    n_experts: int = 0
    moe_every: int = 2
    capacity_factor: float = 1.25
    remat: bool = True

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        return cls(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                   n_kv_heads=2, d_ff=128, max_seq_len=128, dtype="float32", **kw)

    @classmethod
    def llama2_7b(cls) -> "LlamaConfig":
        return cls(vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
                   n_kv_heads=32, d_ff=11008, max_seq_len=4096)

    @classmethod
    def llama3_8b(cls) -> "LlamaConfig":
        return cls(vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
                   n_kv_heads=8, d_ff=14336, max_seq_len=8192,
                   rope_theta=500000.0)


def _is_moe_layer(cfg: LlamaConfig, i: int) -> bool:
    return cfg.n_experts > 0 and (i % cfg.moe_every == cfg.moe_every - 1)


def llama_init(key, cfg: LlamaConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    hd = cfg.head_dim
    keys = jax.random.split(key, cfg.n_layers * 8 + 3)
    ki = iter(range(len(keys)))
    params: dict = {
        "tok": {
            "embedding": (
                jax.random.normal(keys[next(ki)], (cfg.vocab_size, cfg.d_model)) * 0.02
            ).astype(dtype)
        }
    }
    for i in range(cfg.n_layers):
        layer = {
            "attn_norm": {"scale": jnp.ones((cfg.d_model,), dtype)},
            "wq": dense_init(keys[next(ki)], cfg.d_model, cfg.n_heads * hd, dtype),
            "wk": dense_init(keys[next(ki)], cfg.d_model, cfg.n_kv_heads * hd, dtype),
            "wv": dense_init(keys[next(ki)], cfg.d_model, cfg.n_kv_heads * hd, dtype),
            "wo": dense_init(keys[next(ki)], cfg.n_heads * hd, cfg.d_model, dtype),
            "ffn_norm": {"scale": jnp.ones((cfg.d_model,), dtype)},
        }
        if _is_moe_layer(cfg, i):
            e = cfg.n_experts
            k1, k2, k3 = jax.random.split(keys[next(ki)], 3)
            layer["moe"] = {
                "gate": {"kernel": (jax.random.normal(k1, (cfg.d_model, e)) * 0.02).astype(dtype)},
                "w_up": {"kernel": (jax.random.normal(k2, (e, cfg.d_model, cfg.d_ff)) * 0.02).astype(dtype)},
                "w_down": {"kernel": (jax.random.normal(k3, (e, cfg.d_ff, cfg.d_model)) * 0.02).astype(dtype)},
            }
        else:
            layer["w_gate"] = dense_init(keys[next(ki)], cfg.d_model, cfg.d_ff, dtype)
            layer["w_up"] = dense_init(keys[next(ki)], cfg.d_model, cfg.d_ff, dtype)
            layer["w_down"] = dense_init(keys[next(ki)], cfg.d_ff, cfg.d_model, dtype)
        params[f"layers_{i}"] = layer
    params["norm"] = {"scale": jnp.ones((cfg.d_model,), dtype)}
    params["lm_head"] = dense_init(keys[next(ki)], cfg.d_model, cfg.vocab_size, dtype)
    return params


# ------------------------------------------------- the dense layer, written once
def _lora_delta(h, loras, name, aid):
    """Per-slot low-rank delta: h[B,T,D] x A[aid][D,r] x Bm[aid][r,O]."""
    a = loras[name + "_a"][aid]  # [B, D, r]
    b = loras[name + "_b"][aid]  # [B, r, O]
    return jnp.einsum("btd,bdr->btr", h, a) @ b if a.ndim == 3 else (h @ a) @ b


@tracing.part("project")
def llama_project(layer, x, cos, sin, positions, cfg: LlamaConfig, *,
                  loras=None, aids=None):
    """The layer's first half on the residual ``x`` [B, T, D]: norm, q/k/v
    (plus the LoRA deltas of the slots' adapters ``aids`` on q and v where
    ``loras`` is given), rope at ``positions`` ([B, T]; None = 0..T-1).
    Returns q [B, T, H, hd], k and v [B, T, KV, hd]: head counts are the
    kernels' widths over ``head_dim``, so a tensor-parallel slice is the
    same call. A layer in the serving layout (``llama_serving_layout``:
    ``wqkv``) takes ONE product and the result is sliced; the weights are
    read as they lie either way."""
    B, T, _ = x.shape
    h = rms_norm(x, layer["attn_norm"]["scale"])
    if "wqkv" in layer:
        nq, nkv = (n * cfg.head_dim for n in (cfg.n_heads, cfg.n_kv_heads))
        qkv = h @ layer["wqkv"]["kernel"]
        q, k, v = qkv[..., :nq], qkv[..., nq:nq + nkv], qkv[..., nq + nkv:]
    else:
        q, k, v = (h @ layer["wq"]["kernel"], h @ layer["wk"]["kernel"],
                   h @ layer["wv"]["kernel"])
    if loras is not None:
        q = q + _lora_delta(h, loras, "wq", aids)
        v = v + _lora_delta(h, loras, "wv", aids)
    q, k, v = (t.reshape(B, T, -1, cfg.head_dim) for t in (q, k, v))
    return rope(q, cos, sin, positions), rope(k, cos, sin, positions), v


def _rejoin(y, tp_axis):
    """A row-parallel product back on the residual stream: summed over the
    tensor-parallel ranks inside a shard_map body, itself elsewhere."""
    return y if tp_axis is None else jax.lax.psum(y, tp_axis)


@tracing.part("attn_out")
def llama_attn_out(layer, x, att, tp_axis: str | None = None):
    """The attended rows ``att`` [B, T, H, hd] (or [B, H, hd] for T = 1)
    through ``wo``, onto the residual ``x`` [B, T, D]."""
    B, T, _ = x.shape
    return x + _rejoin(att.reshape(B, T, -1) @ layer["wo"]["kernel"], tp_axis)


@tracing.part("ffn")
def llama_ffn(layer, x, *, tp_axis: str | None = None):
    """The layer's second half on the residual ``x``: norm, SwiGLU,
    residual. A layer in the serving layout (``w_gate_up``) takes one
    product for gate and up."""
    h = rms_norm(x, layer["ffn_norm"]["scale"])
    w_down = layer["w_down"]["kernel"]
    if "w_gate_up" in layer:
        gu = h @ layer["w_gate_up"]["kernel"]
        ff = gu.shape[-1] // 2
        y = (jax.nn.silu(gu[..., :ff]) * gu[..., ff:]) @ w_down
    else:
        y = swiglu(h, layer["w_gate"]["kernel"], layer["w_up"]["kernel"],
                   w_down)
    return x + _rejoin(y, tp_axis)


# a serving layout's joined kernels, and the kernels each lays side by side
_JOINED = {"wqkv": ("wq", "wk", "wv"), "w_gate_up": ("w_gate", "w_up")}


def llama_serving_layout(params, cfg: LlamaConfig):
    """The tree as a serving engine keeps it: every layer's ``wq``, ``wk``,
    ``wv`` side by side as ONE kernel ``wqkv`` [D, (H + 2 KV) hd] and
    ``w_gate``, ``w_up`` as one ``w_gate_up`` [D, 2 ff] — a decode step is
    bound by its count of operations, so the halves above take one product
    where the layer holds the joined kernel, and no program lays a weight
    out again. Norms, ``wo``, ``w_down``, an expert layer's ``moe``, the
    embedding and the head stay as they are; a layer already joined is left
    alone.

    Done IN PLACE, a kernel at a time: the layer's dicts lose the originals
    as the joined kernel is made, so the most held is one joined kernel above
    the tree — a second set of them (3.7 GB for 13 layers at Mistral's widths)
    beside a tree that fills half the chip would not be freed before the
    pools are made. The tree stays one every function of this file takes;
    train, checkpoints and ``parallel/``'s column slices want the three
    apart and never come here. Works on a tree of tracers
    (``jax.eval_shape``)."""
    for i in range(cfg.n_layers):
        layer = params[f"layers_{i}"]
        for joined, names in _JOINED.items():
            if names[0] in layer:
                layer[joined] = {"kernel": jnp.concatenate(
                    [layer.pop(n)["kernel"] for n in names], axis=1)}
    return params


def _block(layer, x, cos, sin, cfg: LlamaConfig, mesh, attn_impl, seq_axis):
    q, k, v = llama_project(layer, x, cos, sin, None, cfg)
    # named for the remat policy: the flash backward consumes q/k/v
    # directly, so saving them skips recomputing three projections + rope
    # per layer in the backward pass (bytes: 3*d_model*T per layer)
    q = _checkpoint_name(q, "attn_qkv")
    k = _checkpoint_name(k, "attn_qkv")
    v = _checkpoint_name(v, "attn_qkv")
    att = attention(q, k, v, causal=True, mesh=mesh, seq_axis=seq_axis, impl=attn_impl)
    # named so the remat policy can SAVE attention outputs: recomputing
    # the O(T^2) attention forward in the backward pass costs ~10 MFU
    # points at 8k context, while saving att is only d_model*T per layer
    att = _checkpoint_name(att, "attn_out")
    x = llama_attn_out(layer, x, att)
    if "moe" not in layer:
        return llama_ffn(layer, x), 0.0
    from ray_tpu.parallel.moe import moe_ffn

    out, aux = moe_ffn(
        rms_norm(x, layer["ffn_norm"]["scale"]),
        layer["moe"]["gate"]["kernel"],
        layer["moe"]["w_up"]["kernel"],
        layer["moe"]["w_down"]["kernel"],
        capacity_factor=cfg.capacity_factor,
        mesh=mesh,
    )
    return x + out, aux


def _maybe_remat_block(cfg: LlamaConfig):
    """One remat policy for all forward paths (dense, pipelined).

    Selective remat: attention outputs (+lse), post-rope q/k/v and the
    FFN gate/up products are SAVED (~(4*d_model + 2*d_ff) * T * L bytes
    of residuals, ~10x d_model*T*L with the usual d_ff ratio); norms and
    the remaining matmuls rematerialize. Saving attention kills the
    O(T^2) flash-forward recompute (43% -> 49% MFU at 8k measured);
    saving qkv/ffn trades affordable HBM for the rest (-> 54% at 8k,
    69% at 512). Set remat=False only when everything fits."""
    if not cfg.remat:
        return _block
    return jax.checkpoint(
        _block, static_argnums=(4, 5, 6, 7),
        policy=jax.checkpoint_policies.save_only_these_names(
            "attn_out", "attn_qkv", "ffn_hidden"),
    )


def _ce_loss(logits, targets):
    """Next-token cross entropy shared by llama_loss / llama_pp_loss."""
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0].mean()


def llama_forward(params, tokens, cfg: LlamaConfig, *, mesh=None,
                  attn_impl: str = "auto", seq_axis: str | None = "sp"):
    """tokens: [B, T] int32 -> logits [B, T, V]."""
    if mesh is not None and (seq_axis not in mesh.shape or mesh.shape[seq_axis] == 1):
        seq_axis = None
    cos, sin = rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    x = params["tok"]["embedding"][tokens]
    aux_total = 0.0
    block = _maybe_remat_block(cfg)
    for i in range(cfg.n_layers):
        x, aux = block(params[f"layers_{i}"], x, cos, sin, cfg, mesh, attn_impl, seq_axis)
        aux_total = aux_total + aux
    x = rms_norm(x, params["norm"]["scale"])
    logits = x @ params["lm_head"]["kernel"]
    return logits, aux_total


def llama_loss(params, batch, cfg: LlamaConfig, *, mesh=None, attn_impl="auto"):
    """Next-token cross entropy; batch: {"tokens": [B, T+1]}."""
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits, aux = llama_forward(params, inputs, cfg, mesh=mesh, attn_impl=attn_impl)
    return _ce_loss(logits, targets) + 0.01 * aux


# ------------------------------------------------------- pipelined variant
def llama_pp_init(key, cfg: LlamaConfig, n_stages: int) -> dict:
    """Init with transformer layers stacked for pipeline parallelism:
    ``stages`` leaves carry a leading [n_stages, layers_per_stage] axis
    (sharded on the ``pp`` mesh axis by pipeline_apply); embedding/norm/head
    stay in ``dense`` and run outside the pipeline body. Dense layers only
    (MoE composes with ep/fsdp meshes on the non-pipelined path)."""
    if cfg.n_experts:
        raise ValueError("pipelined llama requires dense layers (n_experts=0)")
    if cfg.n_layers % n_stages:
        raise ValueError(f"{cfg.n_layers} layers not divisible by {n_stages} stages")
    params = llama_init(key, cfg)
    per = cfg.n_layers // n_stages
    layers = [params.pop(f"layers_{i}") for i in range(cfg.n_layers)]
    stages = []
    for s in range(n_stages):
        chunk = layers[s * per: (s + 1) * per]
        stages.append(jax.tree.map(lambda *xs: jnp.stack(xs), *chunk))  # [per,...]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *stages)  # [pp, per, ...]
    return {"dense": params, "stages": stacked}


def _block_tp(layer, x, cos, sin, cfg: LlamaConfig, tp_axis: str):
    """Megatron-style tensor-parallel transformer block for use INSIDE a
    shard_map body (each tp rank holds a weight slice): q/k/v and
    gate/up are column-parallel (heads / ff split across ranks), wo and
    w_down row-parallel with a psum to rejoin the residual stream."""
    q, k, v = llama_project(layer, x, cos, sin, None, cfg)
    att = attention(q, k, v, causal=True, mesh=None, seq_axis=None,
                    impl="plain")
    x = llama_attn_out(layer, x, att, tp_axis)
    return llama_ffn(layer, x, tp_axis=tp_axis)


def pp_stage_param_specs(stacked_params, *, pp_axis: str = "pp",
                         tp_axis: str | None = None):
    """PartitionSpecs for pipeline stage weights: leading stage axis on
    pp; with ``tp_axis``, attention/ffn weights additionally split
    Megatron-style (column for wq/wk/wv/w_gate/w_up, row for
    wo/w_down)."""
    from jax.sharding import PartitionSpec as P

    col = {"wq", "wk", "wv", "w_gate", "w_up"}
    row = {"wo", "w_down"}

    def spec(path, leaf):
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        if tp_axis:
            if any(n in col for n in names):
                return P(pp_axis, *([None] * (leaf.ndim - 2)), tp_axis)
            if any(n in row for n in names):
                return P(pp_axis, *([None] * (leaf.ndim - 3)), tp_axis, None)
        return P(pp_axis)

    return jax.tree_util.tree_map_with_path(spec, stacked_params)


def llama_pp_loss(params, batch, cfg: LlamaConfig, mesh, *, n_microbatches: int,
                  attn_impl: str = "plain", batch_axis: str | None = "dp",
                  tp_axis: str | None = None):
    """Next-token CE through a GPipe pipeline over the mesh's pp axis
    (ref: SURVEY §2.3 PP — the reference only gets PP via vLLM config or
    compiled-graph p2p channels; here the pipeline is one jitted SPMD
    program, see parallel/pipeline.py). With ``tp_axis`` each stage ALSO
    runs Megatron tensor parallelism over that mesh axis — the full
    dp x tp x pp composition in one program."""
    from jax import lax

    from ray_tpu.parallel.pipeline import pipeline_apply

    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    dense = params["dense"]
    x = dense["tok"]["embedding"][inputs]
    cos, sin = rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    block = _maybe_remat_block(cfg)

    if tp_axis is not None:
        tp_block = (jax.checkpoint(_block_tp, static_argnums=(4, 5))
                    if cfg.remat else _block_tp)

        def stage_fn(stage_params, h):
            def layer_step(h, layer):
                return tp_block(layer, h, cos, sin, cfg, tp_axis), None

            h, _ = lax.scan(layer_step, h, stage_params)
            return h

        param_specs = pp_stage_param_specs(
            params["stages"], tp_axis=tp_axis)
    else:
        def stage_fn(stage_params, h):
            def layer_step(h, layer):
                h, _ = block(layer, h, cos, sin, cfg, None, attn_impl, None)
                return h, None

            h, _ = lax.scan(layer_step, h, stage_params)
            return h

        param_specs = None

    x = pipeline_apply(stage_fn, params["stages"], x, mesh,
                       n_microbatches=n_microbatches, batch_axis=batch_axis,
                       param_specs=param_specs)
    x = rms_norm(x, dense["norm"]["scale"])
    return _ce_loss(x @ dense["lm_head"]["kernel"], targets)


def make_train_step(cfg: LlamaConfig, optimizer, *, mesh=None, attn_impl="auto",
                    donate: bool = True):
    """Returns jitted (params, opt_state, batch) -> (params, opt_state, loss).

    Shard via jit's in_shardings at the call site (train/ wires this to
    PartitionRules.llama over the worker-group mesh).
    """

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: llama_loss(p, batch, cfg, mesh=mesh, attn_impl=attn_impl)
        )(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        import optax

        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1) if donate else ())
