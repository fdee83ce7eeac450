"""Tenth model family: a looped decoder — ONE stack of layers applied
``n_passes`` times to every token with the same weights, sandwich norms and an
exit gate (``model_type: ouro``).

Same functional-pytree idiom as ``models/llama.py``. With ``N`` an RMSNorm of
its own scale, pass ``u`` of layer ``l`` on the residual ``x`` at position ``t``:

    a = N1(x);  q, k, v = a . [W_q | W_k | W_v] as heads of hd (no bias, no
        norm on q or k); q and k rotated over the whole head at t, half-split
        — the same t at every pass
    causal softmax(q . k_j / sqrt(hd)) over the k, v that THIS pass of this
        layer made at j <= t: a pass attends its own keys, never another's
    x = x + N2(o . W_o);   x = x + N4(SwiGLU(N3(x)))

Four norms a layer, before and after each half (``norm1`` .. ``norm4``: the
published ``input_layernorm``, ``input_layernorm_2``,
``post_attention_layernorm``, ``post_attention_layernorm_2``). After the last
layer a pass CLOSES: ``h_u = N_f(x)`` (the model's one final norm), ``lam_u =
sigmoid(h_u . w_g + b_g)`` (the exit gate), and ``h_u`` is what pass ``u + 1``
takes in. The exit rule reads the gates alone: ``p_u = lam_u prod_{j<u} (1 -
lam_j)`` (the last pass takes what is left), and the state the head reads is
that of the first pass whose summed ``p`` reaches ``exit_threshold``, the last
if none. Every pass is computed whatever the rule picks — the next position
attends every pass's keys — so the gate chooses a state and skips nothing.

The layer's kernels lie as the products want them: ``wqkv`` [D, (H + 2 KV)
hd] and ``w_gate_up`` [D, 2 ff] are ONE kernel each from the init on — the
same layer is read ``n_passes`` times a step, and nothing lays it out again.
The gate and its running products are float32.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import masked_attention
from ray_tpu.ops.basic import dense_init, rms_norm, rope, rope_freqs
from ray_tpu.utils import tracing


@dataclasses.dataclass(frozen=True)
class LoopedConfig:
    family = "looped"   # whose programs serve it: ray_tpu.llm.<family>
    vocab_size: int = 49152
    d_model: int = 2048
    n_layers: int = 48                # layers of WEIGHTS
    n_heads: int = 16
    n_kv_heads: int = 16
    head_dim: int = 128
    d_ff: int = 5632
    n_passes: int = 4                 # total_ut_steps: times the stack runs
    exit_threshold: float = 1.0       # early_exit_threshold
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 65536
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError("heads do not group over their KV heads, or a "
                             "head does not split in halves")
        if self.n_passes < 1:
            raise ValueError("a stack that never runs")

    @property
    def planes(self) -> int:
        """K/V planes of the cache: one a pass a layer, pass ``u`` (from 0)
        of layer ``l`` at ``u * n_layers + l``."""
        return self.n_passes * self.n_layers

    @classmethod
    def tiny(cls, **kw) -> "LoopedConfig":
        """Every ratio kept: as many KV heads as query heads, a SwiGLU 2.75
        times the model, 3 layers run 4 times (12 planes)."""
        base = dict(vocab_size=256, d_model=64, n_layers=3, n_heads=4,
                    n_kv_heads=4, head_dim=16, d_ff=176, max_seq_len=128,
                    dtype="float32")
        return cls(**{**base, **kw})


def looped_layer_init(key, cfg: LoopedConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    D, hd, F = cfg.d_model, cfg.head_dim, cfg.d_ff
    k = jax.random.split(key, 4)
    ones = {"scale": jnp.ones((D,), dtype)}
    return {"norm1": dict(ones), "norm2": dict(ones), "norm3": dict(ones),
            "norm4": dict(ones),
            "wqkv": dense_init(k[0], D, (cfg.n_heads + 2 * cfg.n_kv_heads) * hd,
                               dtype),
            "wo": dense_init(k[1], cfg.n_heads * hd, D, dtype),
            "w_gate_up": dense_init(k[2], D, 2 * F, dtype),
            "w_down": dense_init(k[3], F, D, dtype)}


def looped_init(key, cfg: LoopedConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, cfg.n_layers + 3)
    params: dict = {"tok": {"embedding": jax.random.normal(
        keys[0], (cfg.vocab_size, cfg.d_model)).astype(dtype)}}
    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = looped_layer_init(keys[3 + i], cfg)
    params["norm"] = {"scale": jnp.ones((cfg.d_model,), dtype)}
    params["gate"] = {
        "kernel": dense_init(keys[1], cfg.d_model, 1, jnp.float32)["kernel"][:, 0],
        "bias": jnp.zeros((), jnp.float32)}
    params["head"] = dense_init(keys[2], cfg.d_model, cfg.vocab_size, dtype)
    return params


# ------------------------------------------------------------------ the halves
def looped_rope(cfg: LoopedConfig):
    """cos, sin over the whole head."""
    return rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)


@tracing.part("project")
def looped_project(layer, x, cos, sin, positions, cfg: LoopedConfig):
    """The first half's way in: ``N1``, the one product for q, k and v, the
    rotation at ``positions`` [B, T]. x: [B, T, D]. Returns q [B, T, H, hd],
    k and v [B, T, KV, hd]."""
    B, T, _ = x.shape
    nq, nkv = (n * cfg.head_dim for n in (cfg.n_heads, cfg.n_kv_heads))
    h = rms_norm(x, layer["norm1"]["scale"], cfg.rms_norm_eps)
    qkv = h @ layer["wqkv"]["kernel"]
    q, k, v = (t.reshape(B, T, -1, cfg.head_dim) for t in (
        qkv[..., :nq], qkv[..., nq:nq + nkv], qkv[..., nq + nkv:]))
    return rope(q, cos, sin, positions), rope(k, cos, sin, positions), v


@tracing.part("attn_out")
def looped_attn_out(layer, x, att, cfg: LoopedConfig):
    """The attended rows ``att`` [B, T, H * hd] through ``W_o`` and ``N2``
    onto the residual ``x`` [B, T, D]."""
    return x + rms_norm(att @ layer["wo"]["kernel"], layer["norm2"]["scale"],
                        cfg.rms_norm_eps)


@tracing.part("ffn")
def looped_ffn(layer, x, cfg: LoopedConfig):
    """The second half on the residual: ``N3``, SwiGLU (gate and up as one
    product), ``N4``, residual."""
    h = rms_norm(x, layer["norm3"]["scale"], cfg.rms_norm_eps)
    gu = h @ layer["w_gate_up"]["kernel"]
    ff = gu.shape[-1] // 2
    y = (jax.nn.silu(gu[..., :ff]) * gu[..., ff:]) @ layer["w_down"]["kernel"]
    return x + rms_norm(y, layer["norm4"]["scale"], cfg.rms_norm_eps)


@tracing.part("head")
def looped_close(params, x, cfg: LoopedConfig, rows=None):
    """A pass's close: ``h = N_f(x)`` — what the next pass takes in — and
    the gate ``lam = sigmoid(g . w_g + b_g)`` in float32 over ``g =
    rows(h)``, the rows whose exit is asked for (None: every row of ``h``).
    Returns (h, g, lam)."""
    h = rms_norm(x, params["norm"]["scale"], cfg.rms_norm_eps)
    g = h if rows is None else rows(h)
    gate = params["gate"]
    lam = jax.nn.sigmoid(g.astype(jnp.float32) @ gate["kernel"] + gate["bias"])
    return h, g, lam


def looped_exit_start(g):
    """The exit rule's state before the first pass, for rows like ``g`` [...,
    D]: (what the gates so far left ``prod (1 - lam)``, the summed ``p``,
    the chosen state, its pass — 0: none yet)."""
    lead = g.shape[:-1]
    return (jnp.ones(lead, jnp.float32), jnp.zeros(lead, jnp.float32),
            jnp.zeros_like(g), jnp.zeros(lead, jnp.int32))


@tracing.part("head")
def looped_exit(state, g, lam, u, cfg: LoopedConfig):
    """The exit rule after pass ``u`` (from 0; a traced value in the
    programs' loop): ``p_u = lam_u . left`` (the last pass: all that is
    left), and a row whose summed ``p`` now reaches the threshold — or that
    no pass chose — takes this pass's state ``g``. All float32."""
    left, total, chosen, depth = state
    last = u == cfg.n_passes - 1
    total = total + jnp.where(last, left, lam * left)
    pick = (depth == 0) & ((total >= cfg.exit_threshold) | last)
    return (left * (1.0 - lam), total, jnp.where(pick[..., None], g, chosen),
            jnp.where(pick, u + 1, depth))


@tracing.part("head")
def looped_logits(params, g):
    """The head on the chosen states: its own [D, vocab] matrix."""
    return g @ params["head"]["kernel"]


def looped_forward(params, tokens, cfg: LoopedConfig):
    """tokens: [B, T] int32 -> (logits [B, T, vocab], exit pass [B, T]): the
    whole model with no cache, plain masked attention, the passes unrolled."""
    B, T = tokens.shape
    cos, sin = looped_rope(cfg)
    idx = jnp.arange(T)
    positions = jnp.broadcast_to(idx[None, :], (B, T))
    causal = jnp.broadcast_to(idx[:, None] >= idx[None, :], (B, T, T))
    x = params["tok"]["embedding"][tokens]
    state = looped_exit_start(x)
    for u in range(cfg.n_passes):
        for i in range(cfg.n_layers):
            layer = params[f"layers_{i}"]
            q, k, v = looped_project(layer, x, cos, sin, positions, cfg)
            x = looped_attn_out(layer, x, masked_attention(q, k, v, causal), cfg)
            x = looped_ffn(layer, x, cfg)
        x, g, lam = looped_close(params, x, cfg)
        state = looped_exit(state, g, lam, u, cfg)
    return looped_logits(params, state[2]), state[3]
