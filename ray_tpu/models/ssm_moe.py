"""Fifth model family: state-space layers beside attention and sparse experts
(``model_type: nemotron_h``) — a LAYER PATTERN in place of "attention then
feed-forward".

Same functional-pytree idiom as ``models/llama.py``. ``pattern`` (the
published ``hybrid_override_pattern``) has one character a block, and every
block is ONE mixer behind one RMSNorm: ``x' = x + Mixer_i(rms_norm(x))``.

* **``M``, Mamba-2.** ``[z | u | dt] = h . W_in`` (``d_inner`` = heads x head
  width, conv width ``C = d_inner + 2 . groups . state``, a ``dt`` a head);
  ``u`` through a causal depthwise convolution of ``conv_kernel`` taps with
  bias and silu, then split into ``x`` [heads, head width] and ``B``, ``C``
  [groups, state] (head h reads group ``h // (heads // groups)``);
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, and the recurrence of
  ``ops/ssm.py`` with ``dt``, the decays and the state in float32;
  ``y . silu(z)`` (the gate FIRST), an RMSNorm over each group's lanes with
  one gain, ``y . W_out``. What the block keeps of a sequence is fixed
  whatever its length: the state ``[heads, head width, state]`` and the
  convolution's last ``conv_kernel - 1`` inputs.
* **``*``, attention.** Grouped-query, no bias, NO rotation (the Mamba-2
  blocks carry order), causal softmax over ``sqrt(head_dim)``.
* **``E``, experts** (``parallel/moe.py`` ``moe_layer``): sigmoid scores in
  float32, the k largest ``score + bias``, weights normalised and scaled;
  every expert, routed and shared, is TWO matrices, ``W_down .
  relu(W_up . h)^2`` — no gate. The layer is told which experts it holds
  (``experts_held``): holders' parts add up to the layer with the shared
  expert counted once.
* **Head.** RMSNorm and an untied head over the held rows (``vocab_held``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import masked_attention
from ray_tpu.ops import ssm
from ray_tpu.ops.basic import dense_init, experts_init, rms_norm
from ray_tpu.parallel.moe import moe_layer_chunked
from ray_tpu.utils import tracing

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
_PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclasses.dataclass(frozen=True)
class SsmMoeConfig:
    family = "ssm_moe"   # whose programs serve it: ray_tpu.llm.<family>
    vocab_size: int = 131072          # rows of embedding and head held HERE
    d_model: int = 2688
    pattern: str = _PUBLISHED         # one character a block: M, E or *
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128             # the scan's tiling: no result depends on it
    n_experts: int = 128              # routed, over all holders
    n_experts_per_tok: int = 6
    d_expert: int = 1856
    n_shared_experts: int = 1
    d_shared: int = 3712              # one shared expert's width
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 262144
    dtype: str = "bfloat16"
    # this holder's routed experts of every expert block, [lo, hi); None = all
    experts_held: tuple[int, int] | None = None
    # which rows of the published vocabulary the vocab_size rows here are
    vocab_held: tuple[int, int] | None = None

    def __post_init__(self):
        if not self.pattern or set(self.pattern) - {MAMBA, EXPERTS, ATTENTION}:
            raise ValueError(f"a pattern of M, E and *, not {self.pattern!r}")
        if self.mamba_heads % self.n_groups or self.n_heads % self.n_kv_heads:
            raise ValueError("heads do not divide into their groups")
        if self.vocab_held and (
                self.vocab_held[1] - self.vocab_held[0] != self.vocab_size):
            raise ValueError(f"vocab_held {self.vocab_held} is not "
                             f"{self.vocab_size} rows")

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def held(self) -> tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_width(self) -> int:
        """Lanes the convolution runs over: x, then B and C of every group."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state

    def blocks_of(self, kind: str) -> tuple[int, ...]:
        """The blocks of one kind, in order: a block's place in this list is
        its index into that kind's pools."""
        return tuple(i for i, c in enumerate(self.pattern) if c == kind)

    @classmethod
    def tiny(cls, **kw) -> "SsmMoeConfig":
        """The published shape's ratios kept: 8 Mamba-2 heads a group, G = 4
        query heads a KV head, a shared expert twice a routed one's width,
        one period of the pattern and a chunk far under the context."""
        base = dict(vocab_size=256, d_model=64, pattern="MEM*EME",
                    n_heads=8, n_kv_heads=2, head_dim=16, mamba_heads=16,
                    mamba_head_dim=8, n_groups=2, ssm_state=16, conv_kernel=4,
                    chunk_size=8, n_experts=16, n_experts_per_tok=4,
                    d_expert=32, d_shared=64, max_seq_len=128, dtype="float32")
        return cls(**{**base, **kw})


def ssm_moe_layer_init(key, cfg: SsmMoeConfig, kind: str) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    D = cfg.d_model
    k = jax.random.split(key, 8)
    layer: dict = {"norm": {"scale": jnp.ones((D,), dtype)}}
    if kind == MAMBA:
        Hm, C = cfg.mamba_heads, cfg.conv_width
        layer |= {
            "in_proj": dense_init(k[0], D, cfg.d_inner + C + Hm, dtype),
            "conv": {"kernel": (jax.random.normal(k[1], (cfg.conv_kernel, C))
                                * cfg.conv_kernel ** -0.5).astype(dtype),
                     "bias": jnp.zeros((C,), dtype)},
            # a trained model's ranges, so that decays are neither 0 nor 1:
            # A in [1, 16], dt = softplus(dt_bias) in [0.001, 0.1]
            "A_log": jnp.log(jax.random.uniform(
                k[2], (Hm,), jnp.float32, 1.0, 16.0)),
            "dt_bias": _inv_softplus(jnp.exp(jax.random.uniform(
                k[3], (Hm,), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))),
            "D": jnp.ones((Hm,), jnp.float32),
            "gate_norm": {"scale": jnp.ones((cfg.d_inner,), dtype)},
            "out_proj": dense_init(k[4], cfg.d_inner, D, dtype),
        }
    elif kind == ATTENTION:
        H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        layer |= {"wq": dense_init(k[0], D, H * hd, dtype),
                  "wk": dense_init(k[1], D, KV * hd, dtype),
                  "wv": dense_init(k[2], D, KV * hd, dtype),
                  "wo": dense_init(k[3], H * hd, D, dtype)}
    else:
        F, Fs = cfg.d_expert, cfg.n_shared_experts * cfg.d_shared
        lo, hi = cfg.held
        layer["moe"] = {
            "router": {"kernel": dense_init(k[0], D, cfg.n_experts, dtype)["kernel"],
                       "bias": jnp.zeros((cfg.n_experts,), jnp.float32)},
            # every holder draws all experts' numbers and keeps its own, so
            # the shares of one seed are slices of one model
            "experts": {
                "w_up": experts_init(k[1], cfg.n_experts, D, F, dtype)[lo:hi],
                "w_down": experts_init(k[2], cfg.n_experts, F, D, dtype)[lo:hi]},
            "shared": {"w_up": dense_init(k[3], D, Fs, dtype),
                       "w_down": dense_init(k[4], Fs, D, dtype)},
        }
    return layer


def _inv_softplus(y):
    return y + jnp.log(-jnp.expm1(-y))


def ssm_moe_init(key, cfg: SsmMoeConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, cfg.n_layers + 2)
    params: dict = {"tok": {"embedding": jax.random.normal(
        keys[0], (cfg.vocab_size, cfg.d_model)).astype(dtype)}}
    for i, kind in enumerate(cfg.pattern):
        params[f"layers_{i}"] = ssm_moe_layer_init(keys[2 + i], cfg, kind)
    params["norm"] = {"scale": jnp.ones((cfg.d_model,), dtype)}
    params["lm_head"] = dense_init(keys[1], cfg.d_model, cfg.vocab_size, dtype)
    return params


# ------------------------------------------------------------------ the halves
def block_norm(layer, x, cfg: SsmMoeConfig):
    """The one norm in front of a block's mixer."""
    return rms_norm(x, layer["norm"]["scale"], cfg.rms_norm_eps)


@tracing.part("project")
def mamba_in(layer, x, cfg: SsmMoeConfig):
    """The Mamba-2 block's norm and input projection. x: [B, T, D]. Returns
    the gate z [B, T, d_inner], the convolution's input u [B, T, C] and the
    raw dt [B, T, heads]."""
    zudt = block_norm(layer, x, cfg) @ layer["in_proj"]["kernel"]
    return jnp.split(zudt, (cfg.d_inner, cfg.d_inner + cfg.conv_width), axis=-1)


def split_conv(xbc, cfg: SsmMoeConfig):
    """The convolution's output [..., C] as x [..., heads, head width] and
    B, C [..., groups, state]."""
    G, S = cfg.n_groups, cfg.ssm_state
    x, Bm, Cm = jnp.split(xbc, (cfg.d_inner, cfg.d_inner + G * S), axis=-1)
    lead = xbc.shape[:-1]
    return (x.reshape(*lead, cfg.mamba_heads, cfg.mamba_head_dim),
            Bm.reshape(*lead, G, S), Cm.reshape(*lead, G, S))


def mamba_dt(layer, dt):
    """``softplus(dt + dt_bias)`` in float32. dt: [..., heads]."""
    return jax.nn.softplus(dt.astype(jnp.float32) + layer["dt_bias"])


def mamba_decay(layer):
    """``A = -exp(A_log)``, a scalar a head, float32."""
    return -jnp.exp(layer["A_log"])


def gated_norm(layer, y, z, cfg: SsmMoeConfig, dtype):
    """``rms_norm_groups(y . silu(z)) . gain``: the gate first, then a norm
    over each group's ``d_inner / groups`` lanes. y: [..., heads, head width]
    float32; z: [..., d_inner]. Returns [..., d_inner] in ``dtype``."""
    lead = z.shape[:-1]
    g = y.reshape(*lead, cfg.d_inner) * jax.nn.silu(z.astype(jnp.float32))
    g = g.reshape(*lead, cfg.n_groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + cfg.rms_norm_eps)
    return (g.reshape(*lead, cfg.d_inner)
            * layer["gate_norm"]["scale"].astype(jnp.float32)).astype(dtype)


@tracing.part("attn_out")
def mixer_out(layer, y, name: str):
    """A mixer's output projection onto the residual (``out_proj`` of a
    Mamba-2 block, ``wo`` of an attention block)."""
    return y @ layer[name]["kernel"]


def mamba_mixer(layer, x, cfg: SsmMoeConfig, valid=None, tails=None):
    """A whole Mamba-2 block over sequences from a zero state, the chunked
    scan. x: [N, T, D]; ``valid`` [N, T]: positions that advance the state
    (None: all); ``tails`` [N] int32: where to read the convolution's saved
    inputs (the K - 1 before that position). Returns (y [N, T, D], the
    state after the last valid position [N, heads, head width, state]
    float32, the saved inputs [N, K - 1, C] or None)."""
    z, u, dt = mamba_in(layer, x, cfg)
    with tracing.part("conv"):
        xbc = ssm.causal_conv(u, layer["conv"]["kernel"],
                              layer["conv"]["bias"])
        saved = (None if tails is None
                 else ssm.conv_tail(u, tails, cfg.conv_kernel))
    with tracing.part("ssm"):
        xs, Bm, Cm = split_conv(xbc, cfg)
        dt = mamba_dt(layer, dt)
        if valid is not None:
            dt = jnp.where(valid[..., None], dt, 0.0)
        y, S = ssm.ssm_chunked(xs, dt, mamba_decay(layer), Bm, Cm, layer["D"],
                               cfg.chunk_size)
        y = gated_norm(layer, y, z, cfg, x.dtype)
    return mixer_out(layer, y, "out_proj"), S, saved


@tracing.part("project")
def attn_project(layer, x, cfg: SsmMoeConfig):
    """The attention block's norm and projections: q [B, T, H, hd], k and v
    [B, T, KV, hd]. Nothing is rotated."""
    h = block_norm(layer, x, cfg)
    B, T, _ = h.shape
    q = (h @ layer["wq"]["kernel"]).reshape(B, T, cfg.n_heads, cfg.head_dim)
    k = (h @ layer["wk"]["kernel"]).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    v = (h @ layer["wv"]["kernel"]).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


@tracing.part("experts")
def expert_block(layer, x, cfg: SsmMoeConfig, valid=None):
    """The expert block on x [B, T, D] -> (y [B, T, D], load [held])."""
    return moe_layer_chunked(
        block_norm(layer, x, cfg), layer["moe"], valid,
        k=cfg.n_experts_per_tok, scale=cfg.routed_scaling_factor,
        norm=cfg.norm_topk_prob, held=cfg.held)


@tracing.part("head")
def ssm_moe_logits(params, x, cfg: SsmMoeConfig):
    """The untied head over the held rows. x: [..., D]."""
    x = rms_norm(x, params["norm"]["scale"], cfg.rms_norm_eps)
    return x @ params["lm_head"]["kernel"]


def ssm_moe_forward(params, tokens, cfg: SsmMoeConfig):
    """tokens: [B, T] int32 -> logits [B, T, held rows]: the whole model
    with no cache, plain masked attention."""
    B, T = tokens.shape
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool)), (B, T, T))
    x = params["tok"]["embedding"][tokens]
    for i, kind in enumerate(cfg.pattern):
        layer = params[f"layers_{i}"]
        if kind == MAMBA:
            y, _, _ = mamba_mixer(layer, x, cfg)
        elif kind == ATTENTION:
            q, k, v = attn_project(layer, x, cfg)
            y = mixer_out(layer, masked_attention(q, k, v, causal), "wo")
        else:
            y, _ = expert_block(layer, x, cfg)
        x = x + y
    return ssm_moe_logits(params, x, cfg)
