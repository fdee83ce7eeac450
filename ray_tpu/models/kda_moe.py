"""Seventh model family: delta-rule layers beside latent attention and
group-routed sparse experts (``Ling-3.0-flash``'s language model) — a layer is
a MIXER by its place, then a feed-forward.

Same functional-pytree idiom as ``models/llama.py``. Every layer is ``x <- x +
Mixer_i(N(x))``, ``x <- x + FFN_i(N(x))``, ``N`` an RMSNorm. Layer ``i`` is a
latent-attention layer where ``(i + 1) % layer_group_size == 0`` and a
delta-rule layer elsewhere (5 : 1 at the published 6).

* **KDA mixer** (Kimi Delta Attention, ``ops/kda.py``). One projection of the
  normed input gives ``[q | k | v | a | beta | gate]``; ``q``, ``k``, ``v``
  (heads x 128 each) go through a causal depthwise convolution of
  ``conv_kernel`` taps with no bias and silu; ``q`` and ``k`` are L2-normalised
  a head (``q`` carries ``head_dim ** -0.5``); the decay is **a lane of a
  head**, ``g = kda_lower_bound . sigmoid(exp(A_log) . (a + a_bias))`` in
  ``(-5, 0)``, ``beta = sigmoid(beta)`` a head; the state ``[heads, 128, 128]``
  takes the delta rule in float32; each head's read-out is RMS-normed over its
  128 lanes with one gain, scaled by the head's gate ``sigmoid(gate)``, and
  projected back. What the mixer keeps of a sequence is fixed whatever its
  length: the state and the convolution's last ``conv_kernel - 1`` inputs.
* **MLA mixer** (``ops/mla.py``: DeepSeek-V3's, no query bottleneck), with the
  same head-wise gate on each head's output before ``wo``. A cache holds
  ``[c, k_rope]``, ``latent_width`` numbers a position a layer.
* **Feed-forward.** The first ``first_dense_layers`` layers are a SwiGLU; every
  later one is ``parallel/moe.py``'s expert layer with **group-limited**
  sigmoid routing: ``n_group`` groups of consecutive experts, the
  ``topk_group`` groups of largest score kept (a group's score: the sum of
  its two largest ``score + bias``), ``n_experts_per_tok`` experts chosen
  among theirs, weighed by the score alone; one shared SwiGLU. The layer is
  told which experts it holds (``experts_held``): holders' parts add up to
  the layer with the shared expert counted once.
* **Head.** RMSNorm and an untied head over the held rows (``vocab_held``).

Rope is the half-split form of ``ops/basic.py``, over the MLA layers' rope
lanes alone: the delta-rule layers rotate nothing.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ray_tpu.ops import kda, kda_chunk, ssm
from ray_tpu.ops.basic import (
    dense_init, experts_init, rms_norm, rope_freqs, swiglu)
from ray_tpu.ops.mla import mla_attend_expanded, mla_project
from ray_tpu.parallel.moe import moe_layer_chunked
from ray_tpu.utils import tracing

KDA, MLA = "kda", "mla"


@dataclasses.dataclass(frozen=True)
class KdaMoeConfig:
    family = "kda_moe"   # whose programs serve it: ray_tpu.llm.<family>
    vocab_size: int = 157184          # rows of embedding and head held HERE
    d_model: int = 2560
    n_layers: int = 42
    layer_group_size: int = 6         # the last layer of every group is MLA
    n_heads: int = 32
    head_dim: int = 128               # a KDA head's key and value lanes
    conv_kernel: int = 4
    kda_lower_bound: float = -5.0
    chunk_size: int = 64              # the scan's tiling: no result depends
    sub_chunk: int = 16               # on either (ops/kda.py says what does)
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 6144                  # the leading dense layers
    first_dense_layers: int = 2
    n_experts: int = 512              # routed, over all holders
    n_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    d_expert: int = 768
    d_shared: int = 768               # the one shared expert's width
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 131072
    rope_theta: float = 6e6
    dtype: str = "bfloat16"
    # this holder's routed experts of every expert layer, [lo, hi); None = all
    experts_held: tuple[int, int] | None = None
    # which rows of the published vocabulary the vocab_size rows here are
    vocab_held: tuple[int, int] | None = None

    def __post_init__(self):
        if self.n_experts % self.n_group or self.chunk_size % self.sub_chunk:
            raise ValueError("experts do not divide into their groups, or a "
                             "chunk into its sub-blocks")
        if self.sub_chunk * -self.kda_lower_bound > 80:
            raise ValueError(f"sub-blocks of {self.sub_chunk} positions at a "
                             f"lower bound of {self.kda_lower_bound} leave "
                             f"float32 (ops/kda.py)")
        if self.vocab_held and (
                self.vocab_held[1] - self.vocab_held[0] != self.vocab_size):
            raise ValueError(f"vocab_held {self.vocab_held} is not "
                             f"{self.vocab_size} rows")

    @property
    def held(self) -> tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """What a token leaves in an MLA layer's cache: [c, k_rope]."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_width(self) -> int:
        """Lanes the convolution runs over: q, k and v of every head."""
        return 3 * self.d_inner

    def mixer(self, i: int) -> str:
        return MLA if (i + 1) % self.layer_group_size == 0 else KDA

    def layers_of(self, kind: str) -> tuple[int, ...]:
        """The layers of one kind of mixer, in order: a layer's place in
        this list is its index into that kind's pools."""
        return tuple(i for i in range(self.n_layers) if self.mixer(i) == kind)

    def is_moe_layer(self, i: int) -> bool:
        return i >= self.first_dense_layers

    @property
    def n_moe_layers(self) -> int:
        return max(0, self.n_layers - self.first_dense_layers)

    @classmethod
    def tiny(cls, **kw) -> "KdaMoeConfig":
        """Every ratio of the published shape kept: 8 routing groups of which
        4 are chosen, two leading dense layers, two whole periods of the
        pattern (here 2 : 1), the rope part smaller than the nope part, four
        sub-blocks a chunk."""
        base = dict(vocab_size=256, d_model=64, n_layers=6, layer_group_size=3,
                    n_heads=4, head_dim=16, chunk_size=8, sub_chunk=2,
                    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                    v_head_dim=16, d_ff=192, first_dense_layers=2,
                    n_experts=32, n_experts_per_tok=6, n_group=8, topk_group=4,
                    d_expert=24, d_shared=24, max_seq_len=128, dtype="float32")
        return cls(**{**base, **kw})


def kda_moe_layer_init(key, cfg: KdaMoeConfig, i: int) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    D, H, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    k = jax.random.split(key, 16)
    layer: dict = {"attn_norm": {"scale": jnp.ones((D,), dtype)},
                   "ffn_norm": {"scale": jnp.ones((D,), dtype)}}
    if cfg.mixer(i) == KDA:
        layer |= {
            # [q | k | v | a | beta | gate]
            "in_proj": dense_init(k[0], D, cfg.conv_width + cfg.d_inner + 2 * H,
                                  dtype),
            "conv": {"kernel": (jax.random.normal(
                k[1], (cfg.conv_kernel, cfg.conv_width))
                * cfg.conv_kernel ** -0.5).astype(dtype)},
            # so that a step's decay is neither 0 nor 1: exp(A_log) in
            # [0.5, 1.5], the bias in [-7, -2] a lane: g = -5 sigmoid(.) from
            # -0.005 (a lane that remembers 200 positions) to -0.6 (two)
            "A_log": jnp.log(jax.random.uniform(k[2], (H,), jnp.float32,
                                                0.5, 1.5)),
            "a_bias": jax.random.uniform(k[3], (cfg.d_inner,), jnp.float32,
                                         -7.0, -2.0),
            "o_norm": {"scale": jnp.ones((hd,), dtype)},
            "wo": dense_init(k[4], cfg.d_inner, D, dtype),
        }
    else:
        r = cfg.kv_lora_rank
        layer |= {
            "wq": dense_init(k[0], D, H * cfg.qk_head_dim, dtype),
            "wkv_a": dense_init(k[1], D, cfg.latent_width, dtype),
            "kv_norm": {"scale": jnp.ones((r,), dtype)},
            "wkv_b": dense_init(
                k[2], r, H * (cfg.qk_nope_head_dim + cfg.v_head_dim), dtype),
            "wg": dense_init(k[3], D, H, dtype),
            "wo": dense_init(k[4], H * cfg.v_head_dim, D, dtype),
        }
    if not cfg.is_moe_layer(i):
        layer["w_gate"] = dense_init(k[5], D, cfg.d_ff, dtype)
        layer["w_up"] = dense_init(k[6], D, cfg.d_ff, dtype)
        layer["w_down"] = dense_init(k[7], cfg.d_ff, D, dtype)
        return layer
    lo, hi = cfg.held
    F, Fs = cfg.d_expert, cfg.d_shared
    layer["moe"] = {
        "router": {
            "kernel": dense_init(k[5], D, cfg.n_experts, dtype)["kernel"],
            # e_score_correction_bias; non-zero so that choosing by s + b
            # and weighing by s are two things and the groups' sums differ
            "bias": 0.1 * jax.random.normal(k[6], (cfg.n_experts,)),
        },
        # every holder draws all experts' numbers and keeps its own, so the
        # shares of one seed are slices of one model
        "experts": {
            "w_gate": experts_init(k[7], cfg.n_experts, D, F, dtype)[lo:hi],
            "w_up": experts_init(k[8], cfg.n_experts, D, F, dtype)[lo:hi],
            "w_down": experts_init(k[9], cfg.n_experts, F, D, dtype)[lo:hi],
        },
        "shared": {"w_gate": dense_init(k[10], D, Fs, dtype),
                   "w_up": dense_init(k[11], D, Fs, dtype),
                   "w_down": dense_init(k[12], Fs, D, dtype)},
    }
    return layer


def kda_moe_init(key, cfg: KdaMoeConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, cfg.n_layers + 2)
    params: dict = {"tok": {"embedding": jax.random.normal(
        keys[0], (cfg.vocab_size, cfg.d_model)).astype(dtype)}}
    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = kda_moe_layer_init(keys[2 + i], cfg, i)
    params["norm"] = {"scale": jnp.ones((cfg.d_model,), dtype)}
    params["lm_head"] = dense_init(keys[1], cfg.d_model, cfg.vocab_size, dtype)
    return params


# ------------------------------------------------------------------ the halves
def mixer_norm(layer, x, cfg: KdaMoeConfig):
    """The norm in front of a layer's mixer."""
    return rms_norm(x, layer["attn_norm"]["scale"], cfg.rms_norm_eps)


@tracing.part("project")
def kda_in(layer, x, cfg: KdaMoeConfig):
    """The KDA mixer's norm and its one input projection. x: [B, T, D].
    Returns the convolution's input u [B, T, 3 . d_inner] (q | k | v), the
    decay's projection a [B, T, d_inner] and the raw beta and gate, each
    [B, T, heads]."""
    z = mixer_norm(layer, x, cfg) @ layer["in_proj"]["kernel"]
    C, di, H = cfg.conv_width, cfg.d_inner, cfg.n_heads
    return jnp.split(z, (C, C + di, C + di + H), axis=-1)


def conv_taps(layer, cfg: KdaMoeConfig):
    """The convolution's taps and the bias it has not: what
    ``ops/ssm.py``'s convolution takes."""
    return layer["conv"]["kernel"], jnp.zeros((cfg.conv_width,), jnp.float32)


def kda_qkv(xc, cfg: KdaMoeConfig, laid: bool = False):
    """The convolution's output [..., 3 . d_inner] (``laid``: ``by_head`` of
    it) as q, k (L2-normalised a head, q scaled by ``head_dim ** -0.5``;
    float32) and v, each [..., heads, head_dim]."""
    if laid:
        q, k, v = jnp.split(xc, 3, axis=-2)
    else:
        lead = xc.shape[:-1]
        q, k, v = (a.reshape(*lead, cfg.n_heads, cfg.head_dim)
                   for a in jnp.split(xc, 3, axis=-1))
    return (kda.l2_normalise(q) * cfg.head_dim ** -0.5, kda.l2_normalise(k), v)


def kda_decay(layer, a, beta, cfg: KdaMoeConfig, laid: bool = False):
    """(g [..., heads, head_dim], beta [..., heads]) in float32: the log of
    each key lane's decay and the head's write strength. a: [..., d_inner]
    (``laid``: ``by_head`` of it); beta: [..., heads], raw."""
    if laid:
        a = a.astype(jnp.float32) + layer["a_bias"].reshape(a.shape[-2:])
    else:
        a = (a.astype(jnp.float32) + layer["a_bias"]).reshape(
            *a.shape[:-1], cfg.n_heads, cfg.head_dim)
    return (kda.kda_gate(a, layer["A_log"], cfg.kda_lower_bound),
            jax.nn.sigmoid(beta.astype(jnp.float32)))


def head_gate(raw):
    """The head-wise output gate of both mixers: ``sigmoid`` of a scalar a
    head, float32. raw: [..., heads]."""
    return jax.nn.sigmoid(raw.astype(jnp.float32))


def kda_out(layer, o, gate, cfg: KdaMoeConfig, dtype):
    """``sigmoid(gate)_h . N_h(o_h)``: an RMSNorm over each head's lanes with
    one gain, THEN the head's gate. o: [..., heads, head_dim] float32; gate:
    [..., heads], raw. Returns [..., d_inner] in ``dtype``."""
    o = rms_norm(o, layer["o_norm"]["scale"], cfg.rms_norm_eps)
    o = o * head_gate(gate)[..., None]
    return o.reshape(*o.shape[:-2], cfg.d_inner).astype(dtype)


@tracing.part("attn_out")
def mixer_out(layer, y):
    """A mixer's output projection onto the residual."""
    return y @ layer["wo"]["kernel"]


def by_head(x, cfg: KdaMoeConfig):
    """``x`` [..., n . head_dim] as [..., n, head_dim], laid out so HERE,
    while it is narrow: ``ops/kda_chunk.py``'s kernel reads a head's rows of
    [N, T, heads, head_dim] float32, and left free the compiler turns q, k
    and g into that layout after widening them, a float32 pass each."""
    return jax.lax.optimization_barrier(
        x.reshape(*x.shape[:-1], -1, cfg.head_dim))


def kda_mixer(layer, x, cfg: KdaMoeConfig, valid=None, tails=None,
              kernel: bool = False):
    """A whole KDA mixer over sequences from a zero state, the chunked scan.
    x: [N, T, D]; ``valid`` [N, T]: positions that move the state (None:
    all); ``tails`` [N] int32: where to read the convolution's saved inputs
    (the K - 1 before that position); ``kernel``: whether the scan may run
    as ``ops/kda_chunk.py``'s kernel (the caller's platform rule; it does
    where the shapes are the kernel's too, else ``ops/kda.py``'s plain
    form). Returns (y [N, T, D], the state after the last valid position
    [N, heads, head_dim, head_dim] float32, the saved inputs [N, K - 1, 3 .
    d_inner] or None)."""
    u, a, beta, gate = kda_in(layer, x, cfg)
    with tracing.part("conv"):
        xc = ssm.causal_conv(u, *conv_taps(layer, cfg))
        if tails is None:
            saved = None
        else:
            # read out beside the convolution and not whenever the scheduler
            # likes: left free, it kept every layer's u (384 MB at 4 x 4,096)
            # until the program's end for three rows of it
            xc, saved = jax.lax.optimization_barrier(
                (xc, ssm.conv_tail(u, tails, cfg.conv_kernel)))
    with tracing.part("delta"):
        laid = kernel and kda_chunk.fits(cfg.head_dim, cfg.head_dim,
                                         cfg.chunk_size, cfg.sub_chunk)
        scan = kda_chunk.kda_chunk_scan if laid else kda.kda_chunked
        if laid:
            xc, a = by_head(xc, cfg), by_head(a, cfg)
        q, k, v = kda_qkv(xc, cfg, laid)
        g, beta = kda_decay(layer, a, beta, cfg, laid)
        if valid is not None:  # padding decays nothing and writes nothing
            g = jnp.where(valid[..., None, None], g, 0.0)
            beta = jnp.where(valid[..., None], beta, 0.0)
        o, S = scan(q, k, v, g, beta, cfg.chunk_size, cfg.sub_chunk)
        y = kda_out(layer, o, gate, cfg, x.dtype)
    return mixer_out(layer, y), S, saved


@tracing.part("project")
def mla_in(layer, x, cos, sin, positions, cfg: KdaMoeConfig):
    """The MLA mixer's norm and projections: queries, the cache row and the
    head-wise gate [B, T, heads] (after its sigmoid)."""
    h = mixer_norm(layer, x, cfg)
    q, latent = mla_project(layer, h, cos, sin, positions, cfg)
    return q, latent, head_gate(h @ layer["wg"]["kernel"])


@tracing.part("ffn")
def ffn_norm(layer, x, cfg: KdaMoeConfig):
    return rms_norm(x, layer["ffn_norm"]["scale"], cfg.rms_norm_eps)


@tracing.part("ffn")
def dense_ffn(layer, h):
    return swiglu(h, layer["w_gate"]["kernel"], layer["w_up"]["kernel"],
                  layer["w_down"]["kernel"])


def route_kw(cfg: KdaMoeConfig) -> dict:
    """What ``parallel/moe.py``'s router takes of the configuration."""
    return dict(k=cfg.n_experts_per_tok, scale=cfg.routed_scaling_factor,
                norm=cfg.norm_topk_prob, n_group=cfg.n_group,
                topk_group=cfg.topk_group)


def kda_moe_ffn(layer, x, cfg: KdaMoeConfig, valid=None):
    """The layer's second half on the residual ``x`` [B, T, D], a long
    prompt's tokens a chunk at a time. Returns (x, load): ``load`` [held
    experts] is None for a dense layer."""
    h = ffn_norm(layer, x, cfg)
    if "moe" not in layer:
        return x + dense_ffn(layer, h), None
    with tracing.part("ffn"):
        y, load = moe_layer_chunked(h, layer["moe"], valid, held=cfg.held,
                                    **route_kw(cfg))
    return x + y, load


@tracing.part("head")
def kda_moe_logits(params, x, cfg: KdaMoeConfig):
    """The untied head over the held rows. x: [..., D]."""
    x = rms_norm(x, params["norm"]["scale"], cfg.rms_norm_eps)
    return x @ params["lm_head"]["kernel"]


def kda_moe_forward(params, tokens, cfg: KdaMoeConfig):
    """tokens: [B, T] int32 -> logits [B, T, held rows]: the whole model
    with no cache (the chunked scan, expanded attention, causal)."""
    B, T = tokens.shape
    cos, sin = rope_freqs(cfg.qk_rope_head_dim, cfg.max_seq_len, cfg.rope_theta)
    idx = jnp.arange(T)
    positions = jnp.broadcast_to(idx[None, :], (B, T))
    mask = jnp.broadcast_to(idx[None, :, None] >= idx[None, None, :], (B, T, T))
    x = params["tok"]["embedding"][tokens]
    for i in range(cfg.n_layers):
        layer = params[f"layers_{i}"]
        if cfg.mixer(i) == KDA:
            y, _, _ = kda_mixer(layer, x, cfg)
        else:
            q, latent, gate = mla_in(layer, x, cos, sin, positions, cfg)
            y = mixer_out(layer, mla_attend_expanded(
                layer, q, latent, mask, cfg, gate))
        x, _ = kda_moe_ffn(layer, x + y, cfg)
    return kda_moe_logits(params, x, cfg)
