"""Plain reference of Ling-3.0-flash's language model as its ``config.json``
names its mechanisms and Kimi Delta Attention (arXiv:2510.26692), DeepSeek-V2's
MLA and DeepSeek-V3's ``noaux_tc`` group-limited routing describe them, written
from the layers' equations and not from the program. Straightforward
``jax.numpy`` in float32 at ``highest`` matmul precision: no cache, no kernels,
no batching, no grouped product (every held expert is applied to every token
and the unchosen ones are weighed by zero) and **no chunking: the delta rule
runs one position at a time** (``lax.scan`` over positions), so it shares
nothing with the program's chunked form. MLA is computed expanded over the
whole sequence, a block of queries at a time so that 4k positions fit; weights
come from (seed, layer) alone (``lib/weights_kda_moe.py``).

Every layer is ``x <- x + Mixer_i(N(x))``, ``x <- x + FFN_i(N(x))``, ``N(x) =
x / sqrt(mean x^2 + eps) . g``; the mixer is MLA where ``(i + 1) % group == 0``:

  KDA  [q~ | k~ | v~ | a | b | z] = h.W_in;  (q~, k~, v~)_t <- silu(sum_j w_j .
       u_{t-3+j}) (zeros before 0), heads x 128 each;
       q = q~ / |q~| . 128^-1/2, k = k~ / |k~| (a head);
       g = lower_bound . sigmoid(exp(A_log_h) . (a + bias)), alpha = exp(g) a
       LANE of a head; beta = sigmoid(b) a head;
       S' = Diag(alpha_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;
       o_t = S_t^T q_t;  out = W_o concat_h(sigmoid(z)_h . N_h(o_h))
  MLA  q = h.Wq -> [H, nope + rope];  [c~, k_R] = h.Wkva;  c = N(c~);
       rope over q's rope lanes and the ONE k_R;  [k_nope, v] = c.Wkvb a head;
       causal softmax of (q_nope.k_nope + q_R.k_R) / sqrt(nope + rope);
       out = W_o concat_h(sigmoid(h.Wg)_h . o_h)
  FFN  layers < first_dense: SwiGLU. Else s = sigmoid(h.Wr), s' = s + b; the
       experts are n_group groups of consecutive ids, a group's score the sum
       of its two largest s', the topk_group groups of largest score stay
       (ties to the lower group), the k largest s' among THEIR experts are
       chosen; weights s (never s') of the chosen, normalised, x scale;
       out = sum over the HELD chosen e of w_e . swiglu_e(h) + swiglu_shared(h)
  head: logits = N(x) . W_head over the held rows.

``mode`` puts the reference in the program's place at a lower precision, as
the control of ``correct`` ("bfloat16" / "fp8" round every matmul input; the
state stays float32). ``variant`` changes the mathematics, for the controls
that must FAIL the comparison: ``decay`` ("none": alpha = 1; "head": one decay
a head, the mean of its lanes' g), ``delta`` (False: no ``k k^T S`` term, ``S =
Diag(alpha) S + beta k v^T``), ``beta`` ("one"), ``qknorm`` (False: q and k as
the convolution leaves them, q still scaled), ``silu`` (False: none after the
convolution), ``gate`` ("before": the gate first, then the norm), ``state``
("bfloat16": the state rounded to bf16 after every position), ``pad`` (n: the
sequence's first ``pad_from`` positions are followed by n - pad_from positions
of token 0 that advance state and convolution before the rest — what a
prefill that ran on past a prompt's true length to its pad would leave),
``groups`` ("none": the k largest of all experts, no group chosen; "max": a
group's score its largest s' alone), ``bias`` ("weights": the chosen are
weighed by s + b), ``rope`` (False: no rotation in the MLA layers)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.lib import weights_kda_moe as W
from benchmarks.reference.dense_gqa import _HI, _f32, _mm, _rope, _round
from benchmarks.reference.mla_moe import _swiglu, experts_sum
from ray_tpu.models.kda_moe import KDA


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda(w, h, cfg, mode: str, var: dict, state_at: tuple):
    """h: [T, D] (normed) -> (out [T, D], states [len(state_at), H, dk, dv]
    after those positions, conv inputs [len(state_at), K - 1, C] saved after
    them)."""
    T = h.shape[0]
    H, hd, K, C, di = (cfg.n_heads, cfg.head_dim, cfg.conv_kernel,
                       cfg.conv_width, cfg.d_inner)
    z = _mm(h, w["in_proj"]["kernel"], mode)
    u, a, b, gate = (z[:, :C], z[:, C:C + di], z[:, C + di:C + di + H],
                     z[:, C + di + H:])
    padded = jnp.concatenate([jnp.zeros((K - 1, C), jnp.float32), u])
    conv = sum(w["conv"]["kernel"][j] * padded[j:j + T] for j in range(K))
    if var.get("silu", True):
        conv = jax.nn.silu(conv)
    q, k, v = (conv[:, i * di:(i + 1) * di].reshape(T, H, hd) for i in range(3))
    if var.get("qknorm", True):
        q, k = _unit(q), _unit(k)
    q = q * hd ** -0.5
    g = cfg.kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(w["A_log"])[:, None] * (a + w["a_bias"]).reshape(T, H, hd))
    if var.get("decay") == "none":
        g = jnp.zeros_like(g)
    elif var.get("decay") == "head":
        g = jnp.broadcast_to(g.mean(axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(b)
    if var.get("beta") == "one":
        beta = jnp.ones_like(beta)
    low_state, delta = var.get("state") == "bfloat16", var.get("delta", True)

    def step(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = jnp.exp(g_t)[:, :, None] * S
        held = jnp.einsum("hkv,hk->hv", S, k_t, precision=_HI) if delta else 0.0
        S = S + b_t[:, None, None] * k_t[:, :, None] * (v_t - held)[:, None, :]
        if low_state:
            S = _round(S, "bfloat16")
        return S, jnp.einsum("hkv,hk->hv", S, q_t, precision=_HI)

    # one scan a stretch between the positions whose state is asked for:
    # keeping every position's state would be T x 2 MB a layer at the real size
    S, os_, at, lo = jnp.zeros((H, hd, hd), jnp.float32), [], {}, 0
    for hi in sorted({*state_at, T}):
        if hi > lo:
            S, o = jax.lax.scan(step, S, (q[lo:hi], k[lo:hi], v[lo:hi],
                                          g[lo:hi], beta[lo:hi]))
            os_.append(o)
        at[hi], lo = S, hi
    states = jnp.stack([at[n] for n in state_at]) if state_at else None
    saved = (jnp.stack([padded[n:n + K - 1] for n in state_at])
             if state_at else None)
    o = jnp.concatenate(os_)                                   # [T, H, hd]
    gain, zg = w["o_norm"]["scale"], jax.nn.sigmoid(gate)[:, :, None]
    if var.get("gate") == "before":
        o = _rms(o * zg, gain, cfg.rms_norm_eps)
    else:
        o = _rms(o, gain, cfg.rms_norm_eps) * zg
    return _mm(o.reshape(T, di), w["wo"]["kernel"], mode), states, saved


def mla(w, h, cfg, mode: str, var: dict, q_block: int):
    """h: [T, D] (normed) -> (out [T, D], rows [T, r + rope]: [c, k_R] as a
    cache would hold them)."""
    T = h.shape[0]
    H, r, n, dv = (cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                   cfg.v_head_dim)
    q = _mm(h, w["wq"]["kernel"], mode).reshape(T, H, cfg.qk_head_dim)
    a = _mm(h, w["wkv_a"]["kernel"], mode)
    c = _rms(a[:, :r], w["kv_norm"]["scale"], 1e-6)
    q_nope, q_rope, k_rope = q[..., :n], q[..., n:], a[:, None, r:]
    if var.get("rope", True):
        q_rope = _rope(q_rope[None], cfg.rope_theta)[0]
        k_rope = _rope(k_rope[None], cfg.rope_theta)[0]
    k_rope = k_rope[:, 0]
    kv = _mm(c, w["wkv_b"]["kernel"], mode).reshape(T, H, n + dv)
    k_nope, val = _round(kv[..., :n], mode), _round(kv[..., n:], mode)
    blk = min(q_block, T)
    pad = -T % blk
    qn = jnp.pad(q_nope, ((0, pad), (0, 0), (0, 0))).reshape(-1, blk, H, n)
    qr = jnp.pad(q_rope, ((0, pad), (0, 0), (0, 0))).reshape(-1, blk, H, q_rope.shape[-1])
    kr, cols = _round(k_rope, mode), jnp.arange(T)[None, :]

    def block(args):
        a_n, a_r, first = args
        ok = cols <= first + jnp.arange(blk)[:, None]
        s = (jnp.einsum("qhd,thd->hqt", _round(a_n, mode), k_nope, precision=_HI)
             + jnp.einsum("qhd,td->hqt", _round(a_r, mode), kr, precision=_HI)
             ) / jnp.sqrt(jnp.float32(cfg.qk_head_dim))
        p = jax.nn.softmax(jnp.where(ok[None], s, -1e30), axis=-1)
        return jnp.einsum("hqt,thd->qhd", _round(p, mode), val, precision=_HI)

    o = jax.lax.map(block, (qn, qr, jnp.arange(qn.shape[0]) * blk))
    o = o.reshape(-1, H, dv)[:T]
    o = o * jax.nn.sigmoid(_mm(h, w["wg"]["kernel"], mode))[:, :, None]
    return (_mm(o.reshape(T, H * dv), w["wo"]["kernel"], mode),
            jnp.concatenate([c, k_rope], axis=-1))


def _largest(a, k: int):
    """k rounds of "the largest left" over the last axis: the first of equals
    is the lower index. Returns [..., k] indices."""
    out = []
    for _ in range(k):
        e = jnp.argmax(a, axis=-1)
        out.append(e)
        a = jnp.where(jnp.arange(a.shape[-1]) == e[..., None], -jnp.inf, a)
    return jnp.stack(out, axis=-1)


def route(h, router, cfg, mode: str, var: dict | None = None):
    """h: [T, D] -> (chosen [T, k], combine [T, E]: each token's weight for
    each expert, zero for the unchosen)."""
    var = var or {}
    T, E, G = h.shape[0], cfg.n_experts, cfg.n_group
    s = jax.nn.sigmoid(_mm(h, router["kernel"], mode))
    biased = s + router["bias"]
    if G > 1 and var.get("groups") != "none":
        groups = biased.reshape(T, G, E // G)
        best = jnp.sort(groups, axis=-1)
        score = best[..., -1] if var.get("groups") == "max" else (
            best[..., -1] + best[..., -2])
        stay = _largest(score, cfg.topk_group)                # [T, topk_group]
        kept = (stay[:, :, None] == jnp.arange(G)).any(axis=1)  # [T, G]
        biased = jnp.where(jnp.repeat(kept, E // G, axis=1), biased, -jnp.inf)
    chosen = _largest(biased, cfg.n_experts_per_tok)
    picked = jnp.zeros_like(s).at[jnp.arange(T)[:, None], chosen].set(1.0)
    w = (s + router["bias"] if var.get("bias") == "weights" else s) * picked
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * cfg.routed_scaling_factor


def moe(w, h, cfg, mode: str, held=None, var: dict | None = None,
        shared: bool = True, expert_block: int = 16):
    """The expert layer on h [T, D] (normed). ``held`` = (lo, hi) gives one
    holder's routed part (``w["experts"]`` then holds those experts alone);
    ``shared`` False leaves the shared expert out (for adding holders' parts
    up). Returns (y, chosen)."""
    chosen, combine = route(h, w["router"], cfg, mode, var)
    y = experts_sum(h, combine, w["experts"], held or cfg.held, mode,
                    expert_block)
    if shared:
        sh = w["shared"]
        y = y + _swiglu(h, sh["w_gate"]["kernel"], sh["w_up"]["kernel"],
                        sh["w_down"]["kernel"], mode)
    return y, chosen


def _f32_but_experts(w):
    if "moe" not in w:
        return _f32(w)
    moe_w = w["moe"]
    out = _f32({k: v for k, v in w.items() if k != "moe"})
    out["moe"] = {**_f32({k: v for k, v in moe_w.items() if k != "experts"}),
                  "experts": moe_w["experts"]}
    return out


@partial(jax.jit, static_argnames=("cfg", "kind", "mode", "variant",
                                   "state_at", "q_block"))
def _layer_jit(w, x, cfg, kind, mode, variant, state_at, q_block):
    """One layer. x: [T, D] float32 -> (x, what the mixer leaves for a cache:
    (states, conv inputs) or (rows,), the expert layer's choices or None)."""
    w, var = _f32_but_experts(w), dict(variant)
    h = _rms(x, w["attn_norm"]["scale"], cfg.rms_norm_eps)
    if kind == KDA:
        y, *left = kda(w, h, cfg, mode, var, state_at)
    else:
        y, *left = mla(w, h, cfg, mode, var, q_block)
    x = x + y
    h = _rms(x, w["ffn_norm"]["scale"], cfg.rms_norm_eps)
    if "moe" not in w:
        return x + _swiglu(h, w["w_gate"]["kernel"], w["w_up"]["kernel"],
                           w["w_down"]["kernel"], mode), tuple(left), None
    y, chosen = moe(w["moe"], h, cfg, mode, var=var)
    return x + y, tuple(left), chosen


@partial(jax.jit, static_argnames=("cfg", "mode"))
def _logits_jit(head, x, cfg, mode):
    x = _rms(x, jnp.ones((cfg.d_model,), jnp.float32), cfg.rms_norm_eps)
    return _mm(x, head.astype(jnp.float32), mode)


def forward(seed: int, cfg, tokens, *, mode: str = "float32",
            variant: dict | None = None, logits_from: int = 0,
            state_at: tuple = (), q_block: int = 128) -> dict:
    """Full forward pass over ``tokens`` [T] (ids over the held slice of the
    vocabulary): ``logits`` [T - logits_from, held rows] of the positions
    from ``logits_from`` on; every MLA layer's cache rows ``rows`` [MLA
    layers, T, r + rope]; every KDA layer's state and saved convolution
    inputs after ``n`` positions, for each ``n`` of ``state_at``: ``state``
    [KDA layers, len(state_at), heads, dk, dv], ``conv`` [.., len(state_at),
    K - 1, C]; every expert layer's choices ``chosen`` [expert layers, T, k]."""
    variant = dict(variant or {})
    pad = variant.pop("pad", None)
    tokens = [int(t) for t in tokens]
    if pad:  # pad positions of token 0 after the first pad_from true ones
        n = variant.pop("pad_from")
        tokens = tokens[:n] + [0] * (pad - n) + tokens[n:]
        # a state asked for at the prompt's end is read where the pad ends
        state_at = tuple(s if s < n else s + pad - n for s in state_at)
    key = W.seed_key(seed)
    x = W.embedding(key, cfg).astype(jnp.float32)[jnp.asarray(tokens, jnp.int32)]
    frozen = tuple(sorted(variant.items()))
    out = {"state": [], "conv": [], "rows": [], "chosen": []}
    for i in range(cfg.n_layers):
        kind = cfg.mixer(i)
        x, left, chosen = _layer_jit(W.layer_from_seed(key, cfg, i), x, cfg,
                                     kind, mode, frozen, tuple(state_at),
                                     q_block)
        for name, a in zip(("state", "conv") if kind == KDA else ("rows",), left):
            out[name].append(a)
        if chosen is not None:
            out["chosen"].append(chosen)
    if pad:  # the rows a cache would hold: the pad positions' taken out
        keep = jnp.asarray([t for t in range(len(tokens)) if not n <= t < pad])
        x = x[keep]
        out["rows"] = [a[keep] for a in out["rows"]]
    res = {name: jnp.stack(a) for name, a in out.items()
           if a and a[0] is not None}
    res["logits"] = _logits_jit(W.head(key, cfg)["kernel"], x[logits_from:],
                                cfg, mode)
    return res
