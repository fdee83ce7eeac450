"""Plain reference of the ``nemotron_h`` shape as NVIDIA-Nemotron-3-Nano-30B-
A3B's ``config.json`` gives it, written from the blocks' equations and not
from the program. Straightforward ``jax.numpy`` in float32 at ``highest``
matmul precision: no cache, no kernels, no batching, no grouped product
(every held expert is applied to every token and the unchosen ones are
weighed by zero) and **no chunked scan: the recurrence runs one position at a
time** (``lax.scan`` over positions), so it shares nothing with the program's
chunking. Attention is computed a block of queries at a time under a plain
causal mask so that 2k positions fit; weights come from (seed, block) alone
(``lib/weights_ssm_moe.py``).

Every block is ``x <- x + Mixer_i(RMS(x))``, ``RMS(x) = x / sqrt(mean x^2 +
eps) . g``, the mixer by the pattern's character:

  M  [z | u | dt] = h.W_in;  u_t <- silu(b + sum_j w_j . u_{t-3+j}) (zeros
     before 0);  u_t = [x_t (heads x P) | B_t | C_t (groups x N)];
     dt_t = softplus(dt_t + dt_bias), A = -exp(A_log);
     S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t;
     y <- RMS_group(y . silu(z)) . g;  out = y.W_out
  *  q, k, v = h.Wq, h.Wk, h.Wv (no rotation); causal softmax over
     sqrt(hd), query head i on KV head i // (H / KV);  out = o.Wo
  E  s = sigmoid(h.Wr); the k largest s + b; weights s of the chosen,
     normalised, x scale;  out = sum over the HELD chosen e of
     w_e . Wd_e relu(Wu_e h)^2  +  Wd_s relu(Wu_s h)^2
  head: logits = RMS(x) . W_head over the held rows.

``mode`` puts the reference in the program's place at a lower precision, as
the control of ``correct`` ("bfloat16" rounds every matmul input; the state
stays float32). ``variant`` changes the mathematics, for the controls that
must FAIL the comparison: ``state`` ("bfloat16": the state is rounded to
bf16 after every position), ``gate`` ("after": the norm first, then the
gate), ``skip`` (False: no ``D . x``), ``pad`` (n: the sequence's first
``pad_from`` positions are followed by n - pad_from positions of token 0 that
advance state and convolution before the rest — what a prefill that ran on
past a prompt's true length to its pad would leave), ``rope`` (True: q and k
rotated half-split at ``rope_theta`` 10,000), ``act`` ("relu": no square;
"swiglu": a gate — the up matrix's own columns reversed — times up)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.lib import weights_ssm_moe as W
from benchmarks.reference.dense_gqa import _HI, _f32, _mm, _rope, _round
from ray_tpu.models.ssm_moe import ATTENTION, MAMBA


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def mamba(w, h, cfg, mode: str, var: dict, state_at: tuple):
    """h: [T, D] (normed) -> (out [T, D], states [len(state_at), H, P, N]
    after those positions, conv inputs [len(state_at), K - 1, C] saved
    after them)."""
    T = h.shape[0]
    Hm, P, G, N, K = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.n_groups,
                      cfg.ssm_state, cfg.conv_kernel)
    di, C = cfg.d_inner, cfg.conv_width
    zudt = _mm(h, w["in_proj"]["kernel"], mode)
    z, u, dt = zudt[:, :di], zudt[:, di:di + C], zudt[:, di + C:]
    padded = jnp.concatenate([jnp.zeros((K - 1, C), jnp.float32), u])
    conv = w["conv"]["bias"] + sum(
        w["conv"]["kernel"][j] * padded[j:j + T] for j in range(K))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :di].reshape(T, Hm, P)
    Bm = jnp.repeat(xbc[:, di:di + G * N].reshape(T, G, N), Hm // G, axis=1)
    Cm = jnp.repeat(xbc[:, di + G * N:].reshape(T, G, N), Hm // G, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    A = -jnp.exp(w["A_log"])
    low_state = var.get("state") == "bfloat16"

    def step(S, t):
        x_t, B_t, C_t, dt_t = t
        S = jnp.exp(dt_t * A)[:, None, None] * S + (
            (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        if low_state:
            S = _round(S, "bfloat16")
        y = jnp.einsum("hpn,hn->hp", S, C_t, precision=_HI)
        if var.get("skip", True):
            y = y + w["D"][:, None] * x_t
        return S, y

    # one scan a stretch between the positions whose state is asked for:
    # keeping every position's state would be T x 2 MB a block at the real size
    S, ys, at, lo = jnp.zeros((Hm, P, N), jnp.float32), [], {}, 0
    for hi in sorted({*state_at, T}):
        if hi > lo:
            S, y = jax.lax.scan(step, S, (x[lo:hi], Bm[lo:hi], Cm[lo:hi],
                                          dt[lo:hi]))
            ys.append(y)
        at[hi], lo = S, hi
    states = jnp.stack([at[n] for n in state_at]) if state_at else None
    saved = (jnp.stack([padded[n:n + K - 1] for n in state_at])
             if state_at else None)
    y = jnp.concatenate(ys).reshape(T, di)
    zg = jax.nn.silu(z)
    gain = w["gate_norm"]["scale"]

    def groups(a):
        a = a.reshape(T, G, -1)
        return (a / jnp.sqrt(jnp.mean(a * a, axis=-1, keepdims=True)
                             + cfg.rms_norm_eps)).reshape(T, di)

    y = groups(y) * gain * zg if var.get("gate") == "after" else (
        groups(y * zg) * gain)
    return _mm(y, w["out_proj"]["kernel"], mode), states, saved


def attention(w, h, cfg, mode: str, var: dict, q_block: int):
    """h: [T, D] (normed) -> (out [T, D], k [T, KV * hd], v [T, KV * hd])."""
    T = h.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _mm(h, w["wq"]["kernel"], mode).reshape(T, H, hd)
    k = _mm(h, w["wk"]["kernel"], mode).reshape(T, KV, hd)
    v = _mm(h, w["wv"]["kernel"], mode).reshape(T, KV, hd)
    if var.get("rope"):
        q, k = _rope(q[None], 10000.0)[0], _rope(k[None], 10000.0)[0]
    blk = min(q_block, T)
    pad = -T % blk
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, blk, KV, H // KV, hd)
    kr, vr, cols = _round(k, mode), _round(v, mode), jnp.arange(T)[None, :]

    def block(args):
        qs, first = args
        ok = cols <= first + jnp.arange(blk)[:, None]
        s = jnp.einsum("qkgd,tkd->kgqt", _round(qs, mode), kr,
                       precision=_HI) / jnp.sqrt(jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(ok[None, None], s, -1e30), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", _round(p, mode), vr, precision=_HI)

    o = jax.lax.map(block, (qb, jnp.arange(qb.shape[0]) * blk))
    o = o.reshape(-1, H * hd)[:T]
    return (_mm(o, w["wo"]["kernel"], mode), k.reshape(T, KV * hd),
            v.reshape(T, KV * hd))


def route(h, router, cfg, mode: str):
    """h: [T, D] -> (chosen [T, k], combine [T, E]: each token's weight for
    each expert, zero for the unchosen)."""
    s = jax.nn.sigmoid(_mm(h, router["kernel"], mode))
    biased, chosen = s + router["bias"], []
    for _ in range(cfg.n_experts_per_tok):   # k rounds of "the largest left"
        e = jnp.argmax(biased, axis=-1)      # first of equals: the lower index
        chosen.append(e)
        biased = biased.at[jnp.arange(h.shape[0]), e].set(-jnp.inf)
    chosen = jnp.stack(chosen, axis=-1)
    picked = jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None], chosen].set(1.0)
    w = s * picked
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen, w * cfg.routed_scaling_factor


def _expert(h, w_up, w_down, mode: str, act: str):
    up = _mm(h, w_up, mode)
    if act == "swiglu":  # a gate the model has not: up's columns reversed
        hid = jax.nn.silu(_mm(h, w_up[:, ::-1], mode)) * up
    else:
        hid = jax.nn.relu(up)
        hid = hid if act == "relu" else hid * hid
    return _mm(hid, w_down, mode)


def moe(w, h, cfg, mode: str, held=None, var: dict | None = None,
        shared: bool = True):
    """The expert block on h [T, D] (normed). ``held`` = (lo, hi) gives one
    holder's routed part (``w["experts"]`` then holds those experts alone);
    ``shared`` False leaves the shared expert out (for adding holders' parts
    up). Returns (y, chosen)."""
    act = (var or {}).get("act", "relu2")
    chosen, combine = route(h, w["router"], cfg, mode)
    lo, hi = held or cfg.held

    def one(acc, xs):
        wu, wd, cw = xs
        y = _expert(h, wu.astype(jnp.float32), wd.astype(jnp.float32), mode, act)
        return acc + y * cw[:, None], None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        w["experts"]["w_up"], w["experts"]["w_down"], combine[:, lo:hi].T))
    if shared:
        y = y + _expert(h, w["shared"]["w_up"]["kernel"],
                        w["shared"]["w_down"]["kernel"], mode, act)
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
def _block_jit(w, x, cfg, kind, mode, variant, state_at, q_block):
    """One block. x: [T, D] float32 -> (x, what the block leaves for a
    cache: (states, conv inputs), (k, v) or (chosen,))."""
    w, var = _f32_but_experts(w), dict(variant)
    h = _rms(x, w["norm"]["scale"], cfg.rms_norm_eps)
    if kind == MAMBA:
        y, *left = mamba(w, h, cfg, mode, var, state_at)
    elif kind == ATTENTION:
        y, *left = attention(w, h, cfg, mode, var, q_block)
    else:
        y, *left = moe(w["moe"], h, cfg, mode, var=var)
    return x + y, tuple(left)


@partial(jax.jit, static_argnames=("cfg", "mode"))
def _logits_jit(head, x, cfg, mode):
    x = _rms(x, jnp.ones((cfg.d_model,), jnp.float32), cfg.rms_norm_eps)
    return _mm(x, head.astype(jnp.float32), mode)


def forward(seed: int, cfg, tokens, *, mode: str = "float32",
            variant: dict | None = None, logits_from: int = 0,
            state_at: tuple = (), q_block: int = 128) -> dict:
    """Full forward pass over ``tokens`` [T] (ids over the held slice of the
    vocabulary): ``logits`` [T - logits_from, held rows] of the positions
    from ``logits_from`` on; every attention block's keys and values as it
    reads them ``k``, ``v`` [attention blocks, T, KV * hd]; every Mamba-2
    block's state and saved convolution inputs after ``n`` positions, for
    each ``n`` of ``state_at``: ``state`` [Mamba-2 blocks, len(state_at),
    heads, P, N], ``conv`` [.., len(state_at), K - 1, C]; every expert
    block's choices ``chosen`` [expert blocks, T, k]."""
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
    out = {"state": [], "conv": [], "k": [], "v": [], "chosen": []}
    for i, kind in enumerate(cfg.pattern):
        x, left = _block_jit(W.layer_from_seed(key, cfg, i), x, cfg, kind, mode,
                             frozen, tuple(state_at), q_block)
        names = {MAMBA: ("state", "conv"), ATTENTION: ("k", "v")}.get(
            kind, ("chosen",))
        for name, a in zip(names, left):
            out[name].append(a)
    if pad:  # the rows a cache would hold: the pad positions' taken out
        keep = jnp.asarray([t for t in range(len(tokens))
                            if not n <= t < pad])
        x = x[keep]
        out["k"] = [a[keep] for a in out["k"]]
        out["v"] = [a[keep] for a in out["v"]]
    res = {name: jnp.stack(a) for name, a in out.items()
           if a and a[0] is not None}
    res["logits"] = _logits_jit(W.head(key, cfg)["kernel"], x[logits_from:],
                                cfg, mode)
    return res
