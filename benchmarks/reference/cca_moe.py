"""Plain reference of ZAYA1's language model as its ``config.json`` names its
parts and Compressed Convolutional Attention (arXiv:2510.04476) and the ZAYA1
report (arXiv:2511.17127) describe them, written from the layer's equations
and not from the program. Straightforward ``jax.numpy`` in float32 at
``highest`` matmul precision: no cache, no kernels, no batching, no grouped
product (every held expert is applied to every token and the unchosen ones
are weighed by zero), the convolutions as shifted sums over the whole
sequence, attention as ONE masked softmax, the router's carry through a
Python loop over layers; weights come from (seed, layer) alone
(``lib/weights_cca_moe.py``).

A layer, ``N(x) = x / sqrt(mean x^2 + eps) . g``:

  x <- a1 . x + b1 . CCA(N(x));   x <- a2 . x + b2 . MoE(N(x), r)

  CCA  [q~ | k~ | v1 | v2] = h.W_in;  q~ [H, hd], k~ [KV, hd], G = H / KV
       m_q[j] = (q~[j] + k~[j // G]) / 2;  m_k[i] = mean_{j // G = i} m_q[j]
       u = [q~ ; k~];  c0_t = w0[1].u_t + w0[0].u_{t-1} + b0 (a lane)
       c1_t[head] = c0_t[head].M[1][head] + c0_{t-1}[head].M[0][head] + b1
       q = c1[q] + m_q;  k = c1[k] + m_k
       q <- q / |q| . sqrt(hd);  k <- k / |k| . sqrt(hd) . tau_i
       rope over the first rotary_dim lanes of every head of q and k
       v_t = [v1_t ; v2_{t-1}]  (zeros before position 0 everywhere)
       o = softmax(q k^T / sqrt(hd), causal) v;  CCA = concat(o).W_o
       a cache holds k (as attended) and v; the ROW after position t is
       [u_t | c0_t | v2_t]
  MoE  r_l = h.W_down + gamma_l . r_{l-1}  (r_{-1} = 0)
       s = gelu(gelu(N_r(r_l).W1 + c1).W2 + c2).W3;  p = softmax(s)
       e* = argmax(p + bias), ties to the lower index
       MoE = p_{e*} . swiglu_{e*}(h)
  head: logits = N(x) . E^T over every row of the tied table.

``mode`` puts the reference in the program's place at a lower precision, as
the control of ``correct`` ("bfloat16" / "fp8" round every matmul input; the
router stays float32 under "bfloat16", as the program's does, and is rounded
under "fp8"). ``variant`` changes the mathematics, for the controls that must
FAIL the comparison: ``conv`` ("none": ``q = q~ + m_q``; "depthwise": the
first convolution alone; "depthwise2": the second depthwise too, its taps the
diagonals of ``M``), ``mean`` ("none"; "first": a key head takes its FIRST
query head's ``m_q``, no group average), ``vshift`` ("none"; "head0": the
first half is the late one), ``qknorm`` (False), ``temp`` ("one": tau = 1;
"after": ``k`` is cached BEFORE its temperature, which the attention then
applies — the logits are the published model's and only the cache differs),
``rope`` ("whole": every lane of a head rotated), ``theta`` (another base),
``pad`` (n: the sequence's first ``pad_from`` positions are followed by n -
pad_from positions of token 0 that advance the row before the rest — what a
prefill that ran on past a prompt's true length would leave), ``carry``
(False: gamma = 0), ``router_in`` ("x": the router reads the residual, not
its norm), ``router`` ("bfloat16": the router's products rounded), ``bias``
("weights": the chosen is weighed by ``p + bias``), ``weight`` ("one": top-1
weighs 1), ``gains`` ("one": residual gains 1)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.lib import weights_cca_moe as W
from benchmarks.reference.dense_gqa import _HI, _f32, _mm, _rope, _round
from benchmarks.reference.mla_moe import experts_sum


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _before(a):
    """Each position's predecessor along axis 0, zeros before position 0."""
    return jnp.concatenate([jnp.zeros_like(a[:1]), a[:-1]])


def _rope_lanes(x, lanes: int, theta: float):
    """x: [T, heads, hd]; the first ``lanes`` lanes of every head rotated."""
    return jnp.concatenate([_rope(x[None, ..., :lanes], theta)[0],
                            x[..., lanes:]], axis=-1)


def cca(w, h, cfg, mode: str, var: dict, row_at: tuple):
    """h: [T, D] (normed) -> (out [T, H . hd] before W_o's gains, k [T, KV .
    hd] as a cache holds it, v [T, KV . hd], rows [len(row_at), row width]:
    what the positions ``row_at`` leave)."""
    T = h.shape[0]
    H, KV, hd, C, half = (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                          cfg.conv_width, cfg.v_half)
    G = H // KV
    z = _mm(h, w["w_in"]["kernel"], mode)
    u, v1, v2 = z[:, :C], z[:, C:C + half], z[:, C + half:]
    latent = u.reshape(T, H + KV, hd)
    qt, kt = latent[:, :H], latent[:, H:]
    m_q = (qt + jnp.repeat(kt, G, axis=1)) / 2
    m_k = (m_q[:, ::G] if var.get("mean") == "first"
           else m_q.reshape(T, KV, G, hd).mean(axis=2))
    if var.get("mean") == "none":
        m_q, m_k = jnp.zeros_like(m_q), jnp.zeros_like(m_k)
    w0, M = w["conv0"]["kernel"], w["conv1"]["kernel"]
    c0 = w0[1] * u + w0[0] * _before(u) + w["conv0"]["bias"]
    c0h, b1 = c0.reshape(T, H + KV, hd), w["conv1"]["bias"].reshape(H + KV, hd)
    conv = var.get("conv")
    if conv == "none":
        c1 = latent
    elif conv == "depthwise":
        c1 = c0h
    elif conv == "depthwise2":
        d0, d1 = (jnp.diagonal(m, axis1=-2, axis2=-1) for m in (M[0], M[1]))
        c1 = c0h * d1 + _before(c0h) * d0 + b1
    else:
        def tap(a, m):
            return jnp.einsum("thd,hde->the", _round(a, mode), _round(m, mode),
                              precision=_HI)

        c1 = tap(c0h, M[1]) + tap(_before(c0h), M[0]) + b1
    q, k = c1[:, :H] + m_q, c1[:, H:] + m_k
    if var.get("qknorm", True):
        q, k = _unit(q) * hd ** 0.5, _unit(k) * hd ** 0.5
    tau = jnp.ones_like(w["temp"]) if var.get("temp") == "one" else w["temp"]
    lanes = hd if var.get("rope") == "whole" else cfg.rotary_dim
    theta = float(var.get("theta", cfg.rope_theta))
    q = _rope_lanes(q, lanes, theta)
    k_plain = _rope_lanes(k, lanes, theta)          # a rotation is linear:
    k = k_plain * tau[:, None]                      # tau before it or after
    shift = var.get("vshift")
    if shift == "none":
        v = jnp.concatenate([v1, v2], axis=-1)
    elif shift == "head0":
        v = jnp.concatenate([_before(v1), v2], axis=-1)
    else:
        v = jnp.concatenate([v1, _before(v2)], axis=-1)
    vh = v.reshape(T, KV, hd)
    s = jnp.einsum("qhd,khd->hqk", _round(q, mode),
                   _round(jnp.repeat(k, G, axis=1), mode), precision=_HI
                   ) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -1e30), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", _round(p, mode),
                   _round(jnp.repeat(vh, G, axis=1), mode), precision=_HI)
    cached = (k_plain if var.get("temp") == "after" else k).reshape(T, KV * hd)
    rows = (jnp.stack([jnp.concatenate([u[t], c0[t], v2[t]]) for t in row_at])
            if row_at else None)
    return (_mm(o.reshape(T, H * hd), w["wo"]["kernel"], mode), cached, v, rows)


def route(h, r_prev, rt, cfg, mode: str, var: dict):
    """h: [T, D]; r_prev: [T, R]. Returns (chosen [T], combine [T, E]: each
    token's weight for each expert, zero for the unchosen, the stream r [T,
    R], p [T, E])."""
    low = var.get("router", "fp8" if mode == "fp8" else "float32")
    gamma = 0.0 if not var.get("carry", True) else rt["gamma"]
    r = _mm(h, rt["down"], low) + gamma * r_prev
    a = _rms(r, rt["norm"]["scale"], cfg.rms_norm_eps)
    a = jax.nn.gelu(_mm(a, rt["w1"], low) + rt["b1"])
    a = jax.nn.gelu(_mm(a, rt["w2"], low) + rt["b2"])
    p = jax.nn.softmax(_mm(a, rt["w3"], low), axis=-1)
    chosen = jnp.argmax(p + rt["bias"], axis=-1)
    picked = jax.nn.one_hot(chosen, cfg.n_experts, dtype=jnp.float32)
    if var.get("weight") == "one":
        weight = picked
    elif var.get("bias") == "weights":
        weight = (p + rt["bias"]) * picked
    else:
        weight = p * picked
    return chosen, weight, r, p


def moe(w, h, x, r_prev, cfg, mode: str, held=None, var: dict | None = None,
        expert_block: int = 4):
    """The expert sublayer on h [T, D] (normed; ``x`` the residual, which the
    ``router_in`` control reads). ``held`` = (lo, hi) gives one holder's part
    (``w["experts"]`` then holds those experts alone). Returns (y, chosen,
    r, p)."""
    var = var or {}
    chosen, combine, r, p = route(x if var.get("router_in") == "x" else h,
                                  r_prev, w["router"], cfg, mode, var)
    y = experts_sum(h, combine, w["experts"], held or cfg.held, mode,
                    expert_block)
    return y, chosen, r, p


def _f32_but_experts(w):
    moe_w = w["moe"]
    out = _f32({k: v for k, v in w.items() if k != "moe"})
    out["moe"] = {"router": _f32(moe_w["router"]), "experts": moe_w["experts"]}
    return out


@partial(jax.jit, static_argnames=("cfg", "mode", "variant", "row_at"))
def _layer_jit(w, x, r_prev, cfg, mode, variant, row_at):
    """One layer. x: [T, D] float32, r_prev: [T, R] -> (x, r, what the mixer
    leaves for a cache (k, v, rows), the router's (p, chosen))."""
    w, var = _f32_but_experts(w), dict(variant)
    res = w["res"]
    if var.get("gains") == "one":
        res = jax.tree.map(jnp.ones_like, res)
    h = _rms(x, w["attn_norm"]["scale"], cfg.rms_norm_eps)
    y, k, v, rows = cca(w, h, cfg, mode, var, row_at)
    x = res["attn_x"] * x + res["attn_y"] * y
    h = _rms(x, w["ffn_norm"]["scale"], cfg.rms_norm_eps)
    y, chosen, r, p = moe(w["moe"], h, x, r_prev, cfg, mode, var=var)
    return res["ffn_x"] * x + res["ffn_y"] * y, r, (k, v, rows), (p, chosen)


@partial(jax.jit, static_argnames=("cfg", "mode"))
def _logits_jit(table, x, cfg, mode):
    """The tied head, a block of the table's rows at a time: a float32 copy
    of all 262,272 rows is 2.1 GB beside the program's 12.5."""
    x = _rms(x, jnp.ones((cfg.d_model,), jnp.float32), cfg.rms_norm_eps)
    V = table.shape[0]
    blocks = next(b for b in (32, 16, 8, 4, 2, 1) if V % b == 0)
    out = jax.lax.map(lambda rows: _mm(x, rows.astype(jnp.float32).T, mode),
                      table.reshape(blocks, V // blocks, -1))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], V)


def forward(seed: int, cfg, tokens, *, mode: str = "float32",
            variant: dict | None = None, logits_from: int = 0,
            state_at: tuple = (), zero_row: int | None = None) -> dict:
    """Full forward pass over ``tokens`` [T]: ``logits`` [T - logits_from,
    vocab] of the positions from ``logits_from`` on; every layer's cache rows
    ``k``, ``v`` [layers, T, KV . hd]; every layer's ROW after ``n``
    positions, for each ``n`` of ``state_at``: ``row`` [layers,
    len(state_at), row width] (``[u | c0 | v2]`` of position ``n - 1``); the
    router's stream ``r`` [layers, T, R], probabilities ``p`` [layers, T, E]
    and choices ``chosen`` [layers, T]."""
    variant = dict(variant or {})
    pad = variant.pop("pad", None)
    tokens = [int(t) for t in tokens]
    if pad:  # pad positions of token 0 after the first pad_from true ones
        n = variant.pop("pad_from")
        tokens = tokens[:n] + [0] * (pad - n) + tokens[n:]
        # a row asked for at the prompt's end is read where the pad ends
        state_at = tuple(s if s < n else s + pad - n for s in state_at)
    key = W.seed_key(seed)
    table = W.embedding(key, cfg, zero_row)
    x = table[jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    r = jnp.zeros((len(tokens), cfg.router_hidden), jnp.float32)
    frozen = tuple(sorted(variant.items()))
    row_at = tuple(s - 1 for s in state_at)
    out = {"k": [], "v": [], "row": [], "r": [], "p": [], "chosen": []}
    for i in range(cfg.n_layers):
        x, r, (k, v, rows), (p, chosen) = _layer_jit(
            W.layer_from_seed(key, cfg, i), x, r, cfg, mode, frozen, row_at)
        for name, a in (("k", k), ("v", v), ("row", rows), ("r", r), ("p", p),
                        ("chosen", chosen)):
            out[name].append(a)
    if pad:  # the rows a cache would hold: the pad positions' taken out
        keep = jnp.asarray([t for t in range(len(tokens)) if not n <= t < pad])
        x = x[keep]
        for name in ("k", "v", "r", "p", "chosen"):
            out[name] = [a[keep] for a in out[name]]
    res = {name: jnp.stack(a) for name, a in out.items()
           if a and a[0] is not None}
    res["logits"] = _logits_jit(table, x[logits_from:], cfg, mode)
    return res
