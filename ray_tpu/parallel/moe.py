"""Mixture-of-experts layers: four routers, two regimes, two expert forms.

Absent from the reference (ref: SURVEY §2.3 — "no MoE expert parallel
in-tree"; vLLM handles EP internally).

* **Training (``moe_ffn``, reached from ``llama_forward``):** Switch top-1
  with capacity dropping, two matrices an expert, in the einsum
  dispatch/combine formulation — a capacity-bounded one-hot ``[T, E, C]``
  dispatch tensor, expert weights sharded on the ``ep`` mesh axis, and
  sharding propagation turning the einsums into all_to_all over ICI.
* **Serving (``sigmoid_topk_route`` / ``softmax_topk_route`` /
  ``mlp_top1_route`` + ``routed_experts`` + ``moe_layer``, from the seven expert
  families' modules under ``llm/``):** no capacity, so no token is ever
  dropped. The router is the DeepSeek-V3 family's (sigmoid scores, the k
  largest ``score + bias`` chosen — among all experts, or inside the few
  groups of largest score — weighed by the score alone, scaled), Cohere2's
  (no bias, no scale), Qwen3-MoE's (a softmax, the k most probable) or an MLP
  with a carry across layers (``mlp_top1_route``). An expert, routed or
  shared, is a three-matrix SwiGLU or — where its tree has no ``w_gate`` —
  two matrices, ``W_down . relu(W_up . h)^2``. A one-hot dispatch does not
  scale to 128 experts x 12k prefill tokens, so the routed product is a
  grouped matmul over the assignments sorted by expert (``_streams_experts``
  says which) — or, for a decode step's few rows of two-matrix experts,
  every held expert on every token with the unchosen weighed by zero
  (``_applies_every_expert``). The layer is told which experts it holds
  (``held``): it routes over all of them and computes its own experts' part
  of the sum — the exchange between holders is not here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops import grouped_swiglu
from ray_tpu.utils import tracing


def top1_gating(logits, n_experts: int, capacity: int):
    """Switch-style top-1 routing with capacity dropping.

    logits: [tokens, E]. Returns (dispatch [T, E, C] one-hot float,
    combine [T, E, C] weights, aux_loss scalar).
    """
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)  # [T]
    gate = jnp.take_along_axis(probs, expert_idx[:, None], axis=-1)[:, 0]

    one_hot = jax.nn.one_hot(expert_idx, n_experts, dtype=jnp.float32)  # [T, E]
    # position of each token within its expert's queue
    pos_in_expert = (jnp.cumsum(one_hot, axis=0) - 1.0) * one_hot  # [T, E]
    keep = (pos_in_expert < capacity) & (one_hot > 0)
    pos = pos_in_expert.astype(jnp.int32)

    dispatch = keep[..., None] & (
        jax.nn.one_hot(pos, capacity, dtype=jnp.bool_)
    )  # [T, E, C]
    dispatch = dispatch.astype(jnp.float32)
    combine = dispatch * gate[:, None, None]

    # load-balancing auxiliary loss (Switch Transformer eq. 4)
    density = one_hot.mean(axis=0)
    density_proxy = probs.mean(axis=0)
    aux_loss = (density * density_proxy).sum() * n_experts
    return dispatch, combine, aux_loss


def moe_ffn(x, gate_w, w_up, w_down, *, capacity_factor: float = 1.25,
            mesh=None, ep_axis: str = "ep"):
    """Expert-parallel FFN block.

    x: [B, T, D]; gate_w: [D, E]; w_up: [E, D, F]; w_down: [E, F, D]
    (expert axis of w_up/w_down sharded on ``ep`` by the caller's rules).
    """
    B, T, D = x.shape
    E = gate_w.shape[-1]
    tokens = x.reshape(B * T, D)
    capacity = max(1, int(capacity_factor * (B * T) / E))

    logits = tokens @ gate_w
    dispatch, combine, aux = top1_gating(logits, E, capacity)

    # [T,E,C] x [T,D] -> [E, C, D]; sharding propagation inserts all_to_all
    expert_in = jnp.einsum("tec,td->ecd", dispatch, tokens)
    if mesh is not None and ep_axis in mesh.shape and mesh.shape[ep_axis] > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P

        expert_in = jax.lax.with_sharding_constraint(
            expert_in, NamedSharding(mesh, P(ep_axis))
        )
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, w_up))
    expert_out = jnp.einsum("ecf,efd->ecd", h, w_down)
    out = jnp.einsum("tec,ecd->td", combine, expert_out)
    return out.reshape(B, T, D), aux


# ------------------------------------------------------------------ serving
@tracing.part("router")
def sigmoid_topk_route(h, router_w, bias, k: int, scale: float,
                       norm: bool = True, n_group: int = 1,
                       topk_group: int = 1):
    """``noaux_tc`` routing: ``s = sigmoid(h . W)`` in float32 (the family
    computes its gate in float32 whatever the model's type: a bf16 score
    would flip near-tied choices), the ``k`` experts with the largest ``s +
    bias``, weighed by ``s`` alone — the bias chooses and never weighs. Equal
    sums go to the lower expert index (``lax.top_k``). With ``n_group`` > 1
    the choice is **group-limited**: the experts are ``n_group`` groups of
    consecutive ids, a group's score is the sum of its two largest ``s +
    bias``, the ``topk_group`` groups of largest score stay (equal scores: the
    lower group) and the ``k`` are chosen among their experts alone — a token
    then reaches at most ``topk_group`` of the groups' holders. One group
    chooses among all.

    h: [T, D]; router_w: [D, E]; bias: [E], or None for a router that
    chooses by the score itself. Returns (idx [T, k] int32, weights [T, k]
    float32)."""
    s = jax.nn.sigmoid(jnp.matmul(
        h.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    by = s if bias is None else s + bias.astype(jnp.float32)
    if n_group > 1:
        T, E = by.shape
        groups = by.reshape(T, n_group, E // n_group)
        # a group's two largest as two passes of max (``lax.top_k`` of 2 in
        # 64 became a whole sort on the chip, 2.7 % of a decode step)
        first = groups.max(axis=-1, keepdims=True)
        at = jnp.argmax(groups, axis=-1, keepdims=True)
        second = jnp.where(jnp.arange(E // n_group) == at, -jnp.inf,
                           groups).max(axis=-1, keepdims=True)
        _, stay = jax.lax.top_k((first + second)[..., 0], topk_group)
        kept = (stay[:, :, None] == jnp.arange(n_group)).any(axis=1)
        by = jnp.where(kept[:, :, None], groups, -jnp.inf).reshape(T, E)
    _, idx = jax.lax.top_k(by, k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scale


# rows HANDED to a routed product (T * k, whoever holds their experts) at or
# under which it streams. At 6 or 8 experts a token those are the decode steps:
# 192 to 768 rows handed, 32 to 192 on the held experts (their smallest prefill
# program hands 3,072). At ONE a token the rows ARE the tokens: a step of 80
# slots hands 80 (5 an expert of 16), and prefill waves of up to 1,024 tokens
# (16-64 rows an expert) stream too; 2,048-8,192 tokens are ``ragged_dot``'s
_FEW_ROWS = 1024


def _streams_experts(rows: int) -> bool:
    """Which of the grouped products ``routed_experts`` runs, decided by
    what the code can see and by no option. On a TPU, ``rows = T * k`` at or
    under ``_FEW_ROWS`` — a decode step, and at ONE expert a token a short
    prefill wave: up to 64 rows a held expert, bound by the touched experts'
    bytes (2048 x 2048: 25 MB, 31 us against 8 us of MXU at 64 rows) — streams
    them through ONE kernel. The bound is on the rows HANDED (those of experts
    held elsewhere sort last and cost no weight): row block and float32 output
    stay in VMEM, twice each — 26 MB at 768 x 2560, 22 at 384 x 4096, 25 at
    1,024 x 2048, of the kernel's 100 MB, beside 48 for the weights in flight.
    More rows — hundreds to thousands a group, bound by the MXU, and past 3,168
    rows ``ragged_dot`` stops reading the weights alone — and every other
    backend, where the kernel would be interpreted, keep the three
    ``jax.lax.ragged_dot`` calls, which thereby stay the kernel's plain
    reference and what the CPU tests run. Asked for three-matrix experts only:
    two-matrix experts (a tree with no ``w_gate``) have no streamed form —
    ``_applies_every_expert`` says what a step's few rows of theirs take
    instead, and more rows are two ``ragged_dot`` calls."""
    return jax.default_backend() == "tpu" and rows <= _FEW_ROWS


# rows at or under which two-matrix experts are ALL applied to every token:
# at 6 or 8 experts a token that is at most 170 tokens, under the 240 or so
# where 16 held experts' products would outweigh the bytes of their matrices
_DENSE_ROWS = 1024


def _applies_every_expert(rows: int, gated: bool = True) -> bool:
    """The two-matrix experts' form for a decode step's few rows, on every
    backend: one batched product of every token with EVERY held expert, the
    unchosen weighed by zero. A step of 128 slots touches most of the 16
    held experts anyway (10.5 under a seeded router, all at an even one's 6
    rows each), and the MXU has the room: on the chip two ``ragged_dot``
    calls over those 768 sorted rows took 3.3 ms an expert block for 0.32 GB
    of weights, an eighth of the bandwidth, this product 0.43 (PERF.md
    section 6, PR 38). Past ``_DENSE_ROWS`` the rows of a prompt are sorted
    and grouped as every family's."""
    return not gated and rows <= _DENSE_ROWS


def expert_passes(load, rows: int, gated: bool = True):
    """How often the grouped product of ``rows`` assignments puts an
    expert's matrices through the MXU, summed over ``load`` [..., held]:
    the kernel's row chunks where it runs, every held expert once where all
    are applied, else once a touched expert."""
    if gated and _streams_experts(rows):
        return grouped_swiglu.expert_passes(load)
    if _applies_every_expert(rows, gated):
        return jnp.asarray(load.size)
    return (load > 0).sum()


@tracing.part("experts")
def routed_experts(h, idx, w, experts, held: tuple[int, int], valid=None):
    """The held experts' part of ``sum_e w_e . swiglu_e(h)``, with no
    capacity: the ``T * k`` assignments are sorted by expert, each expert's
    rows form one group of a grouped product (``_streams_experts`` says
    which: one kernel that streams the touched experts for a decode step's
    few rows, ``jax.lax.ragged_dot`` for prefill's many), and the weighted
    rows are summed back per token; a step's few rows of two-matrix experts
    skip the sort (``_applies_every_expert``). Assignments to experts outside ``held =
    (lo, hi)`` and of rows where ``valid`` is False (dead decode slots,
    prompt padding) sort behind the last group and add nothing.

    h: [T, D]; idx, w: [T, k]; experts: {"w_gate", "w_up": [hi-lo, D, F],
    "w_down": [hi-lo, F, D]}, or the two-matrix form without ``w_gate``.
    Returns (y [T, D], load [hi-lo] int32: the rows each held expert got)."""
    T, k = idx.shape
    lo, hi = held
    n = hi - lo
    with tracing.part("router"):
        keep = (idx >= lo) & (idx < hi)
        if valid is not None:
            keep &= valid[:, None]
        group = jnp.where(keep, idx - lo, n).reshape(-1)  # n = "nobody here"
        order = jnp.argsort(group)                        # stable
        load = jnp.bincount(group, length=n + 1)[:n].astype(jnp.int32)
    if _applies_every_expert(T * k, "w_gate" in experts):
        return _every_expert(h, idx - lo, w, keep, experts), load
    xs = h[order // k]                                   # [T * k, D]
    if "w_gate" in experts and _streams_experts(T * k):
        ys = grouped_swiglu.grouped_swiglu(
            xs, experts["w_gate"], experts["w_up"], experts["w_down"], load)
    else:
        # prefill's many rows a group and every other backend:
        # ``_streams_experts`` says why
        ys = _ragged_experts(xs, experts, load)
    ws = jnp.where(keep, w, 0.0).reshape(-1)[order]
    # rows past the last group belong to no expert: whatever the grouped
    # product left there is dropped, not scaled
    ys = jnp.where(ws[:, None] != 0, ys * ws[:, None].astype(ys.dtype), 0)
    y = ys[jnp.argsort(order)].reshape(T, k, -1).sum(axis=1)
    return y.astype(h.dtype), load


@tracing.part("router")
def softmax_topk_route(h, router_w, k: int, norm: bool = True):
    """The Qwen3-MoE router: ``p = softmax(h . W)`` over ALL experts in
    float32 (for the reason ``sigmoid_topk_route`` is), the ``k`` most
    probable, renormalised to sum 1 where ``norm``. Equal probabilities go
    to the lower expert index. h: [T, D]; router_w: [D, E]. Returns
    (idx [T, k] int32, weights [T, k] float32)."""
    p = jax.nn.softmax(jnp.matmul(
        h.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    w, idx = jax.lax.top_k(p, k)
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w


def moe_route(h, moe, *, k: int, scale: float, norm: bool = True,
              softmax: bool = False, n_group: int = 1, topk_group: int = 1):
    """The layer's router on ``h`` [T, D] by its tree and the model's kind:
    ``softmax_topk_route``, else ``sigmoid_topk_route`` (no ``bias``: by its
    scores): ONE matrix on ``h`` (not ``mlp_top1_route``). Returns (idx, w)."""
    if softmax:
        return softmax_topk_route(h, moe["router"]["kernel"], k, norm)
    return sigmoid_topk_route(h, moe["router"]["kernel"],
                              moe["router"].get("bias"), k, scale, norm,
                              n_group, topk_group)


def tokens_here(idx, held: tuple[int, int], valid=None):
    """How many of the tokens chose at least one of the ``held`` experts: the
    share of a step's tokens this holder has to see at all. idx: [T, k];
    valid: [T] or None. Returns an int32 scalar."""
    here = ((idx >= held[0]) & (idx < held[1])).any(axis=-1)
    if valid is not None:
        here &= valid
    return here.sum().astype(jnp.int32)


def moe_layer(h, moe, *, k: int, scale: float, norm: bool = True,
              held: tuple[int, int], valid=None, shared_scale: float = 1.0,
              softmax: bool = False, n_group: int = 1, topk_group: int = 1):
    """One expert layer of the family on ``h`` [T, D] (already normed):
    the held routed experts' part plus the shared experts (one SwiGLU of
    the summed shared width, which every holder computes alike) times
    ``shared_scale`` — 1 where the shared experts are summed, 1 / their
    number where they are averaged; a layer with no ``shared`` sub-tree has
    none, and its holders' parts add up to the layer. The router is
    ``moe_route``'s. A ``shared`` sub-tree with no ``w_gate`` is the
    two-matrix form, as the routed experts beside it are
    (``_shared_expert``).
    Returns (y [T, D], load [hi-lo])."""
    idx, w = moe_route(h, moe, k=k, scale=scale, norm=norm, softmax=softmax,
                       n_group=n_group, topk_group=topk_group)
    return moe_experts(h, idx, w, moe, held, valid, shared_scale)


def moe_experts(h, idx, w, moe, held: tuple[int, int], valid=None,
                shared_scale: float = 1.0):
    """``moe_layer`` behind its router: the held experts' part for the
    choices ``idx``, ``w`` plus the shared experts. Returns (y, load)."""
    y, load = routed_experts(h, idx, w, moe["experts"], held, valid)
    if "shared" not in moe:
        return y, load
    with tracing.part("ffn"):
        # every holder computes the shared experts alike, so a sum over
        # holders' outputs has to count them once, not once a holder
        shared = _shared_expert(h, moe["shared"])
        if shared_scale != 1:
            shared = shared * jnp.asarray(shared_scale, shared.dtype)
        return y + shared, load


# tokens an expert layer takes at a time in a long prefill: the sorted
# assignments ([tokens * k, D] and three [tokens * k, F] hidden arrays) are
# its largest temporaries, and nothing couples one token's experts to another's
_MOE_CHUNK = 2048


def moe_layer_chunked(h, moe, valid=None, **kw):
    """``moe_layer`` on ``h`` [B, T, D] (already normed), ``_MOE_CHUNK``
    tokens at a time where there are whole chunks of them (a long prefill).
    ``valid``: [B, T] or None; ``kw``: ``moe_layer``'s. Returns (y [B, T, D],
    load [held experts])."""
    B, T, D = h.shape
    flat = h.reshape(B * T, D)
    ok = None if valid is None else valid.reshape(B * T)
    n = B * T
    if n <= _MOE_CHUNK or n % _MOE_CHUNK:
        y, load = moe_layer(flat, moe, valid=ok, **kw)
        return y.reshape(B, T, D), load
    chunks = n // _MOE_CHUNK
    ok = jnp.ones((n,), bool) if ok is None else ok
    y, load = jax.lax.map(
        lambda c: moe_layer(c[0], moe, valid=c[1], **kw),
        (flat.reshape(chunks, _MOE_CHUNK, D), ok.reshape(chunks, _MOE_CHUNK)))
    return y.reshape(B, T, D), load.sum(axis=0)


def _relu2(a):
    """``relu(a)^2``: the ungated experts' activation."""
    return jnp.square(jax.nn.relu(a))


def _ragged_experts(xs, experts, load):
    """The routed product as ``jax.lax.ragged_dot`` calls over the sorted
    rows: three for SwiGLU experts, two for the ungated form."""
    if "w_gate" in experts:
        hid = jax.nn.silu(jax.lax.ragged_dot(xs, experts["w_gate"], load)) * (
            jax.lax.ragged_dot(xs, experts["w_up"], load))
    else:
        hid = _relu2(jax.lax.ragged_dot(xs, experts["w_up"], load))
    return jax.lax.ragged_dot(hid, experts["w_down"], load)


def _every_expert(h, held_idx, w, keep, experts):
    """Every held two-matrix expert on every token, each token's outputs
    summed under its weights (zero for an expert it did not choose). h:
    [T, D]; held_idx [T, k]: the chosen experts' places among the held;
    w, keep: [T, k]. Returns [T, D] in h's dtype."""
    n = experts["w_up"].shape[0]
    hot = (held_idx[:, :, None] == jnp.arange(n)) & keep[:, :, None]
    combine = jnp.where(hot, w[:, :, None], 0.0).sum(axis=1)      # [T, n]
    hid = _relu2(jnp.einsum("td,edf->etf", h, experts["w_up"]))
    ys = jnp.einsum("etf,efd->etd", hid, experts["w_down"])
    return jnp.einsum("etd,te->td", ys, combine.astype(ys.dtype),
                      preferred_element_type=jnp.float32).astype(h.dtype)


def _shared_expert(h, sh):
    """The shared experts as one expert of their summed width, in the form
    their tree has."""
    if "w_gate" in sh:
        from ray_tpu.ops.basic import swiglu

        return swiglu(h, sh["w_gate"]["kernel"], sh["w_up"]["kernel"],
                      sh["w_down"]["kernel"])
    return _relu2(h @ sh["w_up"]["kernel"]) @ sh["w_down"]["kernel"]


@tracing.part("router")
def mlp_top1_route(h, r_prev, router, eps: float = 1e-5):
    """The ZAYA router: an MLP over a stream of its own that CARRIES from
    layer to layer. ``r = h . W_down + gamma . r_prev`` (``r_prev`` None: the
    first layer's, zeros), ``s = gelu(gelu(N(r) . W1 + c1) . W2 + c2) . W3``
    with ``N`` an RMSNorm with a gain and ``gelu`` the tanh form, ``p =
    softmax(s)``; the ONE expert of largest ``p + bias`` is chosen (equal
    sums go to the lower index) and weighed by ``p`` itself, not
    renormalised — the bias chooses and never weighs. All of it in float32
    at the highest precision, for the reason ``sigmoid_topk_route`` gives:
    with one expert a token a flipped choice replaces the whole sublayer's
    output. ``r`` goes on to the next layer's router beside the residual; it
    is a token's own and is cached nowhere.

    h: [T, D]; r_prev: [T, R] float32 or None; router: {"down": [D, R],
    "gamma": scalar, "norm": {"scale": [R]}, "w1", "w2": [R, R], "b1", "b2":
    [R], "w3": [R, E], "bias": [E]}. Returns (idx [T, 1] int32, weights
    [T, 1] float32, r [T, R] float32)."""
    f32, hi = jnp.float32, jax.lax.Precision.HIGHEST

    def mm(a, w):
        return jnp.matmul(a, w.astype(f32), precision=hi)

    r = mm(h.astype(f32), router["down"])
    if r_prev is not None:
        r = r + router["gamma"].astype(f32) * r_prev
    a = r * jax.lax.rsqrt(jnp.mean(r * r, axis=-1, keepdims=True) + eps)
    a = a * router["norm"]["scale"].astype(f32)
    a = jax.nn.gelu(mm(a, router["w1"]) + router["b1"].astype(f32))
    a = jax.nn.gelu(mm(a, router["w2"]) + router["b2"].astype(f32))
    p = jax.nn.softmax(mm(a, router["w3"]), axis=-1)
    idx = jnp.argmax(p + router["bias"].astype(f32), axis=-1)[:, None]
    return idx.astype(jnp.int32), jnp.take_along_axis(p, idx, axis=-1), r
