"""Mixture-of-experts layers: three routers, two regimes.

Absent from the reference (ref: SURVEY §2.3 — "no MoE expert parallel
in-tree"; vLLM handles EP internally).

* **Training (``moe_ffn``, reached from ``llama_forward``):** Switch top-1
  with capacity dropping, two matrices an expert, in the einsum
  dispatch/combine formulation — a capacity-bounded one-hot ``[T, E, C]``
  dispatch tensor, expert weights sharded on the ``ep`` mesh axis, and
  sharding propagation turning the einsums into all_to_all over ICI.
* **Serving (``sigmoid_topk_route`` / ``softmax_topk_route`` +
  ``routed_experts`` + ``moe_layer``, reached from ``llm/mla_moe.py``,
  ``llm/cohere2_moe.py`` and ``llm/sparse_moe.py``):** the DeepSeek-V3
  family's layer, Cohere2's with no bias, no routed scale and its shared
  experts averaged, and the Qwen3-MoE shape's: a softmax over all experts,
  the k most probable renormalised, no shared expert at all. Sigmoid
  scores, the k experts with the largest ``score + bias`` chosen and
  weighed by the score alone, no capacity (no token is ever dropped),
  three-matrix SwiGLU experts and, where the model has them, shared experts
  every token passes through. The one-hot dispatch does not scale to 128 experts x 12k prefill
  tokens, so the routed product is a grouped matmul over the assignments
  sorted by expert (``jax.lax.ragged_dot`` for prefill's many rows a group,
  ``ops/grouped_swiglu.py`` for a decode step's few). The layer is told which
  experts it holds (``held``): it routes over all of them and computes its
  own experts' part of the sum — on one chip that is all of them, and the
  exchange between holders is not here.
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
                       norm: bool = True):
    """``noaux_tc`` routing with one group: ``s = sigmoid(h . W)`` in
    float32 (the family computes its gate in float32 whatever the model's
    type: a bf16 score would flip near-tied choices), the ``k`` experts with
    the largest ``s + bias``, weighed by ``s`` alone — the bias chooses and
    never weighs. Equal sums go to the lower expert index (``lax.top_k``).

    h: [T, D]; router_w: [D, E]; bias: [E], or None for a router that
    chooses by the score itself. Returns (idx [T, k] int32, weights [T, k]
    float32)."""
    s = jax.nn.sigmoid(jnp.matmul(
        h.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(
        s if bias is None else s + bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scale


# rows of a routed product at or under which it is a decode step's: the two
# cells' steps route 192 and 384 rows, their smallest prefill program 3,072
_FEW_ROWS = 512


def _streams_experts(rows: int) -> bool:
    """Which of the two grouped products ``routed_experts`` runs, decided by
    what the code can see and by no option. On a TPU, ``rows = T * k`` at or
    under ``_FEW_ROWS`` — a decode step: a few rows an expert, bound by the
    bytes of the touched experts — goes through ``ops/grouped_swiglu.py``,
    which reads each touched expert's three matrices once (measured alone on
    a v5e, PR 32: 192 rows over 46 of 128 experts of 2048 x 768, 631 us
    against 931; 384 rows over 16 experts of 4096 x 4096, 2,190 us against
    3,168). More rows — every prefill program: thousands of rows a group,
    bound by the MXU — and every other backend, where the kernel would be
    interpreted, keep the three ``jax.lax.ragged_dot`` calls, which thereby
    stay the kernel's plain reference and what the CPU tests run."""
    return jax.default_backend() == "tpu" and rows <= _FEW_ROWS


def expert_passes(load, rows: int):
    """How often the grouped product of ``rows`` assignments puts an
    expert's matrices through the MXU, summed over ``load`` [..., held]:
    the kernel's row chunks where it runs, else once a touched expert."""
    if _streams_experts(rows):
        return grouped_swiglu.expert_passes(load)
    return (load > 0).sum()


@tracing.part("experts")
def routed_experts(h, idx, w, experts, held: tuple[int, int], valid=None):
    """The held experts' part of ``sum_e w_e . swiglu_e(h)``, with no
    capacity: the ``T * k`` assignments are sorted by expert, each expert's
    rows form one group of a grouped product (``_streams_experts`` says
    which: one kernel that streams the touched experts for a decode step's
    few rows, ``jax.lax.ragged_dot`` for prefill's many), and the weighted
    rows are summed back per token. Assignments to experts outside ``held =
    (lo, hi)`` and of rows where ``valid`` is False (dead decode slots,
    prompt padding) sort behind the last group and add nothing.

    h: [T, D]; idx, w: [T, k]; experts: {"w_gate", "w_up": [hi-lo, D, F],
    "w_down": [hi-lo, F, D]}. Returns (y [T, D], load [hi-lo] int32: the
    rows each held expert got)."""
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
    xs = h[order // k]                                   # [T * k, D]
    if _streams_experts(T * k):
        ys = grouped_swiglu.grouped_swiglu(
            xs, experts["w_gate"], experts["w_up"], experts["w_down"], load)
    else:
        hid = jax.nn.silu(jax.lax.ragged_dot(xs, experts["w_gate"], load)) * (
            jax.lax.ragged_dot(xs, experts["w_up"], load))
        ys = jax.lax.ragged_dot(hid, experts["w_down"], load)
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


def moe_layer(h, moe, *, k: int, scale: float, norm: bool = True,
              held: tuple[int, int], valid=None, shared_scale: float = 1.0,
              softmax: bool = False):
    """One expert layer of the family on ``h`` [T, D] (already normed):
    the held routed experts' part plus the shared experts (one SwiGLU of
    the summed shared width, which every holder computes alike) times
    ``shared_scale`` — 1 where the shared experts are summed, 1 / their
    number where they are averaged; a layer with no ``shared`` sub-tree has
    none, and its holders' parts add up to the layer. A router without a
    ``bias`` chooses by its scores; ``softmax`` is the model's router being
    ``softmax_topk_route`` (it has no scale). Returns (y [T, D], load
    [hi-lo])."""
    from ray_tpu.ops.basic import swiglu

    if softmax:
        idx, w = softmax_topk_route(h, moe["router"]["kernel"], k, norm)
    else:
        idx, w = sigmoid_topk_route(h, moe["router"]["kernel"],
                                    moe["router"].get("bias"), k, scale, norm)
    y, load = routed_experts(h, idx, w, moe["experts"], held, valid)
    if "shared" not in moe:
        return y, load
    sh = moe["shared"]
    with tracing.part("ffn"):
        shared = swiglu(h, sh["w_gate"]["kernel"], sh["w_up"]["kernel"],
                        sh["w_down"]["kernel"])
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
