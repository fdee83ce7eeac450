"""Pipeline parallelism as an SPMD collective-permute schedule.

The reference gets PP only by delegating to vLLM config or by building
p2p compiled-graph channels (ref: SURVEY §2.3 PP; dag_node_operation.py
provides the schedule substrate). TPU-native version: the pipeline IS one
jitted program — stage weights live on the ``pp`` mesh axis, activations
hop stages via ``lax.ppermute`` inside a ``lax.scan`` over
microbatch-steps (GPipe schedule), and autodiff through the scan gives the
backward pipeline for free. No per-hop task submission, no host round
trips — the whole schedule is compiler-visible.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P


def pipeline_spmd_local(stage_fn, stage_params, x_micro, *, axis_name: str = "pp"):
    """Per-shard GPipe loop. Call inside shard_map over ``axis_name``.

    stage_fn: (params, activation [B, ...]) -> activation
    stage_params: this stage's params (leaves with leading [1] stage axis
        already squeezed by the caller's in_specs)
    x_micro: [M, B, ...] microbatched input (same on every stage; only
        stage 0 actually consumes it)
    Returns [M, B, ...] outputs of the LAST stage (zeros elsewhere) — psum
    or read from the last pp rank.
    """
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    M = x_micro.shape[0]
    total_steps = M + n - 1
    perm = [(i, (i + 1) % n) for i in range(n)]

    out_shape = jax.eval_shape(lambda p, x: stage_fn(p, x), stage_params, x_micro[0])
    state0 = jnp.zeros(out_shape.shape, out_shape.dtype)
    outputs0 = jnp.zeros((M,) + out_shape.shape, out_shape.dtype)

    def step(carry, t):
        state, outputs = carry
        # stage 0 ingests microbatch t (if still in range); others take the
        # activation that just arrived from the previous stage
        mb_index = jnp.clip(t, 0, M - 1)
        inp = jnp.where(my == 0, x_micro[mb_index], state)
        out = stage_fn(stage_params, inp)
        # last stage records its finished microbatch (t - (n-1))
        done_index = t - (n - 1)
        is_done = jnp.logical_and(my == n - 1, done_index >= 0)
        outputs = lax.cond(
            is_done,
            lambda o: lax.dynamic_update_index_in_dim(
                o, out, jnp.clip(done_index, 0, M - 1), 0
            ),
            lambda o: o,
            outputs,
        )
        # rotate activations to the next stage
        state_next = lax.ppermute(out, axis_name, perm)
        return (state_next, outputs), None

    (state, outputs), _ = lax.scan(step, (state0, outputs0), jnp.arange(total_steps))
    # broadcast final outputs from the last stage to every stage
    outputs = lax.psum(
        jnp.where(my == n - 1, outputs, jnp.zeros_like(outputs)), axis_name
    )
    return outputs


def pipeline_apply(stage_fn, stacked_params, x, mesh, *, n_microbatches: int,
                   axis_name: str = "pp", batch_axis: str | None = None,
                   param_specs=None):
    """Run a GPipe pipeline over ``mesh``'s ``axis_name``.

    stacked_params: pytree whose leaves have a leading stage axis of size
        n_stages, sharded on ``axis_name`` (see stack_stage_params).
    x: [B_total, ...] input batch.
    batch_axis: optional mesh axis to shard the WITHIN-microbatch batch dim
        over (dp) — pp x dp composition: each dp slice runs its own pipeline
        instance on B_total/n_microbatches/dp rows per step (so
        B_total/n_microbatches must divide by the dp size; the
        microbatch-step dim itself stays replicated).
    param_specs: optional per-leaf PartitionSpecs for stacked_params whose
        FIRST axis entry must be ``axis_name`` — pass tp-sharded weight
        specs to run tensor parallelism INSIDE each pipeline stage (the
        stage_fn is then responsible for the matching psums).
    Returns [B_total, ...] final-stage outputs.
    """
    B = x.shape[0]
    if B % n_microbatches:
        raise ValueError(f"batch {B} not divisible by {n_microbatches} microbatches")
    x_micro = x.reshape(n_microbatches, B // n_microbatches, *x.shape[1:])

    if param_specs is None:
        param_specs = jax.tree.map(lambda _: P(axis_name), stacked_params)
    x_spec = P(None, batch_axis) if batch_axis else P()

    def body(params, xm):
        squeezed = jax.tree.map(lambda p: jnp.squeeze(p, 0), params)
        return pipeline_spmd_local(stage_fn, squeezed, xm, axis_name=axis_name)

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )
    out_micro = fn(stacked_params, x_micro)
    return out_micro.reshape(B, *out_micro.shape[2:])


def stack_stage_params(per_stage_params: list):
    """[stage0_tree, stage1_tree, ...] -> one tree with leading stage axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)
