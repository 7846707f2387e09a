"""Pipeline parallelism: stage splitting, GPipe schedule, bubble math.

``split_stages`` reshapes a layer-stacked parameter tree (L, ...) into
(S, L/S, ...) so each pipeline stage owns a contiguous layer slab.
``pipeline_apply`` runs the classic GPipe collective schedule inside
``shard_map`` over one mesh axis: every stage applies its local layers to
the microbatch in flight, then ``ppermute`` rotates activations to the next
stage; M microbatches drain in M + S - 1 steps.  ``bubble_fraction`` is the
idle fraction of that schedule, (S-1)/(M+S-1) — the quantity the launch
planner trades against per-stage memory.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def split_stages(params, n_stages: int):
    """Reshape every leaf's leading layer dim L -> (n_stages, L/n_stages)."""

    def split(x):
        l = x.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return x.reshape(n_stages, l // n_stages, *x.shape[1:])

    return jax.tree.map(split, params)


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    """Idle fraction of the GPipe schedule: (S-1)/(M+S-1)."""
    return (n_stages - 1) / (n_microbatches + n_stages - 1)


def pipeline_apply(layer_fn, stage_params, xs, axis_name: str):
    """GPipe schedule over the ``axis_name`` mesh axis (call in shard_map).

    layer_fn(w, h) -> h applies ONE layer; ``stage_params`` holds this
    stage's layers stacked on dim 0; ``xs`` is (M, microbatch...) with M
    microbatches.  Returns (M, microbatch...) outputs, replicated across
    stages (the last stage's results are psum-broadcast, so out_specs can
    stay replicated for single-controller callers).
    """
    stage = jax.lax.axis_index(axis_name)
    n_stages = jax.lax.psum(1, axis_name)
    m = xs.shape[0]
    n_steps = m + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def stage_fwd(h):
        def body(h, w):
            return layer_fn(w, h), None

        h, _ = jax.lax.scan(body, h, stage_params)
        return h

    def step(carry, t):
        state, outputs = carry
        inp = jax.lax.dynamic_index_in_dim(
            xs, jnp.minimum(t, m - 1), 0, keepdims=False)
        # stage 0 feeds fresh microbatches for the first M steps; everyone
        # else consumes what rotated in from the previous stage
        feed = (stage == 0) & (t < m)
        h = stage_fwd(jnp.where(feed, inp, state))
        oi = t - (n_stages - 1)
        emit = (stage == n_stages - 1) & (oi >= 0)
        outputs = outputs.at[jnp.clip(oi, 0, m - 1)].add(
            jnp.where(emit, h, jnp.zeros_like(h)))
        state = jax.lax.ppermute(h, axis_name, perm)
        return (state, outputs), None

    (_, outputs), _ = jax.lax.scan(
        step, (jnp.zeros_like(xs[0]), jnp.zeros_like(xs)),
        jnp.arange(n_steps))
    # only the last stage accumulated anything: psum replicates it everywhere
    return jax.lax.psum(outputs, axis_name)

