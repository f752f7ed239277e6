"""Pipeline parallelism: layer stages sharded over a ``pp`` mesh axis.

Reference analogue: the PP sizes the reference passes to its engines
(reference: components/backends/trtllm/src/dynamo/trtllm/utils/
trtllm_utils.py:134-138 — PP is engine-internal there). TPU-native
formulation: the stacked layer parameters ``[L, ...]`` shard over
``pp`` (device s holds layers [s·L/n, (s+1)·L/n)); activations flow
stage→stage with ``lax.ppermute`` on a GPipe microbatch schedule, so all
stages work concurrently on different microbatches.

Schedule: M microbatches through n stages takes M+n-1 steps (bubble
fraction (n-1)/(M+n-1)); microbatch m enters stage 0 at step m and exits
stage n-1 at step m+n-1. The final psum gathers the last stage's
outputs to every device (outputs are zero elsewhere).

This is the serving-side PP primitive (one forward, no backward); the
engine integration point is the layer scan in model.py — a pp-sharded
engine runs ``pipeline_apply`` with the decode batch split into
microbatches and the KV cache layer-sharded over the same axis
(cache axis 0 is layers, so ``P("pp", ...)`` keeps every stage's pages
local). Single-chip benches cannot exercise it; parity is pinned on the
virtual-device mesh in tests/test_pipeline.py.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply_local(
    x_mb: jax.Array,       # [M, mb, D] — all microbatches (replicated input)
    local_layers: Any,     # pytree with leading local-layer axis (this stage's slice)
    layer_fn: Callable,    # (x [mb, D], layer_params) -> x [mb, D]
    axis_name: str,
) -> jax.Array:
    """Per-device body (run under shard_map over ``axis_name``).
    Returns [M, mb, D] outputs, identical on every device."""
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    M = x_mb.shape[0]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def stage(x):
        def body(c, lp):
            return layer_fn(c, lp), None

        y, _ = lax.scan(body, x, local_layers)
        return y

    def step(t, carry):
        recv, outputs = carry
        # Stage 0 injects microbatch t; later stages consume the permuted
        # activation from their predecessor.
        inject = x_mb[jnp.clip(t, 0, M - 1)]
        inp = jnp.where(me == 0, inject, recv)
        out = stage(inp)
        # The last stage emits microbatch t-(n-1) (it has now traversed
        # every stage); other steps/stages write nothing.
        out_idx = jnp.clip(t - (n - 1), 0, M - 1)
        valid = (me == n - 1) & (t >= n - 1) & (t - (n - 1) < M)
        outputs = jnp.where(valid, outputs.at[out_idx].set(out), outputs)
        recv_next = lax.ppermute(out, axis_name, perm)
        return (recv_next, outputs)

    recv0 = lax.pcast(jnp.zeros_like(x_mb[0]), (axis_name,), to="varying")
    out0 = lax.pcast(jnp.zeros_like(x_mb), (axis_name,), to="varying")
    _, outputs = lax.fori_loop(0, M + n - 1, step, (recv0, out0))
    # Only the last stage holds real outputs; zeros elsewhere → psum
    # broadcasts them to the whole group.
    return lax.psum(outputs, axis_name)


def pipeline_apply(
    mesh: Mesh,
    axis_name: str,
    params_stacked: Any,   # pytree, leading axis L divisible by the pp size
    x: jax.Array,          # [B, D] — full batch (replicated)
    layer_fn: Callable,
    num_microbatches: int,
) -> jax.Array:
    """GPipe-microbatched forward of a stacked-layer network with the
    layer axis sharded over ``axis_name``. Returns [B, D]."""
    B, D = x.shape
    M = num_microbatches
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    x_mb = x.reshape(M, B // M, D)

    layer_spec = jax.tree.map(lambda _: P(axis_name), params_stacked)
    fn = jax.shard_map(
        functools.partial(pipeline_apply_local, layer_fn=layer_fn, axis_name=axis_name),
        mesh=mesh,
        in_specs=(P(), layer_spec),
        out_specs=P(),
    )
    sharded = jax.tree.map(
        lambda leaf: jax.device_put(
            leaf, NamedSharding(mesh, P(axis_name, *([None] * (leaf.ndim - 1))))
        ),
        params_stacked,
    )
    out = fn(jax.device_put(x_mb, NamedSharding(mesh, P())), sharded)
    return out.reshape(B, D)
