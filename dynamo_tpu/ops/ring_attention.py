"""Ring attention: causal attention with the sequence sharded over a
mesh axis — the long-context prefill primitive.

The reference has NO sequence/context parallelism anywhere (SURVEY §2.6:
long context is handled by engine --max-model-len + KV offload), so this
is net-new TPU design per SURVEY §7: shard the sequence over an ``sp``
mesh axis, keep q local, and rotate (k, v) chunks around the ring with
``lax.ppermute`` (XLA lowers to ICI neighbor exchanges), accumulating
online-softmax partials. Compute and communication overlap naturally:
each ring step's permute is independent of that step's attention math,
and XLA schedules them concurrently.

Causality over the ring: the device holding query chunk i only
accumulates kv chunks j<=i fully, chunk j==i with the local causal mask,
and skips j>i (their contribution is masked, and m/l guards keep the
skipped steps from polluting the accumulators).

Memory: each device holds T/n of q, k, v and one in-flight kv chunk —
peak activation memory for a T-token prefill drops by ~n, which is the
whole point: a 1M-token prompt on v5e-16 becomes 62.5k tokens per chip.

Usage: wrap in shard_map over the sp axis (see ``ring_prefill`` below
and tests/test_ring_attention.py for the mesh plumbing).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def ring_attention_local(
    q: jax.Array,  # [Tc, H, hd] — this device's query chunk (roped)
    k: jax.Array,  # [Tc, KVH, hd] — this device's key chunk (roped)
    v: jax.Array,  # [Tc, KVH, hd]
    axis_name: str,
    *,
    causal: bool = True,
) -> jax.Array:
    """Per-device body (call under shard_map over ``axis_name``).
    Supports GQA (H a multiple of KVH). Returns [Tc, H, hd] in q.dtype."""
    Tc, H, hd = q.shape
    KVH = k.shape[1]
    G = H // KVH
    n = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    scale = hd ** -0.5
    qg = q.reshape(Tc, KVH, G, hd)
    local = jnp.arange(Tc, dtype=jnp.int32)
    q_pos = me * Tc + local  # global positions of this device's queries

    def step(i, carry):
        k_cur, v_cur, m, l, acc = carry
        src = lax.rem(me - i + n, n)  # origin device of the kv chunk in hand
        kv_pos = src * Tc + local
        s = jnp.einsum("tkgh,skh->tkgs", qg, k_cur).astype(jnp.float32) * scale
        if causal:
            mask = kv_pos[None, :] <= q_pos[:, None]  # [Tc, Tc]
            s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)                    # [Tc, KVH, G]
        m_new = jnp.maximum(m, m_cur)
        # A fully-masked step contributes nothing; keep m finite so the
        # correction exp() stays well-defined.
        m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(s == NEG_INF, 0.0, p)
        corr = jnp.exp(jnp.where(m == NEG_INF, NEG_INF, m - m_safe))
        l_new = corr * l + jnp.sum(p, axis=-1)
        pv = jnp.einsum("tkgs,skh->tkgh", p.astype(v_cur.dtype), v_cur)
        acc_new = acc * corr[..., None] + pv.astype(jnp.float32)
        # Rotate kv to the next device (XLA: ICI neighbor exchange).
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, m_new, l_new, acc_new)

    # Constants start replicated under shard_map; the carry becomes
    # device-varying after step 1, so the loop types must match up front.
    def varying(x):
        return lax.pcast(x, (axis_name,), to="varying")

    m0 = varying(jnp.full((Tc, KVH, G), NEG_INF, jnp.float32))
    l0 = varying(jnp.zeros((Tc, KVH, G), jnp.float32))
    acc0 = varying(jnp.zeros((Tc, KVH, G, hd), jnp.float32))
    _, _, _, l, acc = lax.fori_loop(0, n, step, (k, v, m0, l0, acc0))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(Tc, H, hd).astype(q.dtype)


def ring_prefill(
    mesh: Mesh,
    axis_name: str,
    q: jax.Array,  # [T, H, hd] — full sequence (sharded or to-be-sharded)
    k: jax.Array,  # [T, KVH, hd]
    v: jax.Array,
    *,
    causal: bool = True,
) -> jax.Array:
    """Causal attention for a long sequence sharded over ``axis_name``.
    T must divide evenly by the axis size."""
    spec = P(axis_name, None, None)
    fn = jax.shard_map(
        functools.partial(ring_attention_local, axis_name=axis_name, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    sharding = NamedSharding(mesh, spec)
    return fn(
        jax.device_put(q, sharding),
        jax.device_put(k, sharding),
        jax.device_put(v, sharding),
    )
