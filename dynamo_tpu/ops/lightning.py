"""Lightning (linear) attention with a decaying matrix state: the chunked scan
of prefill and the one-step update of decode.

A head of width d carries ``S`` [d, d]: ``S_t = lambda S_{t-1} + k_t^T v_t`` and
``o_t = q_t S_t`` (``q`` arrives already times ``d ** -0.5``). ``S`` is float32
wherever it is worked on: every product that reads or makes it is at
``Precision.HIGHEST`` (the XLA forms) or on the vector unit in float32 (the
kernel), and a prefill carries it in float32 from chunk to chunk. It is rounded
once where it comes to rest, to the pool's dtype (the cache's: bfloat16 as the
pages are, where the model is served in bfloat16), after a prefill's last
token and after each decode step.

- ``lightning_prefill``: C tokens at a time (``chunk``: a divisor of the
  cache's block, so that every block boundary is a chunk's end and the state
  there can be kept as a snapshot). With ``a_i`` the log-decay summed
  up to and including token i of the chunk (0 where a token is padding, which
  then neither decays the state nor adds to it):
  ``o_i = sum_{j<=i} exp(a_i - a_j) (q_i . k_j) v_j + exp(a_i) q_i S_prev`` and
  ``S_new = exp(a_C) S_prev + sum_j exp(a_C - a_j) k_j^T v_j``. The decay
  matrix is made from differences, never as ``exp(a_i) exp(-a_j)``: the
  fastest head's ``exp(-a_j)`` overflows float32 inside one chunk. An XLA
  scan over chunks: 4Cd + 4d^2 operations a token and head, half a percent of
  the model's at the published widths, so no kernel (PERF.md section 6).
- ``lightning_decode``: a Pallas kernel over (row, head group) that reads a
  row's state out of the pool at its read slot, decays it, adds ``k^T v``,
  multiplies ``q`` through and writes it to the row's write slot of the same
  pool in place (the pool is aliased to the output). Read and write slot
  differ on the first step of a new block: the state the block before ended
  with stays behind as that block's snapshot (block_manager/pool.py). The XLA
  form gathers ``[B, H, d, d]`` out of the pool and scatters it back.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HI = lax.Precision.HIGHEST
PREFILL_CHUNK = 128
_HEADS_PER_STEP = 8  # 8 x d x d values of state a grid step: 256 KB in bfloat16 at d = 128


def log_decay(num_heads: int) -> jax.Array:
    """``log lambda_h = -2 ** (-8 (h + 1) / H)`` (Lightning Attention-2)."""
    h = jnp.arange(1, num_heads + 1, dtype=jnp.float32)
    return -jnp.exp2(-8.0 * h / num_heads)


def lightning_prefill(q, k, v, s0, n_valid, snap_at=None, chunk: int = PREFILL_CHUNK):
    """q, k, v [B, T, H, d] (q times its scale), ``s0`` [B, H, d, d] float32,
    ``n_valid`` [B] tokens of each row that are real, ``snap_at`` [B, S]
    multiples of ``chunk`` a row (0: none) → (o [B, T, H, d] in q's dtype, the
    state after each row's last real token, the states [B, S, H, d, d] after
    its first ``snap_at`` tokens)."""
    B, T, H, d = q.shape
    C = math.gcd(T, chunk)
    n = T // C
    if snap_at is None:
        snap_at = jnp.zeros((B, 1), jnp.int32)
    real = jnp.arange(T, dtype=jnp.int32)[None, :] < n_valid[:, None]                # [B, T]
    g = jnp.where(real[..., None], log_decay(H)[None, None, :], 0.0).reshape(B, n, C, H)
    a = jnp.cumsum(g, axis=2)                                                        # [B, n, C, H]
    chunks = lambda x: jnp.moveaxis(x.reshape(B, n, C, H, d), 1, 0)  # noqa: E731
    tri = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]

    def step(carry, xs):
        s, snap = carry
        qc, kc, vc, ac, rc, done = xs                             # [B, C, H, d] x3, [B, C, H], [B, C], tokens so far
        diff = ac[:, :, None, :] - ac[:, None, :, :]              # [B, i, j, H]
        decay = jnp.where(tri[None, :, :, None], jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
        scores = jnp.einsum("bihd,bjhd->bijh", qc, kc, preferred_element_type=jnp.float32) * decay
        o = jnp.einsum("bijh,bjhd->bihd", scores.astype(vc.dtype), vc,
                       preferred_element_type=jnp.float32)
        qs = qc.astype(jnp.float32) * jnp.exp(ac)[..., None]
        o = o + jnp.einsum("bihd,bhde->bihe", qs, s, precision=HI)
        last = ac[:, -1]                                          # [B, H]
        kw = kc.astype(jnp.float32) * (jnp.exp(last[:, None] - ac) * rc[..., None])[..., None]
        s = jnp.exp(last)[..., None, None] * s + jnp.einsum(
            "bjhd,bjhe->bhde", kw, vc.astype(jnp.float32), precision=HI)
        snap = jnp.where((snap_at == done)[:, :, None, None, None], s[:, None], snap)
        return (s, snap), o.astype(qc.dtype)

    xs = (chunks(q), chunks(k), chunks(v), jnp.moveaxis(a, 1, 0),
          jnp.moveaxis(real.reshape(B, n, C), 1, 0).astype(jnp.float32),
          jnp.arange(1, n + 1, dtype=jnp.int32) * C)
    s0 = s0.astype(jnp.float32)
    (s, snap), o = lax.scan(step, (s0, jnp.repeat(s0[:, None], snap_at.shape[1], axis=1)), xs)
    return jnp.moveaxis(o, 0, 1).reshape(B, T, H, d), s, snap


def lightning_decode_xla(q, k, v, pool, layer, read, write):
    """q, k, v [B, H, d]; ``pool`` [layers, slots, H, d, d]; ``read`` and
    ``write`` [B] slots → (o [B, H, d] float32, the pool)."""
    H = q.shape[1]
    s = pool[layer, read].astype(jnp.float32)                                        # [B, H, d, d]
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    s = jnp.exp(log_decay(H))[None, :, None, None] * s + kf[..., :, None] * vf[..., None, :]
    o = jnp.einsum("bhd,bhde->bhe", q.astype(jnp.float32), s, precision=HI)
    return o, pool.at[layer, write].set(s.astype(pool.dtype))


def _step_kernel(layer_ref, read_ref, write_ref, q_ref, k_ref, v_ref, lam_ref, s_ref, o_ref, s_out_ref):
    del layer_ref, read_ref, write_ref  # the index maps' alone
    s = lam_ref[...] * s_ref[...].astype(jnp.float32) + k_ref[...] * v_ref[...]  # [hb, d, 1] x [hb, 1, d] → [hb, d, d]
    s_out_ref[...] = s.astype(s_out_ref.dtype)
    o_ref[...] = jnp.sum(q_ref[...] * s, axis=1, keepdims=True)    # [hb, 1, d]


@functools.partial(jax.jit, static_argnames=("interpret",))
def lightning_decode(q, k, v, pool, layer, read, write, *, interpret: bool = False):
    """``lightning_decode_xla`` as a kernel: the state moves pool → VMEM →
    pool once, whatever the batch."""
    B, H, d = q.shape
    hb = math.gcd(H, _HEADS_PER_STEP)
    f32 = jnp.float32
    col = lambda x: x.astype(f32).reshape(B, H, d, 1)  # noqa: E731
    lam = jnp.exp(log_decay(H)).reshape(H, 1, 1)

    def vec(shape):  # a row's head group of q, k or v
        return pl.BlockSpec((None, hb, *shape), lambda b, h, *_: (b, h, 0, 0))

    def state(slots):  # 0 the layer, 1 the read slots, 2 the write slots
        return pl.BlockSpec((None, None, hb, d, d),
                            lambda b, h, *pre: (pre[0][0], pre[slots][b], h, 0, 0))

    o, pool = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, H // hb),
            in_specs=[vec((d, 1)), vec((d, 1)), vec((1, d)),
                      pl.BlockSpec((hb, 1, 1), lambda b, h, *_: (h, 0, 0)), state(1)],
            out_specs=[vec((1, d)), state(2)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, 1, d), f32), jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={7: 1},  # the pool, after three prefetched scalars and q, k, v, lambda
        name="lightning_decode",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), read.astype(jnp.int32), write.astype(jnp.int32),
      col(q), col(k), v.astype(f32).reshape(B, H, 1, d), lam, pool)
    return o.reshape(B, H, d), pool
