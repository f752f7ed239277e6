"""KV page movement: device↔host extraction/injection of paged-cache
blocks, and the host-side wire format.

This is the TPU-native v0 of the reference's NIXL KV data plane
(reference: lib/llm/src/block_manager/storage/nixl.rs, docs/architecture/
kvbm_architecture.md:30-44). GPUs move KV with RDMA; on TPU the
equivalents are host DMA (device_get / device_put) for HBM↔host and the
runtime's TCP response plane for host↔host. The same primitives back
both disaggregated prefill→decode handoff and the G2 host offload tier.

Layout: pages travel as ``[L, n, bs, KVH*hd]`` pairs (k, v). The cache
keeps a page's K and V side by side in one pool (``KVCache.kv`` ``[L, N, 2,
bs, KVH*hd]``, one DMA descriptor a page for the kernels); the split
happens here, after the one gather, and the join before the one scatter,
so the wire, the tiers and a peer that runs the two-pool layout see the
bytes they always saw. ``n`` is bucketed pow2 (block id 0 is the garbage
sink, so padding injects harmlessly).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.model import KVCache, fuse_kv, split_kv


def _bucket(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def _wire_pages(pools: tuple, ids: jax.Array) -> tuple:
    """The named pages of ``_pools`` in wire order: each page gathered once,
    then split → (k, v[, k_scale, v_scale]), each [L, n, bs, ...]."""
    kv, *scales = pools
    return (*split_kv(kv[:, ids]), *(s[:, ids] for s in scales))


_extract_impl = jax.jit(_wire_pages)


_extract_replicated_jits: dict = {}


def _extract_replicated(arrs: tuple, ids, sharding):
    """Extract with fully-replicated outputs: on a multi-host mesh every
    process must be able to np.asarray the result (a KVH-sharded gather
    would leave shards non-addressable)."""
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = sharding.mesh
    key = (id(mesh), len(arrs))
    fn = _extract_replicated_jits.get(key)
    if fn is None:
        rep = NamedSharding(mesh, PartitionSpec())
        fn = jax.jit(_wire_pages, out_shardings=(rep,) * (len(arrs) + 1))
        _extract_replicated_jits[key] = fn
    return fn(arrs, ids)


@functools.partial(jax.jit, donate_argnums=(0,))
def _inject_impl(pools: tuple, ids: jax.Array, pages: tuple):
    """Wire-order ``pages`` into the named blocks of ``_pools``: K and V
    joined into whole pages first, then one scatter a pool."""
    kv, *scales = pools
    k, v, *scale_pages = pages
    return (kv.at[:, ids].set(fuse_kv(k, v)),
            *(s.at[:, ids].set(p) for s, p in zip(scales, scale_pages)))


def _pools(cache: KVCache) -> tuple:
    """The cache's page-parallel pools: (kv,), or (kv, k_scale, v_scale)
    for int8 storage. What every tier/transfer hop moves is their pages in
    wire order, (k, v) or (k, v, k_scale, v_scale) — int8 pages ship at
    half the bf16 bytes plus a ~3% scale sidecar."""
    if cache.k_scale is not None:
        return (cache.kv, cache.k_scale, cache.v_scale)
    return (cache.kv,)


def start_extract(cache: KVCache, block_ids: list[int], replicate=None) -> tuple:
    """Dispatch the page gather WITHOUT syncing → (device arrays, each
    [L, n_bucket, bs, ...], true block count n). The gather is enqueued
    on the device stream BEFORE any later donating dispatch, so it reads
    the pre-donation values; the caller harvests with ``finish_extract``
    once ``host_ready`` (engine/runner.py) reports the async D2H copy
    done. This is what lets the streaming KV exporter overlap page
    copies with the remaining prefill chunks."""
    n = len(block_ids)
    nb = _bucket(n)
    ids = np.zeros((nb,), np.int32)
    ids[:n] = block_ids
    arrs = _pools(cache)
    if replicate is not None:
        out = _extract_replicated(arrs, jnp.asarray(ids), replicate)
    else:
        out = _extract_impl(arrs, jnp.asarray(ids))
    return out, n


def finish_extract(device_pages: tuple, n: int) -> tuple:
    """Sync a ``start_extract`` result → host numpy pages [L, n, ...]."""
    return tuple(np.asarray(p[:, :n]) for p in device_pages)


def extract_pages(cache: KVCache, block_ids: list[int], replicate=None) -> tuple:
    """Copy the named blocks to host → (k, v) numpy pages, each
    [L, n, bs, KVH*hd] — plus (k_scale, v_scale) [L, n, bs, KVH] when the
    cache stores int8. Must run before the cache is donated to a later
    step (i.e. on the engine thread, synchronously). Pass the
    ModelSharding as ``replicate`` on a sharded cache so the gather
    all-gathers to every host."""
    out, n = start_extract(cache, block_ids, replicate)
    return finish_extract(out, n)


def inject_pages(cache: KVCache, block_ids: list[int], *pages) -> KVCache:
    """Write host pages into the named blocks (donates the cache).
    ``pages`` is the tuple ``extract_pages`` produced: (k, v) or
    (k, v, k_scale, v_scale); the arity must match the cache's storage
    format (adapt_pages converts foreign payloads first)."""
    arrs = _pools(cache)
    if len(pages) != len(arrs) + 1:
        raise ValueError(
            f"page payload arity {len(pages)} does not match cache storage "
            f"({'int8' if cache.k_scale is not None else 'dense'}); "
            f"adapt_pages() the payload first"
        )
    n = len(block_ids)
    assert all(p.shape[1] == n for p in pages), "page count mismatch"
    nb = _bucket(n)
    ids = np.zeros((nb,), np.int32)  # pad → block 0 (garbage sink)
    ids[:n] = block_ids
    if nb != n:
        pages = tuple(
            np.pad(p, [(0, 0), (0, nb - n)] + [(0, 0)] * (p.ndim - 2))
            for p in pages
        )
    # k and v both take the pages' dtype, each scale page its pool's.
    dev = tuple(
        jnp.asarray(p, a.dtype) for p, a in zip(pages, (arrs[0], *arrs))
    )
    out = _inject_impl(arrs, jnp.asarray(ids), dev)
    return KVCache(*out)


def delta_blocks(kv_written: int, block_size: int, cursor: int, n_blocks: int) -> tuple[int, int]:
    """→ ``(lo, hi)`` — the full-block delta a live migration still has to
    ship: blocks ``[cursor, hi)`` where ``hi`` counts only positions whose
    KV is actually written (``kv_written``), clamped to the allocated
    block list. Shared by the engine's migration pump and its cutover
    delta pass so the cursor arithmetic is single-sourced: the source
    keeps decoding while chunks stream, and each pump call extracts
    exactly the blocks sealed since the previous cursor."""
    hi = min(kv_written // block_size, n_blocks)
    return cursor, max(hi, cursor)


def quantize_pages_np(k: np.ndarray, v: np.ndarray, num_kv_heads: int):
    """Host-side int8 quantization of float pages [L, n, bs, KVH*hd] →
    (k int8, v int8, k_scale f32 [L, n, bs, KVH], v_scale f32). Same
    absmax scheme (and the same round-half-even) as model.kv_quantize,
    so a page quantized on the host matches one quantized on device —
    heterogeneous fleets (float prefill worker → int8 decode worker)
    stay consistent."""
    def one(x):
        L, n, bs, D = x.shape
        hd = D // num_kv_heads
        xf = np.asarray(x, np.float32).reshape(L, n, bs, num_kv_heads, hd)
        absmax = np.max(np.abs(xf), axis=-1)
        scale = np.where(absmax > 0, absmax, 127.0) / 127.0
        q = np.clip(np.rint(xf / scale[..., None]), -127, 127).astype(np.int8)
        return q.reshape(L, n, bs, D), scale.astype(np.float32)

    kq, ks = one(k)
    vq, vs = one(v)
    return kq, vq, ks, vs


def dequantize_pages_np(k, v, k_scale, v_scale, num_kv_heads: int, dtype):
    """Inverse adapter: int8 pages + scales → float pages in ``dtype``."""
    def one(q, s):
        L, n, bs, D = q.shape
        hd = D // num_kv_heads
        x = q.reshape(L, n, bs, num_kv_heads, hd).astype(np.float32) * s[..., None]
        return x.reshape(L, n, bs, D).astype(dtype)

    return one(k, k_scale), one(v, v_scale)


def _dense_dtype(name):
    """Numpy dtype for a dense-page dtype name (bf16 via ml_dtypes)."""
    if str(name) == "bfloat16":
        import ml_dtypes

        return ml_dtypes.bfloat16
    return np.dtype(str(name))


def adapt_pages(pages: tuple, cache: KVCache, num_kv_heads: int) -> tuple:
    """Convert a page tuple to the cache's storage format: quantize
    float payloads for an int8 cache, dequantize int8 payloads for a
    float cache, pass matching formats through untouched."""
    quant_payload = len(pages) == 4
    quant_cache = cache.k_scale is not None
    if quant_payload == quant_cache:
        return pages
    if quant_cache:
        return quantize_pages_np(pages[0], pages[1], num_kv_heads)
    return dequantize_pages_np(
        *pages, num_kv_heads=num_kv_heads, dtype=_dense_dtype(cache.kv.dtype)
    )


def concat_page_run(
    run: list, *, quantized: bool, num_kv_heads: int, dtype
) -> tuple:
    """Concatenate a tier run's per-block page tuples into ONE batched
    payload in the requested storage format: (k, v) when ``quantized`` is
    False, (k, v, k_scale, v_scale) when True. A persistent disk tier can
    hold blocks written under a different ``kv_quant`` setting than this
    process (a dense-era ``--disk-kv-dir`` reused by an int8 worker, or
    vice versa), so a single leading run may MIX arities — each block is
    bridged to the engine's current format first, after which inject /
    adapt_pages see one uniform tuple. ``dtype`` is the dense page dtype
    (name or numpy dtype) used when dequantizing foreign int8 blocks."""
    want = 4 if quantized else 2
    norm = []
    for blk in run:
        if len(blk) == want:
            norm.append(blk)
        elif quantized:
            norm.append(quantize_pages_np(blk[0], blk[1], num_kv_heads))
        else:
            norm.append(dequantize_pages_np(
                *blk, num_kv_heads=num_kv_heads, dtype=_dense_dtype(dtype)
            ))
    return tuple(
        np.concatenate([blk[i] for blk in norm], axis=1) for i in range(want)
    )


def split_page_run(pages: tuple, n_blocks: int) -> list[tuple]:
    """Inverse of :func:`concat_page_run`: slice one batched page tuple
    ([L, n_blocks, bs, …] on every array) back into per-block tuples
    ([L, 1, bs, …]) for individual tier puts — the drain-on-retire
    receiver stores each adopted block under its own hash."""
    return [
        tuple(np.ascontiguousarray(p[:, i : i + 1]) for p in pages)
        for i in range(n_blocks)
    ]


# ---------------------------------------------------------------------------
# Wire format (msgpack-safe dicts with raw bytes)
# ---------------------------------------------------------------------------


@dataclass
class KvPagePayload:
    """Host KV pages + metadata, serializable over the response plane.
    int8 pages carry fp32 scale sidecars (``k_scale``/``v_scale``,
    [L, n, bs, KVH]) — the disagg/peer wire then moves roughly HALF the
    bf16 bytes per block."""

    k: np.ndarray  # [L, n, bs, KVH*hd]
    v: np.ndarray
    num_tokens: int  # prompt positions covered by these pages
    k_scale: np.ndarray | None = None  # [L, n, bs, KVH] fp32 — int8 pages only
    v_scale: np.ndarray | None = None

    def pages(self) -> tuple:
        """The page tuple in engine wire order (kv_transfer inject/
        adapt_pages arity): (k, v) or (k, v, k_scale, v_scale)."""
        if self.k_scale is not None:
            return (self.k, self.v, self.k_scale, self.v_scale)
        return (self.k, self.v)

    @classmethod
    def from_pages(cls, pages: tuple, num_tokens: int) -> "KvPagePayload":
        """Inverse of ``pages()``: wrap an extract_pages/concat_page_run
        tuple — (k, v) or (k, v, k_scale, v_scale) — in a payload."""
        ks, vs = (pages[2], pages[3]) if len(pages) == 4 else (None, None)
        return cls(k=pages[0], v=pages[1], num_tokens=num_tokens,
                   k_scale=ks, v_scale=vs)

    def to_dict(self) -> dict:
        # bf16 numpy (ml_dtypes) round-trips via uint16 view.
        k, v = self.k, self.v
        kind = str(k.dtype)
        if kind == "bfloat16":
            k, v = k.view(np.uint16), v.view(np.uint16)
        out = {
            "k": k.tobytes(),
            "v": v.tobytes(),
            "shape": list(self.k.shape),
            "dtype": kind,
            "num_tokens": self.num_tokens,
        }
        if self.k_scale is not None:
            out["k_scale"] = np.ascontiguousarray(self.k_scale).tobytes()
            out["v_scale"] = np.ascontiguousarray(self.v_scale).tobytes()
            out["scale_shape"] = list(self.k_scale.shape)
        return out

    @classmethod
    def from_dict(cls, d: dict) -> "KvPagePayload":
        import ml_dtypes

        shape = tuple(d["shape"])
        kind = d["dtype"]
        if kind == "bfloat16":
            k = np.frombuffer(d["k"], np.uint16).reshape(shape).view(ml_dtypes.bfloat16)
            v = np.frombuffer(d["v"], np.uint16).reshape(shape).view(ml_dtypes.bfloat16)
        else:
            k = np.frombuffer(d["k"], np.dtype(kind)).reshape(shape)
            v = np.frombuffer(d["v"], np.dtype(kind)).reshape(shape)
        ks = vs = None
        if d.get("k_scale") is not None:
            sshape = tuple(d["scale_shape"])
            ks = np.frombuffer(d["k_scale"], np.float32).reshape(sshape)
            vs = np.frombuffer(d["v_scale"], np.float32).reshape(sshape)
        return cls(k=k, v=v, num_tokens=int(d["num_tokens"]),
                   k_scale=ks, v_scale=vs)

    # -- chunked streaming --------------------------------------------------
    #
    # A 70B-geometry 2k-token export is ~640 MB — far beyond the framing
    # cap (runtime/framing.py MAX_FRAME) and big enough to stall an event
    # loop if serialized at once. Streams of <=max_bytes frames keep the
    # response plane responsive (reference analogue: NIXL moves KV in
    # block-granular RDMA ops, not one giant message).

    DEFAULT_FRAME_BYTES = 16 << 20

    def to_frames(self, max_bytes: int = DEFAULT_FRAME_BYTES):
        """Yield wire frames: one header, then <=max_bytes data chunks.
        Scale sidecars travel as their own small frames after the pages
        (absent for full-precision payloads, so the wire format is
        backward compatible)."""
        k, v = self.k, self.v
        kind = str(k.dtype)
        if kind == "bfloat16":
            k, v = k.view(np.uint16), v.view(np.uint16)
        kb, vb = k.tobytes(), v.tobytes()
        header = {
            "kind": "kv_header",
            "shape": list(self.k.shape),
            "dtype": kind,
            "num_tokens": self.num_tokens,
            "k_bytes": len(kb),
            "v_bytes": len(vb),
        }
        chunks = [("k", kb), ("v", vb)]
        if self.k_scale is not None:
            ksb = np.ascontiguousarray(self.k_scale).tobytes()
            vsb = np.ascontiguousarray(self.v_scale).tobytes()
            header["scale_shape"] = list(self.k_scale.shape)
            header["k_scale_bytes"] = len(ksb)
            header["v_scale_bytes"] = len(vsb)
            chunks += [("k_scale", ksb), ("v_scale", vsb)]
        yield header
        for name, buf in chunks:
            for off in range(0, len(buf), max_bytes):
                yield {"kind": name, "data": buf[off : off + max_bytes]}

    @classmethod
    def from_frames(cls, frames: list[dict]) -> "KvPagePayload":
        header = frames[0]
        if header.get("kind") != "kv_header":
            raise ValueError("first frame is not a kv_header")
        bufs = {
            name: b"".join(f["data"] for f in frames[1:] if f["kind"] == name)
            for name in ("k", "v", "k_scale", "v_scale")
        }
        want = {
            "k": header["k_bytes"], "v": header["v_bytes"],
            "k_scale": header.get("k_scale_bytes", 0),
            "v_scale": header.get("v_scale_bytes", 0),
        }
        for name, n in want.items():
            if len(bufs[name]) != n:
                raise ValueError(
                    f"truncated kv stream: {name} {len(bufs[name])}/{n}"
                )
        d = {
            "k": bufs["k"], "v": bufs["v"], "shape": header["shape"],
            "dtype": header["dtype"], "num_tokens": header["num_tokens"],
        }
        if header.get("scale_shape") is not None:
            d["k_scale"] = bufs["k_scale"]
            d["v_scale"] = bufs["v_scale"]
            d["scale_shape"] = header["scale_shape"]
        return cls.from_dict(d)
