"""KV page codec: compressed pages across tiers and the object-plane wire.

The KV tier ships raw pages — fp32/bf16 tensors whose size, not the
prefill FLOPs they replace, bounds how many prefix tokens the shm/disk
tiers hold and how long a cross-replica restore spends on the wire.
CacheGen (PAPERS.md) showed codec-compressed KV beats both recompute and
raw transfer; this module is the per-page codec the tier applies at
spill time and undoes at restore:

- ``lossless`` (the engine default): byte-plane shuffle + DEFLATE. The
  page's bytes are regrouped so every element's Nth byte is contiguous
  — for floating KV that clusters the sign/exponent bytes (low entropy:
  activations live in a narrow dynamic range) away from the near-random
  mantissa bytes, which is what gives a generic entropy coder runs to
  work with. Decoding is bit-exact by construction, so the greedy
  token-identity invariant every KV feature has shipped with holds
  unchanged. The ratio is data-dependent: narrow-range bf16 KV
  compresses hard, full-mantissa fp32 from random-init weights is
  entropy-bound near 1x on its mantissa planes.
- ``int8`` (opt-in): per-(layer, kv-head) symmetric scale quantization to
  int8, then DEFLATE over the quantized planes. 4x from the width cut on fp32
  before entropy coding; reconstruction error is bounded per element by
  ``amax / 127`` within its (layer, head) group. NOT bit-exact — greedy
  outputs can diverge, which is why it is off by default.
- ``none``: identity passthrough (the PR 7 raw-page wire format). Kept
  so a codec rollout can mix replicas: the tier's read path accepts
  both raw and encoded blobs regardless of its own write mode.

Pages encode independently (one payload per [L, Hkv, 1, page, D] slice)
so a chunked restore stream can decode exactly the pages that landed.
Tensor-parallel engines (ISSUE 20) spill the pool per-KV-head-sharded:
``encode_pages(..., shards=N)`` splits every page along the KV-head axis
into N independently-encoded sub-payloads carried inside ONE page
payload (``mode="shards"``) under one chain digest — a restoring TP
engine decodes each shard's bytes separately and lands them on the
owning chip, while decode_page/decode_pages reassemble the full page
for anyone who wants the unsharded view. Shard payloads only ever meet
readers that understand them: the tier namespace embeds the sharding
layout (`|tp{N}`, engine.kv_tier_namespace), the same isolation rule
``|int8`` applies to quantized pages.
The BATCH entry points (:func:`encode_pages` / :func:`decode_pages` —
what the tier's spill flush and the ChainStream chunk decode call) keep
that per-page payload contract but vectorize all the numpy work across
the whole page batch: one page-major relayout, one fp32 cast + one
(layer, kv-head)-grid amax/quant pass, and ONE byte-plane transpose per
batch instead of one of each per page. Only the entropy-coder call stays
per page — per-page DEFLATE streams are what keep every payload
independently decodable (mixed-codec replica interop, partial chunk
restores), and the match search is a minority of encode time once the
array work is batched. Payloads are byte-identical either way.
Everything here is host-side numpy + zlib — no device work, no locks;
callers keep codec work off the engine and store locks.
"""

from __future__ import annotations

import zlib

import numpy as np

MODES = ("none", "lossless", "int8")

# DEFLATE effort. Level 1 is ~5x faster than the default 6 and within a
# few percent of its ratio on byte-plane-shuffled KV: the shuffle, not
# the match search, is what exposes the redundancy. Encode runs on the
# spill path (engine loop adjacent) so speed wins.
_ZLEVEL = 1


def _dtype(name: str) -> np.dtype:
    """Resolve a stored dtype name, including the ml_dtypes extension
    types (bfloat16 etc.) numpy alone can't name."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def _planes(a: np.ndarray) -> bytes:
    """Byte-plane shuffle: element-major bytes -> plane-major bytes."""
    buf = np.frombuffer(a.tobytes(), np.uint8)
    return np.ascontiguousarray(
        buf.reshape(-1, a.dtype.itemsize).T).tobytes()


def _unplanes(data: bytes, dt: np.dtype) -> bytes:
    planes = np.frombuffer(data, np.uint8).reshape(dt.itemsize, -1)
    return np.ascontiguousarray(planes.T).tobytes()


def encode_page(arr: np.ndarray, mode: str) -> dict:
    """Encode one page array. Returns a self-describing dict payload
    (what the tier stores and ships): ``mode``, ``data`` (compressed
    bytes), ``shape``, ``dtype`` (name), ``raw`` (original nbytes), and
    for int8 the per-group ``scale`` bytes + ``sshape``."""
    if mode not in MODES:
        raise ValueError(f"unknown KV codec mode {mode!r}")
    a = np.ascontiguousarray(arr)
    base = {"shape": tuple(a.shape), "dtype": str(a.dtype),
            "raw": int(a.nbytes)}
    if mode == "int8" and np.issubdtype(a.dtype, np.floating):
        f = a.astype(np.float32)
        # one symmetric scale per (layer, kv-head) group: page values
        # within a head share dynamic range, across heads they don't
        red = tuple(range(2, f.ndim)) if f.ndim > 2 \
            else tuple(range(f.ndim))
        s = np.max(np.abs(f), axis=red, keepdims=True)
        s = np.where(s == 0.0, 1.0, s).astype(np.float32)
        q = np.clip(np.rint(f / s * 127.0), -127, 127).astype(np.int8)
        return {**base, "mode": "int8",
                "data": zlib.compress(q.tobytes(), _ZLEVEL),
                "scale": s.tobytes(), "sshape": tuple(s.shape)}
    if mode == "int8":
        mode = "lossless"   # integer KV: quantization buys nothing
    if mode == "lossless":
        return {**base, "mode": "lossless",
                "data": zlib.compress(_planes(a), _ZLEVEL)}
    return {**base, "mode": "none", "data": a.tobytes()}


def decode_page(enc: dict) -> np.ndarray:
    """Invert :func:`encode_page`. Bit-exact for none/lossless; int8
    reconstructs within ``scale/127`` per element. A ``"shards"``
    payload (TP spill) decodes each per-shard sub-payload and
    reassembles the full page along the KV-head axis."""
    mode = enc["mode"]
    if mode == "shards":
        return np.concatenate(
            [decode_page(s) for s in enc["shards"]], axis=1)
    dt = _dtype(enc["dtype"])
    shape = tuple(enc["shape"])
    if mode == "none":
        return np.frombuffer(enc["data"], dt).reshape(shape)
    if mode == "lossless":
        return np.frombuffer(
            _unplanes(zlib.decompress(enc["data"]), dt), dt).reshape(shape)
    if mode == "int8":
        q = np.frombuffer(zlib.decompress(enc["data"]),
                          np.int8).reshape(shape)
        s = np.frombuffer(enc["scale"], np.float32).reshape(enc["sshape"])
        return (q.astype(np.float32) * (s / 127.0)).astype(dt)
    raise ValueError(f"unknown KV codec mode {mode!r}")


def encoded_nbytes(enc: dict) -> int:
    """Stored/wire footprint of one encoded page payload."""
    if enc.get("mode") == "shards":
        return sum(encoded_nbytes(s) for s in enc["shards"])
    return len(enc["data"]) + len(enc.get("scale") or b"")


# ---------------------------------------------------------------------------
# batch entry points (ISSUE 18): vectorized twins of encode/decode_page
# ---------------------------------------------------------------------------


def _encode_batch(a: np.ndarray, mode: str) -> list[dict]:
    """Encode every page of ``a`` ([L, Hkv, n, page, D]) — payloads
    byte-identical to ``encode_page(a[:, :, i:i+1], mode)`` per page, but
    the relayout / cast / quant / byte-plane shuffle each run ONCE over
    the batch."""
    n = a.shape[2]
    # page-major contiguous copy: pm[i] holds exactly the bytes of
    # a[:, :, i:i+1] in C order (one relayout for the whole batch)
    pm = np.ascontiguousarray(np.moveaxis(a, 2, 0))     # [n, L, Hkv, pg, D]
    page_shape = (a.shape[0], a.shape[1], 1) + a.shape[3:]
    base = {"shape": page_shape, "dtype": str(a.dtype),
            "raw": int(a.nbytes // n)}
    if mode == "int8" and np.issubdtype(a.dtype, np.floating):
        f = pm.astype(np.float32)
        # same per-(layer, kv-head) groups as encode_page's axes (2..) on
        # the [L, Hkv, 1, page, D] slice — here (page, D) per batch entry
        s = np.max(np.abs(f), axis=(3, 4), keepdims=True)  # [n,L,Hkv,1,1]
        s = np.where(s == 0.0, 1.0, s).astype(np.float32)
        q = np.clip(np.rint(f / s * 127.0), -127, 127).astype(np.int8)
        sshape = (a.shape[0], a.shape[1], 1, 1, 1)
        return [{**base, "mode": "int8",
                 "data": zlib.compress(q[i], _ZLEVEL),
                 "scale": s[i].tobytes(), "sshape": sshape}
                for i in range(n)]
    if mode == "int8":
        mode = "lossless"   # integer KV: quantization buys nothing
    if mode == "lossless":
        # ONE byte-plane transpose for the whole batch (zero-copy uint8
        # view, no tobytes round-trip); per-page slices of the result are
        # the exact _planes() bytes of that page
        itemsize = a.dtype.itemsize
        buf = pm.view(np.uint8).reshape(n, -1, itemsize)
        planes = np.ascontiguousarray(buf.transpose(0, 2, 1))
        return [{**base, "mode": "lossless",
                 "data": zlib.compress(planes[i], _ZLEVEL)}
                for i in range(n)]
    return [{**base, "mode": "none", "data": pm[i].tobytes()}
            for i in range(n)]


def _shard_wrap(per_shard: list[list[dict]], full_shape, dtype,
                raw: int) -> list[dict]:
    """Zip per-shard payload lists into one ``mode="shards"`` payload per
    page: ``per_shard[s][i]`` is shard s of page i."""
    n = len(per_shard[0])
    return [{"mode": "shards", "shape": tuple(full_shape),
             "dtype": str(dtype), "raw": int(raw),
             "shards": [ps[i] for ps in per_shard]}
            for i in range(n)]


def encode_pages(k_np: np.ndarray, v_np: np.ndarray,
                 mode: str, shards: int = 1) -> list[tuple[dict, dict]]:
    """Batch-encode a spilled chain: k_np/v_np are [L, Hkv, n, page, D];
    returns ``[(ek, ev), ...]`` of length n, each payload byte-identical
    to the per-page :func:`encode_page` of that page slice.

    ``shards > 1`` (tensor-parallel spill, ISSUE 20) splits the KV-head
    axis into that many per-shard sub-payloads, each independently
    encoded/decodable, carried inside one ``mode="shards"`` page payload
    — one chain digest, per-shard blobs."""
    if mode not in MODES:
        raise ValueError(f"unknown KV codec mode {mode!r}")
    k = np.ascontiguousarray(k_np)
    v = np.ascontiguousarray(v_np)
    if shards <= 1:
        return list(zip(_encode_batch(k, mode), _encode_batch(v, mode)))
    if k.shape[1] % shards != 0:
        raise ValueError(
            f"{k.shape[1]} KV heads not divisible by {shards} shards")
    h = k.shape[1] // shards
    page_shape = (k.shape[0], k.shape[1], 1) + k.shape[3:]
    raw = k.nbytes // k.shape[2]
    ks = _shard_wrap(
        [_encode_batch(np.ascontiguousarray(
            k[:, s * h:(s + 1) * h]), mode) for s in range(shards)],
        page_shape, k.dtype, raw)
    vs = _shard_wrap(
        [_encode_batch(np.ascontiguousarray(
            v[:, s * h:(s + 1) * h]), mode) for s in range(shards)],
        page_shape, v.dtype, raw)
    return list(zip(ks, vs))


def decode_pages(encs: list[dict]) -> list[np.ndarray]:
    """Invert a batch of :func:`encode_page` payloads — same arrays as
    ``[decode_page(e) for e in encs]``, with the un-shuffle / dequant
    vectorized across the batch when the payloads are homogeneous (the
    tier always spills chains that way; a mixed batch — e.g. raw blobs
    from a pre-codec replica next to encoded ones — falls back to the
    per-page path)."""
    if not encs:
        return []
    first = encs[0]
    if first.get("mode") == "shards":
        # homogeneous sharded batch: vectorize per shard position, then
        # reassemble each page along the KV-head axis. A mixed batch
        # can't occur in practice (the namespace isolates layouts) but
        # degrades to the per-page path like any other mix.
        if all(e.get("mode") == "shards"
               and len(e["shards"]) == len(first["shards"])
               for e in encs):
            parts = [decode_pages([e["shards"][s] for e in encs])
                     for s in range(len(first["shards"]))]
            return [np.concatenate([p[i] for p in parts], axis=1)
                    for i in range(len(encs))]
        return [decode_page(e) for e in encs]
    homogeneous = all(
        e["mode"] == first["mode"] and e["dtype"] == first["dtype"]
        and tuple(e["shape"]) == tuple(first["shape"])
        and tuple(e.get("sshape") or ()) == tuple(first.get("sshape") or ())
        for e in encs)
    if not homogeneous or first["mode"] == "none":
        return [decode_page(e) for e in encs]
    n = len(encs)
    dt = _dtype(first["dtype"])
    shape = tuple(first["shape"])
    if first["mode"] == "lossless":
        elems = int(np.prod(shape))
        # un-shuffle by strided write straight into the output buffer —
        # each page's transpose lands in place, then one zero-copy dtype
        # view (the per-page path pays an extra contiguous+tobytes copy)
        flat = np.empty((n, elems, dt.itemsize), np.uint8)
        for i, e in enumerate(encs):
            flat[i] = np.frombuffer(
                zlib.decompress(e["data"]), np.uint8).reshape(
                dt.itemsize, elems).T
        out = flat.reshape(n, elems * dt.itemsize).view(dt).reshape(
            (n,) + shape)
        return [out[i] for i in range(n)]
    if first["mode"] == "int8":
        q = np.empty((n,) + shape, np.int8)
        s = np.empty((n,) + tuple(first["sshape"]), np.float32)
        for i, e in enumerate(encs):
            q[i] = np.frombuffer(zlib.decompress(e["data"]),
                                 np.int8).reshape(shape)
            s[i] = np.frombuffer(e["scale"], np.float32).reshape(
                e["sshape"])
        # ONE vectorized dequant across the (layer, kv-head) grid
        out = (q.astype(np.float32) * (s / 127.0)).astype(dt)
        return [out[i] for i in range(n)]
    return [decode_page(e) for e in encs]
