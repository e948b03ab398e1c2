"""Serialization: cloudpickle + pickle-5 out-of-band buffers.

TPU-native analog of the reference's SerializationContext
(/root/reference/python/ray/_private/serialization.py:162): cloudpickle for
closures/classes, protocol-5 out-of-band buffers so numpy arrays round-trip
zero-copy through the shared-memory store, and custom reducers for ObjectRef /
ActorHandle (serialization.py:192-241) that record contained references for
dependency tracking and distributed refcounting (borrowing).

TPU twist: ``jax.Array`` values are serialized as host numpy with a device-
residency tag, so a ``get`` on a TPU host can ``device_put`` straight into HBM
(SURVEY.md §7 phase 2).
"""

from __future__ import annotations

import io
import pickle
import threading
from dataclasses import dataclass, field
from typing import Any, Callable

import cloudpickle

from ray_tpu.core.ids import ObjectID
from ray_tpu.core.object_ref import ObjectRef

_JAX_ARRAY_TAG = "__ray_tpu_jax_array__"


@dataclass
class SerializedObject:
    """Pickled payload + out-of-band buffers + contained refs."""

    inband: bytes
    buffers: list  # list of objects supporting the buffer protocol
    contained_refs: list[ObjectRef] = field(default_factory=list)

    def total_bytes(self) -> int:
        return len(self.inband) + sum(len(memoryview(b).cast("B")) for b in self.buffers)

    # --- flat wire/storage format -------------------------------------
    # [u32 nbufs][u64 inband_len][u64 buf_len]*nbufs [inband][pad to 64][buf
    # (64-aligned)]...  Buffer alignment lets readers map numpy arrays
    # zero-copy from shared memory.
    HEADER_ALIGN = 64

    def to_bytes(self) -> bytes:
        out = io.BytesIO()
        self.write_into(out)
        return out.getvalue()

    def write_into(self, out) -> int:
        nbufs = len(self.buffers)
        views = [memoryview(b).cast("B") for b in self.buffers]
        out.write(nbufs.to_bytes(4, "little"))
        out.write(len(self.inband).to_bytes(8, "little"))
        for v in views:
            out.write(len(v).to_bytes(8, "little"))
        out.write(self.inband)
        written = 4 + 8 + 8 * nbufs + len(self.inband)
        for v in views:
            pad = (-written) % self.HEADER_ALIGN
            out.write(b"\x00" * pad)
            out.write(v)
            written += pad + len(v)
        return written

    def serialized_size(self) -> int:
        nbufs = len(self.buffers)
        size = 4 + 8 + 8 * nbufs + len(self.inband)
        for b in self.buffers:
            size += (-size) % self.HEADER_ALIGN
            size += len(memoryview(b).cast("B"))
        return size

    @classmethod
    def from_buffer(cls, buf) -> "SerializedObject":
        """Zero-copy parse: returned buffers are views into ``buf``."""
        mv = memoryview(buf).cast("B")
        nbufs = int.from_bytes(mv[:4], "little")
        inband_len = int.from_bytes(mv[4:12], "little")
        off = 12
        lens = []
        for _ in range(nbufs):
            lens.append(int.from_bytes(mv[off:off + 8], "little"))
            off += 8
        inband = bytes(mv[off:off + inband_len])
        off += inband_len
        buffers = []
        for ln in lens:
            off += (-off) % cls.HEADER_ALIGN
            buffers.append(mv[off:off + ln])
            off += ln
        return cls(inband=inband, buffers=buffers)


class SerializationContext:
    """Per-runtime serializer. Thread-safe."""

    def __init__(self, runtime=None):
        self._runtime = runtime
        self._local = threading.local()
        self._custom_serializers: dict[type, tuple[Callable, Callable]] = {}
        self._static_dispatch: type | None = None  # pickler cls, lazily built

    def register_serializer(self, cls: type, *, serializer: Callable, deserializer: Callable):
        """Custom per-type serializer (ref: ray.util.register_serializer)."""
        self._custom_serializers[cls] = (serializer, deserializer)
        self._static_dispatch = None

    def _pickler_class(self) -> type:
        """One pickler subclass per context, rebuilt only when a custom
        serializer registers or jax first appears. The C pickler snapshots
        `dispatch_table` at construction from the CLASS, so per-call state
        (contained refs) flows through a thread-local instead of closures —
        building a fresh class per serialize() was the old hot-path cost."""
        jnp_array_types = _jax_array_types()
        cached = self._static_dispatch
        if cached is not None and (not jnp_array_types
                                   or jnp_array_types[0] in cached.dispatch_table):
            return cached
        table = dict(getattr(cloudpickle.CloudPickler, "dispatch_table", {}))
        table[ObjectRef] = _reduce_ref_tl
        for t in jnp_array_types:
            table[t] = _reduce_jax_array
        for t, (ser, des) in self._custom_serializers.items():
            table[t] = lambda obj, ser=ser, des=des: (
                _deserialize_custom, (cloudpickle.dumps(des), ser(obj)))
        cls = type("_CtxPickler", (cloudpickle.CloudPickler,),
                   {"dispatch_table": table})
        self._static_dispatch = cls
        return cls

    # ------------------------------------------------------------------
    def serialize(self, value: Any) -> SerializedObject:
        buffers: list = []
        contained: list[ObjectRef] = []
        cls = self._pickler_class()
        sio = io.BytesIO()
        p = cls(sio, protocol=5,
                buffer_callback=lambda b: buffers.append(b.raw()))
        stack = getattr(_ser_tl, "stack", None)
        if stack is None:
            stack = _ser_tl.stack = []
        stack.append((contained, self._runtime))
        try:
            p.dump(value)
        finally:
            stack.pop()
        return SerializedObject(inband=sio.getvalue(), buffers=buffers, contained_refs=contained)

    def deserialize(self, sobj: SerializedObject) -> Any:
        _deser_ctx.runtime = self._runtime
        try:
            return pickle.loads(sobj.inband, buffers=sobj.buffers)
        finally:
            _deser_ctx.runtime = None


class _DeserCtx(threading.local):
    runtime = None


_deser_ctx = _DeserCtx()
_ser_tl = threading.local()  # serialize() call state: [(contained, runtime)]


def _reduce_ref_tl(ref: ObjectRef):
    contained, runtime = _ser_tl.stack[-1]
    contained.append(ref)
    if runtime is not None:
        runtime.reference_counter.add_borrow_on_serialize(ref)
    return (_deserialize_ref_in_context, (ref.id(), ref.owner, ref.owner_addr))


def _deserialize_ref_in_context(object_id: ObjectID, owner, owner_addr):
    ref = ObjectRef(object_id, owner, owner_addr)
    rt = _deser_ctx.runtime
    if rt is not None:
        rt.reference_counter.on_ref_deserialized(ref)
    return ref


def _deserialize_custom(pickled_deserializer: bytes, payload):
    return cloudpickle.loads(pickled_deserializer)(payload)


def _jax_array_types() -> tuple:
    """jax.Array, but ONLY if jax is already imported: a value cannot be a
    jax.Array otherwise, and importing jax here would add ~2s to the first
    serialize in every CPU worker (and could grab TPU chips as a side
    effect — SURVEY.md §7 hard-part 7)."""
    import sys
    if "jax" not in sys.modules:
        return ()
    try:
        import jax
        return (jax.Array,)
    except Exception:
        return ()


def _reduce_jax_array(arr):
    """jax.Array → host numpy + sharding tag. On deserialize we return numpy;
    consumers that want device placement use ray_tpu.util device_get semantics
    or the train/data iterators, which device_put with the recorded sharding."""
    import numpy as np
    host = np.asarray(arr)
    return (_restore_jax_array, (host, str(arr.dtype), True))


def _restore_jax_array(host, dtype, committed):
    # device_put only where it cannot TAKE a chip: TPU chips admit a single
    # attached process (SURVEY.md §7 hard-part 7), and a process that has
    # merely imported jax — a driver that must stay off the chip — would
    # grab it on its first get() of an array. So: a backend this process
    # already initialised, or a process pinned to the CPU platform.
    import sys
    jax = sys.modules.get("jax")
    if jax is None:
        return host
    from jax._src import xla_bridge

    from ray_tpu.core.cpu_env import pinned_to_cpu
    if xla_bridge.backends_are_initialized() or pinned_to_cpu():
        return jax.device_put(host)
    return host
