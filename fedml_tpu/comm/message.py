"""Message envelope for the real-distributed path.

Reference: fedml_core/distributed/communication/message.py:5-86 — a dict with
type/sender/receiver plus arbitrary params, pickled whole (tensors included)
over MPI (mpi_send_thread.py:27) or JSON'd over MQTT/gRPC. Here the envelope
keeps the same key names (``msg_type``/``sender``/``receiver`` and the
MSG_ARG_* constants) but the wire format is explicitly typed: a JSON header +
a raw little-endian array segment per tensor — never pickled objects. Model
payloads are (flat byte vector, leaf-descriptor) pairs produced by
``pack_pytree`` — leaves keep their native dtypes bit-exactly; the descriptor
records path/shape/dtype per leaf.

Framing is zero-copy on both sides (docs/PERFORMANCE.md "The server wire
path"): packing an already-contiguous array contributes a ``memoryview`` of
its buffer (no model bytes copied until a byte-oriented transport joins the
frame), and unpacking produces alignment-safe ``np.frombuffer`` views into
the received buffer, marked read-only so two receivers of one shared
broadcast buffer can never alias-write each other's model. The encode-once
broadcast primitive is :class:`FramedMessage`: one payload serialization per
fan-out, with the per-receiver header patched in place.
"""

from __future__ import annotations

import json
import struct
import threading
from typing import Any

import numpy as np

import jax


# --- wire-level stats --------------------------------------------------------
# Counts payload serializations (frames built with at least one array
# segment) so the encode-once contract is testable: a broadcast to N workers
# increments this ONCE; the legacy per-rank loop increments it N times.
# tools/wire_smoke.py reads these.

_WIRE_LOCK = threading.Lock()
_WIRE_STATS = {"payload_serializations": 0, "frames": 0}


def wire_stats() -> dict[str, int]:
    """Snapshot of the process-wide wire counters."""
    with _WIRE_LOCK:
        return dict(_WIRE_STATS)


def reset_wire_stats() -> None:
    with _WIRE_LOCK:
        for k in _WIRE_STATS:
            _WIRE_STATS[k] = 0


def _byte_view(a: np.ndarray) -> np.ndarray:
    """Flat uint8 view of a contiguous array — zero-copy reinterpretation
    (``ascontiguousarray`` is a no-op on already-contiguous input)."""
    return np.ascontiguousarray(a).reshape(-1).view(np.uint8)


class Message:
    # key names kept for reference parity (message.py:9-24)
    MSG_ARG_KEY_TYPE = "msg_type"
    MSG_ARG_KEY_SENDER = "sender"
    MSG_ARG_KEY_RECEIVER = "receiver"
    MSG_ARG_KEY_MODEL_PARAMS = "model_params"
    MSG_ARG_KEY_NUM_SAMPLES = "num_samples"
    MSG_ARG_KEY_CLIENT_INDEX = "client_idx"
    # protocol-shared header fields every manager family uses: the model
    # structure descriptor (pack_pytree), the authoritative round index a
    # sync/upload belongs to (PR 6: clients train AS this round, so a
    # replayed downlink leg cannot desynchronize a round counter), and the
    # graceful-stop flag on the final fan-out. Defined at the comm layer so
    # protocol modules (fedavg, fedgkt, splitnn, turbo, vertical, tree) and
    # the fault injector share one spelling without importing each other.
    MSG_ARG_KEY_MODEL_DESC = "model_desc"
    MSG_ARG_KEY_ROUND_IDX = "round_idx"
    MSG_ARG_KEY_FINISHED = "finished"
    # compressed-update payload (compress/codec.py EncodedUpdate): the flat
    # byte vector of all encoded planes + the recursive structure descriptor
    MSG_ARG_KEY_ENCODED_UPDATE = "encoded_update"
    MSG_ARG_KEY_ENCODED_DESC = "encoded_desc"
    # barrier-free server plane (fedml_tpu/async_agg): every async downlink
    # stamps the global-model version it carries, clients echo it on their
    # uploads, and the server staleness-weights the fold by the echoed
    # version; tree partials carry the tier's weight sum (what the parent
    # folds by) and fold count (observability: how many client updates the
    # super-update represents)
    MSG_ARG_KEY_MODEL_VERSION = "model_version"
    MSG_ARG_KEY_WEIGHT_SUM = "weight_sum"
    MSG_ARG_KEY_FOLD_COUNT = "fold_count"
    # async edge tiers (fedml_tpu/async_agg/tree.py): a barrier-free tier
    # emits SEVERAL partials per round — the emission sequence number makes
    # replayed legs idempotent at the parent ((round, seq) must advance
    # lexicographically per sender), and the window-complete flag marks the
    # emission that closes this tier's round contribution (the parent's
    # round barrier counts only complete emissions; a missing flag means a
    # legacy single-partial tier and is read as complete)
    MSG_ARG_KEY_PARTIAL_SEQ = "partial_seq"
    MSG_ARG_KEY_WINDOW_COMPLETE = "window_complete"
    # downlink delta coding (compress/downlink.py, docs/COMPRESSION.md
    # "Downlink delta coding"): a delta-coded sync's payload reconstructs
    # the stamped MODEL_VERSION from this base version — a header-only
    # per-receiver scalar riding FramedMessage overrides, so one shared
    # delta blob serves a whole fan-out group without re-serialization
    MSG_ARG_KEY_BASE_VERSION = "base_version"
    # fleet telemetry plane (fedml_tpu/obs/registry.py, docs/OBSERVABILITY.md
    # "Fleet telemetry"): a compact JSON-safe dict of sender-side health
    # metrics piggybacked on ordinary uploads/partials — header-only scalars
    # (never an array segment), OPTIONAL (absent = zero wire overhead), and
    # never read by the aggregation path, so telemetry-on runs stay
    # bit-identical to telemetry-off runs
    MSG_ARG_KEY_TELEMETRY = "telemetry"
    # multi-tenant job plane (fedml_tpu/tenancy/, docs/MULTITENANCY.md): the
    # federation a message belongs to when several jobs share one wire — a
    # header-only scalar stamped by the job's comm facade and read by the
    # server-side router to demux per-job state. OPTIONAL: a message with no
    # job id routes to the implicit default job, so a single-job run's wire
    # bytes and behavior are unchanged (tools/multijob_smoke.py).
    MSG_ARG_KEY_JOB_ID = "job_id"
    # cross-rank causal tracing (fedml_tpu/obs/trace.py wire_ctx,
    # docs/OBSERVABILITY.md "Cross-rank causal tracing"): the sender's open
    # span id + ancestor chain + lane/rank + send wall time, stamped by the
    # comm send/broadcast paths ONLY behind a manager's explicit
    # ``trace_wire`` opt-in. Header-only JSON scalars (never an array
    # segment), OPTIONAL (absent = zero wire overhead, bytes identical to a
    # pre-tracing run), and never read by the aggregation path — the
    # receive side only attaches it to its comm/recv span so
    # tools/trace_merge.py can link N per-rank traces causally.
    MSG_ARG_KEY_TRACE_CTX = "trace_ctx"

    def __init__(self, msg_type: int = 0, sender_id: int = 0, receiver_id: int = 0):
        self.msg_params: dict[str, Any] = {
            self.MSG_ARG_KEY_TYPE: int(msg_type),
            self.MSG_ARG_KEY_SENDER: int(sender_id),
            self.MSG_ARG_KEY_RECEIVER: int(receiver_id),
        }

    # --- reference API surface (message.py:26-73) ---
    def get_sender_id(self) -> int:
        return self.msg_params[self.MSG_ARG_KEY_SENDER]

    def get_receiver_id(self) -> int:
        return self.msg_params[self.MSG_ARG_KEY_RECEIVER]

    def get_type(self) -> int:
        return self.msg_params[self.MSG_ARG_KEY_TYPE]

    def add_params(self, key: str, value: Any) -> None:
        self.msg_params[key] = value

    def get_params(self) -> dict[str, Any]:
        return self.msg_params

    def get(self, key: str, default=None) -> Any:
        return self.msg_params.get(key, default)

    def payload_nbytes(self) -> int:
        """Array-payload size in bytes (the dominant wire cost; the JSON
        header adds a few hundred bytes on top). Cheap — sums ``nbytes``
        over array params without serializing — so the tracing layer can
        attach it to send/receive spans without re-packing the message."""
        n = 0
        for v in self.msg_params.values():
            if isinstance(v, (np.ndarray, jax.Array)):
                n += int(v.nbytes)
        return n

    # --- wire format: JSON header + raw array segments ---
    MAGIC = b"FTM1"

    def frame(self) -> "FramedMessage":
        """Encode this message once into a reusable wire frame (the
        broadcast fan-out primitive — see :class:`FramedMessage`)."""
        return FramedMessage(self)

    def to_bytes(self) -> bytes:
        return self.frame().bytes_for(self.get_receiver_id())

    @classmethod
    def from_bytes(cls, data) -> "Message":
        """Decode a wire frame. Array params are zero-copy read-only views
        into ``data`` (bytes, bytearray, or memoryview) — they stay valid as
        long as the message (which keeps ``data`` alive) does."""
        mv = memoryview(data)
        assert bytes(mv[:4]) == cls.MAGIC, "bad message magic"
        (hlen,) = struct.unpack_from("<I", mv, 4)
        header = json.loads(bytes(mv[8 : 8 + hlen]).decode())
        return cls._from_header_and_tail(header, mv[8 + hlen :])

    @classmethod
    def from_buffers(cls, head, tail) -> "Message":
        """Decode a two-part frame: ``head`` (magic + header) and ``tail``
        (the shared payload segments). The loopback backend posts broadcast
        fan-outs this way so every receiver's arrays view ONE shared payload
        buffer — zero per-receiver payload copies."""
        hv = memoryview(head)
        assert bytes(hv[:4]) == cls.MAGIC, "bad message magic"
        (hlen,) = struct.unpack_from("<I", hv, 4)
        header = json.loads(bytes(hv[8 : 8 + hlen]).decode())
        return cls._from_header_and_tail(header, memoryview(tail))

    @classmethod
    def _from_header_and_tail(cls, header: dict, tail: memoryview) -> "Message":
        # collect array descriptors in segment order
        descs = [(k, v) for k, v in header.items() if isinstance(v, dict) and "__arr__" in v]
        descs.sort(key=lambda kv: kv[1]["__arr__"])
        arrays = {}
        offset = 0
        for k, d in descs:
            (alen,) = struct.unpack_from("<Q", tail, offset)
            offset += 8
            arr = np.frombuffer(
                tail, dtype=np.dtype(d["dtype"]),
                count=int(np.prod(d["shape"])) if d["shape"] else 1, offset=offset,
            )
            # wire views are read-only even when the source buffer is
            # mutable: receivers must never alias-write a (possibly shared)
            # transport buffer
            arr.flags.writeable = False
            arrays[k] = arr.reshape(d["shape"])
            offset += alen
        msg = cls()
        for k, v in header.items():
            msg.msg_params[k] = arrays[k] if k in arrays else v
        return msg

    def __repr__(self):
        sizes = {
            k: f"array{tuple(v.shape)}" if isinstance(v, (np.ndarray, jax.Array)) else v
            for k, v in self.msg_params.items()
        }
        return f"Message({sizes})"


# --- encode-once wire frame --------------------------------------------------

# the receiver slot is rendered as an 11-char fixed-width decimal so it can
# be patched in place per receiver; whitespace padding keeps the header
# valid JSON ("receiver":         3)
_RECV_SENTINEL = -1097393539
_RECV_WIDTH = len(str(_RECV_SENTINEL))


class FramedMessage:
    """One message encoded once, emittable to many receivers.

    ``Message.to_bytes`` used to re-pack the full payload per call, so a
    model broadcast to N workers serialized the model N times. A frame holds
    the payload segments as zero-copy memoryviews plus a header template
    with a fixed-width receiver slot; ``bytes_for(dst)`` patches the slot in
    place (an O(header) operation) and joins the shared segments. Small
    per-receiver header params (e.g. the assigned client index) ride
    ``overrides`` — a cheap header re-dump, never a payload re-pack.
    Overriding array params is rejected: it would orphan a payload segment.
    """

    __slots__ = ("_header", "_arrays", "_tail", "_head", "_slot",
                 "_tail_bytes", "payload_nbytes")

    def __init__(self, msg: Message):
        header: dict[str, Any] = {}
        arrays: list[np.ndarray] = []
        for k, v in msg.msg_params.items():
            if isinstance(v, (np.ndarray, jax.Array)):
                a = np.ascontiguousarray(np.asarray(v))
                header[k] = {"__arr__": len(arrays), "dtype": str(a.dtype),
                             "shape": list(a.shape)}
                arrays.append(a)
            else:
                header[k] = v
        self._header = header
        self._arrays = arrays  # keeps the segment buffers alive
        tail: list = []
        nbytes = 0
        for a in arrays:
            seg = memoryview(_byte_view(a))
            tail.append(struct.pack("<Q", seg.nbytes))
            tail.append(seg)
            nbytes += seg.nbytes
        self._tail = tail
        self._tail_bytes: bytes | None = None
        self.payload_nbytes = nbytes
        # header template with the fixed-width receiver slot
        probe = dict(header)
        probe[Message.MSG_ARG_KEY_RECEIVER] = _RECV_SENTINEL
        hb = json.dumps(probe).encode()
        token = b'"%s": %d' % (Message.MSG_ARG_KEY_RECEIVER.encode(),
                               _RECV_SENTINEL)
        self._head = None
        self._slot = None
        if hb.count(token) == 1:
            # JSON string escaping makes a str-param collision impossible;
            # a nested dict param repeating key+sentinel falls back to the
            # re-dump path below
            at = hb.index(token) + len(token) - _RECV_WIDTH
            self._head = Message.MAGIC + struct.pack("<I", len(hb)) + hb
            self._slot = 8 + at
        with _WIRE_LOCK:
            _WIRE_STATS["frames"] += 1
            if arrays:
                _WIRE_STATS["payload_serializations"] += 1

    def head_for(self, receiver: int, overrides: dict | None = None) -> bytes:
        rid = int(receiver)
        if overrides is None and self._slot is not None:
            tok = b"%*d" % (_RECV_WIDTH, rid)
            if len(tok) == _RECV_WIDTH:
                head = bytearray(self._head)
                head[self._slot : self._slot + _RECV_WIDTH] = tok
                return bytes(head)
        h = dict(self._header)
        if overrides:
            for k, v in overrides.items():
                if isinstance(v, (np.ndarray, jax.Array)):
                    raise ValueError(
                        f"broadcast override {k!r} is an array: per-receiver "
                        "overrides are header-only (share the payload, vary "
                        "the scalars)"
                    )
                tmpl = self._header.get(k)
                if isinstance(tmpl, dict) and "__arr__" in tmpl:
                    raise ValueError(
                        f"cannot override array param {k!r}: it is a framed "
                        "payload segment"
                    )
                h[k] = v
        h[Message.MSG_ARG_KEY_RECEIVER] = rid
        hb = json.dumps(h).encode()
        return Message.MAGIC + struct.pack("<I", len(hb)) + hb

    def tail_bytes(self) -> bytes:
        """The payload segments joined once (lazily cached) — shared across
        every receiver of a broadcast."""
        tb = self._tail_bytes
        if tb is None:
            tb = self._tail_bytes = b"".join(self._tail)
        return tb

    def buffers_for(self, receiver: int, overrides: dict | None = None) -> list:
        """Vectored form: ``[head, len0, seg0, len1, seg1, ...]`` — the
        payload entries are zero-copy views of the original arrays."""
        return [self.head_for(receiver, overrides), *self._tail]

    def bytes_for(self, receiver: int, overrides: dict | None = None) -> bytes:
        """Contiguous wire bytes for one receiver (for byte-oriented
        transports: one join, no payload re-serialization)."""
        return self.head_for(receiver, overrides) + self.tail_bytes()

    def to_message(self, receiver: int, overrides: dict | None = None) -> Message:
        """Rebuild a Message addressed to ``receiver`` whose array params
        share this frame's buffers — the fallback for backends without a
        bytes-level framed-send hook."""
        msg = Message()
        msg.msg_params = dict(self._header)
        for k, v in list(msg.msg_params.items()):
            if isinstance(v, dict) and "__arr__" in v:
                msg.msg_params[k] = self._arrays[v["__arr__"]]
        if overrides:
            for k, v in overrides.items():
                if isinstance(v, (np.ndarray, jax.Array)):
                    raise ValueError(
                        f"broadcast override {k!r} is an array: per-receiver "
                        "overrides are header-only"
                    )
                msg.msg_params[k] = v
        msg.msg_params[Message.MSG_ARG_KEY_RECEIVER] = int(receiver)
        return msg


# --- pytree <-> wire payload -------------------------------------------------


def pack_pytree(tree: Any) -> tuple[np.ndarray, str]:
    """Flatten a pytree of arrays to (flat byte vector, json descriptor).
    The descriptor records leaf paths/shapes/dtypes so the receiver rebuilds
    the exact structure — the anti-pickle wire contract (SURVEY §5.8).
    Leaves keep their native dtypes byte-for-byte (int64 counters and f64
    leaves survive the wire unchanged). Each leaf contributes a zero-copy
    byte view; the single concatenation into ``flat`` is the only copy."""
    from fedml_tpu.core.tree import tree_leaves_with_paths

    leaves = tree_leaves_with_paths(tree)
    desc = [
        {"path": k, "shape": list(np.shape(v)), "dtype": str(np.asarray(v).dtype)}
        for k, v in leaves
    ]
    if leaves:
        flat = np.concatenate([_byte_view(np.asarray(v)) for _, v in leaves])
    else:
        flat = np.zeros((0,), np.uint8)
    return flat, json.dumps(desc)


def pack_encoded_update(enc) -> tuple[np.ndarray, str]:
    """Flatten a (possibly chain-nested) ``EncodedUpdate`` to (flat byte
    vector, json descriptor) — the encoded-update payload type. Each plane is
    packed with :func:`pack_pytree` (native dtypes bit-exact: bf16 values,
    int32 indices, packed-nibble uint8 all survive untouched); the descriptor
    records scheme/meta and per-plane pack descriptors recursively, so the
    receiver rebuilds the exact EncodedUpdate without densifying anything."""
    from fedml_tpu.compress.codec import EncodedUpdate

    segs: list[np.ndarray] = []

    def walk(e) -> dict:
        spec: dict[str, Any] = {"scheme": e.scheme, "meta": e.meta, "planes": {}}
        for name in sorted(e.planes):
            v = e.planes[name]
            if isinstance(v, EncodedUpdate):
                spec["planes"][name] = {"__enc__": walk(v)}
            else:
                flat, desc = pack_pytree(jax.tree.map(np.asarray, v))
                segs.append(flat)
                spec["planes"][name] = {"__tree__": json.loads(desc),
                                        "nbytes": int(flat.size)}
        return spec

    spec = walk(enc)
    flat = np.concatenate(segs) if segs else np.zeros((0,), np.uint8)
    return flat, json.dumps(spec)


def unpack_encoded_update(flat: np.ndarray, descriptor: str):
    """Inverse of :func:`pack_encoded_update`."""
    from fedml_tpu.compress.codec import EncodedUpdate

    flat = np.asarray(flat, dtype=np.uint8)
    offset = 0

    def walk(spec: dict):
        nonlocal offset
        planes = {}
        for name in sorted(spec["planes"]):
            p = spec["planes"][name]
            if "__enc__" in p:
                planes[name] = walk(p["__enc__"])
            else:
                n = int(p["nbytes"])
                planes[name] = unpack_pytree(
                    flat[offset : offset + n], json.dumps(p["__tree__"])
                )
                offset += n
        return EncodedUpdate(spec["scheme"], planes, spec["meta"])

    return walk(json.loads(descriptor))


def unpack_pytree(flat: np.ndarray, descriptor: str) -> Any:
    """Rebuild a nested dict from pack_pytree output (paths use '/').

    Leaves are alignment-safe zero-copy views into ``flat``, always marked
    read-only (matching the pre-view wire semantics, where every leaf was a
    frombuffer-of-bytes copy): a writable alias would let a consumer — e.g.
    a round callback handed views of the server's live global model —
    silently corrupt the source buffer. A leaf whose byte offset is
    misaligned for its dtype falls back to a copy."""
    desc = json.loads(descriptor)
    flat = np.asarray(flat, dtype=np.uint8)
    viewable = flat.flags.c_contiguous
    base_addr = flat.ctypes.data if viewable else 0
    out: dict[str, Any] = {}
    i = 0
    for d in desc:
        dt = np.dtype(d["dtype"])
        n = int(np.prod(d["shape"])) if d["shape"] else 1
        nbytes = n * dt.itemsize
        if viewable and (base_addr + i) % dt.itemsize == 0:
            view = flat[i : i + nbytes].view(dt)
            view.flags.writeable = False
            leaf = view.reshape(d["shape"])
        else:
            leaf = np.frombuffer(flat[i : i + nbytes].tobytes(), dtype=dt).reshape(d["shape"])
        i += nbytes
        node = out
        parts = d["path"].split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return out
