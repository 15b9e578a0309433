"""Regenerate the checked-in ``*_pb2.py`` modules without ``protoc``.

The container has no protobuf compiler, so schema growth works by
mutating the serialized ``FileDescriptorProto`` blobs the existing pb2
modules already carry (``DESCRIPTOR.serialized_pb``), then re-emitting
the module with the new blob.  Every mutation here is idempotent —
running the generator against an already-current pb2 is a no-op — and
append-only: field numbers are only ever added, never renumbered, so
the wire stays compatible with peers running the previous schema
(proto3 parsers skip unknown fields).

Round 22 additions (fleet observatory):

- ``consensus_p2p.TraceCtx`` + ``GossipMsg.trace = 3``: the cross-node
  trace context a gossip publish carries (origin node label, trace id,
  hop count, origin wall-clock timestamp).  Optional on both sides — a
  peer that omits it decodes cleanly (mixed-version satellite).
- ``consensus_port.TraceCtx`` + ``Publish.trace = 3`` and
  ``Gossip.trace = 5``: the same context crossing the host<->sidecar
  stdio boundary in both directions.
- ``consensus_port.GetGossipStats`` + ``Command.get_gossip_stats = 12``:
  per-(peer, topic) delivery counters, peer scores and mesh/control
  stats, returned as a JSON payload.

PR 26 addition (one sidecar frame for a drain's verdicts):

- ``consensus_port.ValidateMessages`` (``repeated ValidateMessage
  verdicts = 1``) + ``Command.validate_messages = 13``: a gossip drain
  hands the sidecar all its verdicts in one command frame and awaits one
  ``Result``.

Usage: ``python tools/gen_proto.py`` (writes in place, prints a diffstat
line per file).
"""

from __future__ import annotations

import os
import sys

from google.protobuf import descriptor_pb2

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROTO_DIR = os.path.join(REPO, "lambda_ethereum_consensus_tpu", "network", "proto")

F = descriptor_pb2.FieldDescriptorProto

_TEMPLATE = '''# -*- coding: utf-8 -*-
# Generated by tools/gen_proto.py (no protoc in the image).  DO NOT EDIT!
# source: {source}
"""Generated protocol buffer code."""
from google.protobuf.internal import builder as _builder
from google.protobuf import descriptor as _descriptor
from google.protobuf import descriptor_pool as _descriptor_pool
from google.protobuf import symbol_database as _symbol_database
# @@protoc_insertion_point(imports)

_sym_db = _symbol_database.Default()




DESCRIPTOR = _descriptor_pool.Default().AddSerializedFile({blob!r})

_builder.BuildMessageAndEnumDescriptors(DESCRIPTOR, globals())
_builder.BuildTopDescriptorsAndMessages(DESCRIPTOR, {module!r}, globals())
# @@protoc_insertion_point(module_scope)
'''


def _message(fdp, name: str):
    for m in fdp.message_type:
        if m.name == name:
            return m
    return None


def _has_field(msg, name: str) -> bool:
    return any(f.name == name for f in msg.field)


def _add_field(
    msg, name, number, ftype, type_name=None, oneof_index=None,
    label=F.LABEL_OPTIONAL,
):
    if _has_field(msg, name):
        return False
    f = msg.field.add()
    f.name = name
    f.number = number
    f.label = label
    f.type = ftype
    if type_name is not None:
        f.type_name = type_name
    if oneof_index is not None:
        f.oneof_index = oneof_index
    return True


def _add_trace_ctx(fdp, package: str) -> bool:
    """``TraceCtx`` — the compact cross-node trace context (append-only)."""
    if _message(fdp, "TraceCtx") is not None:
        return False
    m = fdp.message_type.add()
    m.name = "TraceCtx"
    for name, number, ftype in (
        ("origin", 1, F.TYPE_STRING),    # originating node's label
        ("trace_id", 2, F.TYPE_UINT64),  # origin-local ItemTrace id
        ("hop", 3, F.TYPE_UINT32),       # mesh hops travelled so far
        ("origin_ts", 4, F.TYPE_DOUBLE),  # publish wall-clock (time.time())
    ):
        _add_field(m, name, number, ftype)
    return True


def mutate_p2p(fdp) -> bool:
    changed = _add_trace_ctx(fdp, fdp.package)
    gossip = _message(fdp, "GossipMsg")
    changed |= _add_field(
        gossip, "trace", 3, F.TYPE_MESSAGE, f".{fdp.package}.TraceCtx"
    )
    return changed


def mutate_port(fdp) -> bool:
    changed = _add_trace_ctx(fdp, fdp.package)
    changed |= _add_field(
        _message(fdp, "Publish"), "trace", 3,
        F.TYPE_MESSAGE, f".{fdp.package}.TraceCtx",
    )
    changed |= _add_field(
        _message(fdp, "Gossip"), "trace", 5,
        F.TYPE_MESSAGE, f".{fdp.package}.TraceCtx",
    )
    if _message(fdp, "GetGossipStats") is None:
        fdp.message_type.add().name = "GetGossipStats"
        changed = True
    cmd = _message(fdp, "Command")
    # oneof index 0 is the Command's single "c" oneof
    changed |= _add_field(
        cmd, "get_gossip_stats", 12,
        F.TYPE_MESSAGE, f".{fdp.package}.GetGossipStats", oneof_index=0,
    )
    if _message(fdp, "ValidateMessages") is None:
        fdp.message_type.add().name = "ValidateMessages"
        changed = True
    changed |= _add_field(
        _message(fdp, "ValidateMessages"), "verdicts", 1,
        F.TYPE_MESSAGE, f".{fdp.package}.ValidateMessage",
        label=F.LABEL_REPEATED,
    )
    changed |= _add_field(
        cmd, "validate_messages", 13,
        F.TYPE_MESSAGE, f".{fdp.package}.ValidateMessages", oneof_index=0,
    )
    return changed


TARGETS = (
    ("p2p_pb2.py", "p2p.proto", "p2p_pb2", mutate_p2p),
    ("port_pb2.py", "port.proto", "port_pb2", mutate_port),
)


def _current_blob(path: str) -> bytes:
    """The serialized FileDescriptorProto the checked-in module carries,
    extracted textually (importing would register it in the default pool
    and collide with the regenerated schema)."""
    with open(path, "r") as fh:
        text = fh.read()
    for line in text.splitlines():
        if "AddSerializedFile(" in line:
            # one statement per line; the literal's own bytes may contain
            # '(' / ')' so match the line's outermost parens, not depth
            start = line.index("AddSerializedFile(") + len("AddSerializedFile(")
            literal = line[start : line.rindex(")")]
            return eval(literal, {"__builtins__": {}})  # a bytes literal only
    raise SystemExit(f"{path}: no AddSerializedFile call found")


def main() -> int:
    rc = 0
    for fname, source, module, mutate in TARGETS:
        path = os.path.join(PROTO_DIR, fname)
        fdp = descriptor_pb2.FileDescriptorProto()
        fdp.ParseFromString(_current_blob(path))
        changed = mutate(fdp)
        out = _TEMPLATE.format(
            source=source, blob=fdp.SerializeToString(), module=module
        )
        with open(path, "r") as fh:
            unchanged_file = fh.read() == out
        if not unchanged_file:
            with open(path, "w") as fh:
                fh.write(out)
        print(
            f"{fname}: "
            + ("schema extended" if changed else "schema current")
            + ("" if unchanged_file else " (file rewritten)")
        )
    return rc


if __name__ == "__main__":
    sys.exit(main())
