"""Pure-python protoc fallback for the repo's two .proto files.

The lazy codegen in ``armada_tpu.events``/``armada_tpu.rpc`` shells out to
``protoc``; some containers ship the python ``protobuf`` runtime but not the
compiler binary.  This module covers exactly the dialect those files use
(proto3; messages with scalar / message / repeated / map fields and oneofs;
no enums, no nested user messages, no extensions): it parses the .proto into
a ``FileDescriptorProto`` and emits a ``*_pb2.py`` with the same
``AddSerializedFile`` + ``_builder`` structure protoc's python_out produces,
so downstream imports (including the generated ``rpc_pb2.py``, which resolves
``events.proto`` symbols through the default descriptor pool) work
identically.  When a real ``protoc`` is on PATH the callers prefer it.
"""

from __future__ import annotations

import re

_SCALARS = {
    "double": 1,
    "float": 2,
    "int64": 3,
    "uint64": 4,
    "int32": 5,
    "fixed64": 6,
    "fixed32": 7,
    "bool": 8,
    "string": 9,
    "bytes": 12,
    "uint32": 13,
    "sfixed32": 15,
    "sfixed64": 16,
    "sint32": 17,
    "sint64": 18,
}
_TYPE_MESSAGE = 11
_LABEL_OPTIONAL = 1
_LABEL_REPEATED = 3


def _strip_comments(text: str) -> str:
    text = re.sub(r"/\*.*?\*/", "", text, flags=re.S)
    return re.sub(r"//[^\n]*", "", text)


def _camel(name: str) -> str:
    return "".join(p.capitalize() for p in name.split("_"))


def _tokenize(text: str) -> list[str]:
    # '<' '>' ',' need to be their own tokens for map<K, V>
    return re.findall(r"[A-Za-z0-9_.]+|\"[^\"]*\"|[{}=;<>,]", text)


class _Tokens:
    def __init__(self, toks: list[str]):
        self.toks = toks
        self.i = 0

    def peek(self) -> str:
        return self.toks[self.i] if self.i < len(self.toks) else ""

    def next(self) -> str:
        t = self.peek()
        self.i += 1
        return t

    def expect(self, t: str) -> None:
        got = self.next()
        if got != t:
            raise ValueError(f"expected {t!r}, got {got!r}")

    def skip_block(self) -> None:
        """Consume a balanced {...} (current token must be '{')."""
        self.expect("{")
        depth = 1
        while depth:
            t = self.next()
            if not t:
                raise ValueError("unbalanced block")
            depth += t == "{"
            depth -= t == "}"


def parse_proto(text: str, file_name: str):
    """Parse the supported proto3 subset into a FileDescriptorProto."""
    from google.protobuf import descriptor_pb2

    fdp = descriptor_pb2.FileDescriptorProto()
    fdp.name = file_name
    fdp.syntax = "proto3"
    tk = _Tokens(_tokenize(_strip_comments(text)))
    local_messages: list = []  # (DescriptorProto, [(field, raw_type)])
    while tk.peek():
        t = tk.next()
        if t == "syntax":
            tk.expect("=")
            if tk.next() != '"proto3"':
                raise ValueError("only proto3 is supported")
            tk.expect(";")
        elif t == "package":
            fdp.package = tk.next()
            tk.expect(";")
        elif t == "import":
            fdp.dependency.append(tk.next().strip('"'))
            tk.expect(";")
        elif t == "option":
            while tk.next() != ";":
                pass
        elif t == "service":
            tk.next()  # name; python_out service descriptors are unused here
            tk.skip_block()
        elif t == "message":
            local_messages.append(_parse_message(tk, fdp))
        else:
            raise ValueError(f"unsupported top-level token {t!r}")
    # Resolve message-typed fields now that all local names are known.
    local = {m.name for m, _ in local_messages}
    for msg, deferred in local_messages:
        for field, raw in deferred:
            field.type = _TYPE_MESSAGE
            if raw in local:
                field.type_name = f".{fdp.package}.{raw}"
            else:
                # dotted = already package-qualified (cross-file reference)
                field.type_name = f".{raw}"
    return fdp


def _parse_message(tk: _Tokens, fdp):
    from google.protobuf import descriptor_pb2

    msg = fdp.message_type.add()
    msg.name = tk.next()
    deferred: list = []
    tk.expect("{")
    while True:
        t = tk.next()
        if t == "}":
            return msg, deferred
        if t == "oneof":
            oneof_index = len(msg.oneof_decl)
            msg.oneof_decl.add().name = tk.next()
            tk.expect("{")
            while tk.peek() != "}":
                f = _parse_field(tk, msg, fdp, deferred, tk.next())
                f.oneof_index = oneof_index
            tk.expect("}")
        elif t in ("message", "enum", "reserved", "extensions"):
            raise ValueError(f"unsupported construct {t!r} in {msg.name}")
        else:
            _parse_field(tk, msg, fdp, deferred, t)


def _parse_field(tk: _Tokens, msg, fdp, deferred, first: str):
    label = _LABEL_OPTIONAL
    if first == "repeated":
        label = _LABEL_REPEATED
        first = tk.next()
    if first == "map":
        return _parse_map_field(tk, msg, fdp, deferred)
    raw_type = first
    name = tk.next()
    tk.expect("=")
    number = int(tk.next())
    tk.expect(";")
    field = msg.field.add()
    field.name = name
    field.number = number
    field.label = label
    if raw_type in _SCALARS:
        field.type = _SCALARS[raw_type]
    else:
        deferred.append((field, raw_type))
    return field


def _parse_map_field(tk: _Tokens, msg, fdp, deferred):
    tk.expect("<")
    key_t = tk.next()
    tk.expect(",")
    val_t = tk.next()
    tk.expect(">")
    name = tk.next()
    tk.expect("=")
    number = int(tk.next())
    tk.expect(";")
    if key_t not in _SCALARS:
        raise ValueError(f"unsupported map<{key_t}, {val_t}>")
    entry = msg.nested_type.add()
    entry.name = _camel(name) + "Entry"
    entry.options.map_entry = True
    k = entry.field.add()
    k.name, k.number, k.label, k.type = "key", 1, _LABEL_OPTIONAL, _SCALARS[key_t]
    v = entry.field.add()
    v.name, v.number, v.label = "value", 2, _LABEL_OPTIONAL
    if val_t in _SCALARS:
        v.type = _SCALARS[val_t]
    else:
        # message-valued map (e.g. map<string, ResourceAtoms>): resolve the
        # value type with the same deferral as ordinary message fields
        deferred.append((v, val_t))
    field = msg.field.add()
    field.name = name
    field.number = number
    field.label = _LABEL_REPEATED
    field.type = _TYPE_MESSAGE
    field.type_name = f".{fdp.package}.{msg.name}.{entry.name}"
    return field


_TEMPLATE = '''\
# -*- coding: utf-8 -*-
# Generated by armada_tpu.events._minigen (protoc fallback).  DO NOT EDIT!
# source: {source}
"""Generated protocol buffer code."""
from google.protobuf.internal import builder as _builder
from google.protobuf import descriptor_pool as _descriptor_pool
from google.protobuf import symbol_database as _symbol_database

_sym_db = _symbol_database.Default()

{imports}
DESCRIPTOR = _descriptor_pool.Default().AddSerializedFile({blob!r})

_builder.BuildMessageAndEnumDescriptors(DESCRIPTOR, globals())
_builder.BuildTopDescriptorsAndMessages(DESCRIPTOR, {module!r}, globals())
'''


def generate_pb2_source(
    proto_path: str, file_name: str, module: str, import_lines: str = ""
) -> str:
    """``*_pb2.py`` source for one .proto (``file_name`` is the descriptor
    name the pool registers, i.e. the path protoc would have been given
    relative to -I; ``import_lines`` pre-imports dependency pb2 modules so
    their descriptors are in the pool before AddSerializedFile)."""
    with open(proto_path) as f:
        fdp = parse_proto(f.read(), file_name)
    return _TEMPLATE.format(
        source=file_name,
        imports=import_lines,
        blob=fdp.SerializeToString(),
        module=module,
    )
