"""runtime.Scheme — (version, kind) registry + codec + conversion seam.

ref: pkg/runtime/scheme.go:208-311 and pkg/conversion/scheme.go:25-54. The
Scheme maps (apiVersion, kind) to the internal Python type, encodes objects to
versioned JSON wire form and decodes wire form back to internal objects.

Like the reference, internal types are version-free; each registered version
owns a pair of wire-dict transforms (internal-wire -> versioned-wire and
back). The default version "v1" is the identity transform (camelCase
dataclass encoding from kubernetes_tpu.runtime.serialize). A legacy
"v1beta1" is registered in kubernetes_tpu.api.latest to exercise the seam the
same way the reference ships v1beta1/v1beta2/v1beta3 side by side.
"""

from __future__ import annotations

import json
from typing import Any, Callable, Dict, Optional, Tuple, Type

from kubernetes_tpu.runtime.serialize import from_wire, roundtrip, to_wire
from kubernetes_tpu.util import metrics

__all__ = ["Scheme", "NotRegisteredError"]

WireTransform = Callable[[dict], dict]

# Counted where the codec runs, whoever asked: a pass is one walk between
# an object and its wire dict. What a write hands on (storage/helper.py)
# and what the caches serve never gets here.
_PASSES = metrics.default_registry().counter(
    "runtime_codec_passes_total",
    "Walks between an API object and its wire dict, by direction",
    ("direction",))

# json.dumps(wire, sort_keys=True), less the encoder it builds a call
_DUMPS = json.JSONEncoder(sort_keys=True).encode


class NotRegisteredError(KeyError):
    pass


class Scheme:
    def __init__(self, default_version: str = "v1"):
        self.default_version = default_version
        # version -> kind -> type
        self._types: Dict[str, Dict[str, Type]] = {}
        # (version, kind) -> (internal_wire->versioned, versioned->internal_wire)
        self._transforms: Dict[Tuple[str, str], Tuple[WireTransform, WireTransform]] = {}
        # kind -> internal type (shared across versions)
        self._internal: Dict[str, Type] = {}
        # (version, wire kind) -> internal kind and back (e.g. v1beta1
        # "Minion" <-> Node, ref: pkg/api/v1beta1/register.go)
        self._kind_aliases: Dict[Tuple[str, str], str] = {}
        self._kind_alias_out: Dict[Tuple[str, str], str] = {}
        # (version, kind) -> defaulter(obj), applied on decode
        # (ref: pkg/api/v1beta1/defaults.go addDefaultingFuncs)
        self._defaulters: Dict[Tuple[str, str], Callable[[Any], None]] = {}
        # (version, kind) -> fn(label, value) -> (internal label, value)
        # (ref: pkg/api/v1beta1/conversion.go field-label funcs)
        self._field_labels: Dict[Tuple[str, str], Callable] = {}

    # -- registration -------------------------------------------------------
    def add_known_types(self, version: str, *types_: Type) -> None:
        """ref: scheme.go AddKnownTypes — kind is the type's declared kind."""
        kinds = self._types.setdefault(version, {})
        for t in types_:
            kind = getattr(t, "kind", None)
            if not (isinstance(kind, str) and kind):
                kind = t.__name__
            kinds[kind] = t
            self._internal.setdefault(kind, t)

    def add_conversion(self, version: str, kind: str,
                       encode: WireTransform, decode: WireTransform) -> None:
        """Register wire transforms for a (version, kind) pair
        (ref: conversion.Scheme.AddConversionFuncs)."""
        self._transforms[(version, kind)] = (encode, decode)

    def add_kind_alias(self, version: str, wire_kind: str, kind: str) -> None:
        """A version may spell a kind differently on the wire."""
        self._kind_aliases[(version, wire_kind)] = kind
        self._kind_alias_out[(version, kind)] = wire_kind

    def add_defaulter(self, version: str, kind: str,
                      fn: Callable[[Any], None]) -> None:
        """Defaulting pass applied to objects decoded from this version."""
        self._defaulters[(version, kind)] = fn

    def add_field_label_conversion(self, version: str, kind: str,
                                   fn: Callable) -> None:
        """fn(label, value) -> (internal label, value) for field selectors
        expressed in this version's vocabulary."""
        self._field_labels[(version, kind)] = fn

    def convert_field_label(self, version: str, kind: str,
                            label: str, value: str):
        fn = self._field_labels.get((version, kind))
        if fn is None:
            return label, value
        return fn(label, value)

    def versions(self):
        return sorted(self._types)

    def recognizes(self, version: str, kind: str) -> bool:
        return kind in self._types.get(version, {})

    def type_for(self, version: str, kind: str) -> Type:
        try:
            return self._types[version][kind]
        except KeyError:
            raise NotRegisteredError(f"no kind {kind!r} registered for version {version!r}")

    def object_kind(self, obj: Any) -> str:
        kind = getattr(obj, "kind", "") or type(obj).__name__
        return kind

    def new(self, version: str, kind: str) -> Any:
        return self.type_for(version, kind)()

    # -- codec --------------------------------------------------------------
    def encode_to_wire(self, obj: Any, version: Optional[str] = None) -> dict:
        _PASSES.inc("encode")
        version = version or self.default_version
        kind = self.object_kind(obj)
        if not self.recognizes(version, kind):
            raise NotRegisteredError(f"kind {kind!r} not registered in version {version!r}")
        wire = to_wire(obj)
        if kind.endswith("List") and "items" not in wire:
            # omitempty drops empty lists, but List kinds must always carry
            # items on the wire — clients index .items unconditionally
            wire["items"] = []
        enc, _ = self._transforms.get((version, kind), (None, None))
        if enc is not None:
            wire = enc(wire)
        wire["kind"] = self._kind_alias_out.get((version, kind), kind)
        wire["apiVersion"] = version
        return wire

    @staticmethod
    def wire_to_json(wire: dict) -> str:
        """The one spelling of a wire dict as bytes: store, response and
        watch frame all carry it."""
        return _DUMPS(wire)

    def encode(self, obj: Any, version: Optional[str] = None) -> str:
        """ref: runtime.Codec.Encode — JSON with kind + apiVersion set."""
        return self.wire_to_json(self.encode_to_wire(obj, version))

    def decode_from_wire(self, wire: dict, default_kind: str = "",
                         default_version: str = "") -> Any:
        if not isinstance(wire, dict):
            raise ValueError("expected a JSON object")
        _PASSES.inc("decode")
        wire = dict(wire)
        kind = wire.pop("kind", "") or default_kind
        version = wire.pop("apiVersion", "") or default_version or self.default_version
        if not kind:
            raise ValueError("unable to decode: 'kind' is not set")
        kind = self._kind_aliases.get((version, kind), kind)
        t = self.type_for(version, kind)
        _, dec = self._transforms.get((version, kind), (None, None))
        if dec is not None:
            wire = dec(wire)
        obj = from_wire(t, wire)
        defaulter = self._defaulters.get((version, kind))
        if defaulter is not None:
            defaulter(obj)
        return obj

    def decode(self, data, default_kind: str = "", default_version: str = "") -> Any:
        """ref: runtime.Codec.Decode — bytes/str JSON -> internal object."""
        if isinstance(data, (bytes, bytearray)):
            data = data.decode("utf-8")
        return self.decode_from_wire(json.loads(data), default_kind, default_version)

    def deep_copy(self, obj: Any) -> Any:
        """What decoding ``obj``'s encoding in the default version gives
        (ref: runtime.Scheme.Copy) — without the wire in between where
        that version has no transform for the kind: the typed copy, then
        the version's defaulter, as ``decode_from_wire`` ends."""
        kind = self.object_kind(obj)
        version = self.default_version
        if (version, kind) in self._transforms \
                or not self.recognizes(version, kind):  # or raises
            return self.decode_from_wire(self.encode_to_wire(obj, version))
        out = roundtrip(obj)
        defaulter = self._defaulters.get((version, kind))
        if defaulter is not None:
            defaulter(out)
        return out

    def convert_wire(self, wire: dict, from_version: str, to_version: str) -> dict:
        """Convert a versioned wire dict between versions via the internal form
        (ref: kube-version-change cmd)."""
        obj = self.decode_from_wire(dict(wire), default_version=from_version)
        return self.encode_to_wire(obj, to_version)
