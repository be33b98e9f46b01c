"""Wire codec: API dataclasses ↔ JSON-safe dicts.

The reference's wire format is generated protobuf/JSON marshalers per type
(staging/src/k8s.io/api, apimachinery runtime.Scheme).  Here one generic
codec walks the dataclass type hints recursively — every scheduler-relevant
type (Pod, Node, affinity trees, Resource) round-trips through plain JSON
for the HTTP list/watch tier (client/api_server.py, client/client.py).

Conventions:
  * dataclasses → {"field": value, ...}, WITHOUT the fields that stand at
    their declared default (the reference's ``omitempty`` / unset protobuf
    fields): a field is left out when its value has the default's type and
    equals it (``None`` by identity; a ``default_factory`` field against
    what its factory makes); a field with no default is always present.
    ``from_wire`` gives a missing field its default, so ``decode(encode(x))
    == x`` with the same types, and a payload that carries every field (a
    journal, a fixture, a peer built before this convention) decodes to the
    same object — old and new read each other both ways (WIRE.md);
  * Tuple[X, ...] / List[X] → JSON arrays, Optional[X] → value or null;
  * Dict/Mapping str→str/int pass through;
  * memoized derived state on Pod (underscore keys) never serializes.

What a class's fields are, which defaults they carry and how a hint turns a
wire value back into its type is worked out ONCE per class / hint and kept
(``_ENCODE_PLANS``, ``_DECODE_PLANS``): the per-object path only walks the
plan.
"""

from __future__ import annotations

import dataclasses
import typing
from typing import Any, Callable, Dict, Optional, Tuple, get_args, get_origin, get_type_hints

from kubernetes_tpu.api import types as T
from kubernetes_tpu.api.resource import Resource

_SCALARS = frozenset((type(None), bool, int, float, str))
_MISSING = dataclasses.MISSING

# class → ((field name, has a default, the default), ...).  A factory's
# product is made once and only ever compared against, never handed out.
_ENCODE_PLANS: Dict[type, Tuple[Tuple[str, bool, Any], ...]] = {}
# class → {field name: converter or None (value passes through)}
_DECODE_PLANS: Dict[type, Dict[str, Optional[Callable[[Any], Any]]]] = {}


def _encode_plan(cls) -> Tuple[Tuple[str, bool, Any], ...]:
    plan = []
    for f in dataclasses.fields(cls):
        if f.default is not _MISSING:
            plan.append((f.name, True, f.default))
        elif f.default_factory is not _MISSING:
            plan.append((f.name, True, f.default_factory()))
        else:
            plan.append((f.name, False, None))
    _ENCODE_PLANS[cls] = out = tuple(plan)
    return out


def to_wire(obj: Any) -> Any:
    """Dataclass tree → JSON-safe structure (fields at their default are
    left out; see the module's conventions)."""
    plan = _ENCODE_PLANS.get(type(obj))
    if plan is None:
        if obj is None or isinstance(obj, (bool, int, float, str)):
            return obj
        if isinstance(obj, (list, tuple)):
            return [to_wire(x) for x in obj]
        if isinstance(obj, dict):
            return {str(k): to_wire(v) for k, v in obj.items()}
        if not dataclasses.is_dataclass(obj):
            raise TypeError(f"to_wire: unsupported {type(obj)!r}")
        plan = _encode_plan(type(obj))
    out = {}
    for name, has_default, default in plan:
        v = getattr(obj, name)
        if has_default and (
            v is default or (type(v) is type(default) and v == default)
        ):
            continue
        out[name] = v if type(v) in _SCALARS else to_wire(v)
    return out


def _converter(hint: Any) -> Optional[Callable[[Any], Any]]:
    """The function that turns a non-None wire value into ``hint``'s type,
    or None where the value passes through as it is."""
    origin = get_origin(hint)
    if origin is typing.Union:  # Optional[X]
        args = [a for a in get_args(hint) if a is not type(None)]
        # Optional[X]; unions of primitives (str | int | float) pass through
        return _converter(args[0]) if len(args) == 1 else None
    if origin in (tuple, list):
        args = get_args(hint)
        elem = _converter(args[0]) if args else None
        if elem is None:
            return origin

        def seq(value):
            return [None if v is None else elem(v) for v in value]

        return seq if origin is list else lambda value: tuple(seq(value))
    if origin in (dict, typing.Mapping) or hint in (dict,):
        args = get_args(hint)
        vt = _converter(args[1]) if len(args) == 2 else None
        if vt is None:
            return dict
        return lambda value: {
            k: None if v is None else vt(v) for k, v in value.items()
        }
    if dataclasses.is_dataclass(hint):
        return lambda value: from_wire(value, hint)
    if hint in (int, float, str, bool):
        return hint
    # typing.Any / unparameterized Mapping values
    return None


def _decode_plan(cls) -> Dict[str, Optional[Callable[[Any], Any]]]:
    hints = get_type_hints(cls)
    _DECODE_PLANS[cls] = plan = {
        f.name: _converter(hints[f.name]) for f in dataclasses.fields(cls)
    }
    return plan


def from_wire(data: Dict[str, Any], cls) -> Any:
    """JSON structure → dataclass instance of ``cls``; a field the payload
    does not carry takes its declared default, a key that is no field of
    ``cls`` is ignored."""
    plan = _DECODE_PLANS.get(cls)
    if plan is None:
        plan = _decode_plan(cls)
    kwargs = {}
    for name, value in data.items():
        conv = plan.get(name, _MISSING)
        if conv is _MISSING:
            continue
        kwargs[name] = value if conv is None or value is None else conv(value)
    return cls(**kwargs)


# kind registry for the watch stream's typed envelopes
KINDS = {
    "Pod": T.Pod,
    "Node": T.Node,
    "Resource": Resource,
    "PodDisruptionBudget": T.PodDisruptionBudget,
}


def encode(obj: Any) -> Dict[str, Any]:
    kind = type(obj).__name__
    if kind not in KINDS:
        raise TypeError(f"encode: unregistered kind {kind}")
    return {"kind": kind, "object": to_wire(obj)}


def decode(envelope: Dict[str, Any]) -> Any:
    cls = KINDS.get(envelope.get("kind"))
    if cls is None:
        raise TypeError(f"decode: unregistered kind {envelope.get('kind')!r}")
    return from_wire(envelope["object"], cls)
