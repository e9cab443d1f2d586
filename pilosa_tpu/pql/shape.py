"""Statement shapes: a call tree with its integer argument values
lifted out.

A served read names rows; almost everything the parser and the planner
do for it depends only on the statement's SHAPE — the call names, the
argument keys, every non-integer value — and not on which integers fill
it. This module is the one definition of that split, used by both
halves (``pql.parser``'s template memo, ``plan.planner``'s shape
entries):

- **What is a parameter.** A plain ``int`` (never a ``bool``) in
  argument-VALUE position: ``rowID=3``, ``columnID=…``, ``n=…``, the
  members of a list, the operand(s) of a BSI condition. Nothing else:
  identifiers, strings (a frame named ``f1``), floats, timestamps,
  ``true/false/null``, condition operators all belong to the shape.
- **Slot order** is the parser's source order: a call's children
  first, then its arguments in the order written, list members left to
  right. ``spec_of`` (shape from a call), ``lift`` (key + values of a
  call) and ``ints_of`` walk in that one order.

A *spec* is ``(name, args0, fills, kids)``: ``args0`` the argument
dict with the sighting's own values standing in the slots, ``fills``
what ``bind_args`` overwrites — ``(key, slot)`` for a bare integer,
``(key, (items, ((pos, slot), …)))`` for a list, ``(key, (op, inner))``
for a condition — and ``kids`` the children's specs. Lists are always
rebuilt (a bound call never shares a mutable value with its shape).
"""

from __future__ import annotations

from .ast import Call, Condition

class _Slot:
    def __repr__(self):
        return "?"


# Stands for a lifted integer in a structural shape key: an object of
# its own, so no argument value can equal it.
SLOT = _Slot()


def _spec_value(v, n: int):
    """(fill for ``v`` or None when ``v`` is a constant, next slot)."""
    t = type(v)
    if t is int:
        return n, n + 1
    if t is list or t is tuple:
        at = []
        for pos, x in enumerate(v):
            if type(x) is int:
                at.append((pos, n))
                n += 1
        return (tuple(v), tuple(at)), n
    if t is Condition:
        inner, n = _spec_value(v.value, n)
        return (None if inner is None else (v.op, inner)), n
    return None, n


def spec_of(call: Call, n: int = 0):
    """(spec of ``call``, next free slot), slots numbered from ``n``."""
    kids = []
    for c in call.children:
        k, n = spec_of(c, n)
        kids.append(k)
    fills = []
    for key, v in call.args.items():
        fill, n = _spec_value(v, n)
        if fill is not None:
            fills.append((key, fill))
    return (call.name, dict(call.args), tuple(fills), tuple(kids)), n


def _bind_value(fill, vals):
    if type(fill) is int:
        return vals[fill]
    head, rest = fill
    if type(head) is tuple:  # a list: (items, ((pos, slot), ...))
        out = list(head)
        for pos, slot in rest:
            out[pos] = vals[slot]
        return out
    return Condition(head, _bind_value(rest, vals))  # (op, inner)


def bind_args(spec, vals) -> dict:
    """A fresh argument dict of ``spec`` with ``vals`` in its slots."""
    args = dict(spec[1])
    for key, fill in spec[2]:
        args[key] = vals[fill] if type(fill) is int \
            else _bind_value(fill, vals)
    return args


def bind_call(spec, vals) -> Call:
    """The call ``spec`` stands for, with ``vals`` bound."""
    kids = spec[3]
    return Call(spec[0], bind_args(spec, vals),
                [bind_call(k, vals) for k in kids] if kids else None)


def ints_of(call: Call, out: list) -> None:
    """Append ``call``'s parameters to ``out``, in slot order."""
    for c in call.children:
        ints_of(c, out)
    for v in call.args.values():
        _ints_of_value(v, out)


def _ints_of_value(v, out: list) -> None:
    t = type(v)
    if t is int:
        out.append(v)
    elif t is list or t is tuple:
        out.extend(x for x in v if type(x) is int)
    elif t is Condition:
        _ints_of_value(v.value, out)


def _lift_value(v, out: list, member: bool = False):
    t = type(v)
    if t is str:
        return v
    if t is int:
        out.append(v)
        return SLOT
    if not member:
        if t is list or t is tuple:
            return tuple([_lift_value(x, out, True) for x in v])
        if t is Condition:
            return (Condition, v.op, _lift_value(v.value, out))
    # true / 1.0 / 1 are equal and hash alike: the type tells them apart.
    return (t, v)


def lift(call: Call, out: list) -> tuple:
    """The structural shape key of ``call`` — hashable unless an
    argument value is not (TypeError where it is looked up) — with
    the parameters appended to ``out`` in slot order."""
    kids = call.children
    key = [call.name,
           tuple([lift(c, out) for c in kids]) if kids else ()]
    for k, v in call.args.items():
        key.append(k)
        t = type(v)
        if t is int:
            out.append(v)
            key.append(SLOT)
        elif t is str:
            key.append(v)
        else:
            key.append(_lift_value(v, out))
    return tuple(key)
