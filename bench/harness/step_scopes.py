"""Device time of the train step by the program's own scopes.

The train step names its stages with ``jax.named_scope``, and JAX writes
the scope path into each HLO instruction's ``metadata={op_name=...}``:
forward ops of the loss carry ``jvp(model)``, backward ops (the remat
recompute included) ``transpose(jvp(model))``; aggregation runs under
``fabric.aggregate`` (with the stages of ``AGG_STAGES`` inside it) and the
optimizer under ``optimizer.apply``.

A trace's per-op totals (``trace["op_s"]`` of ``trace_reduce``) are
inclusive: a ``while`` op's event encloses those of its body.  So only the
instructions of the entry computation are summed, each once; the ops of a
loop's body count in the loop's scope.  On a program that names no scope,
:func:`scope_seconds` returns None and the readers leave their metric out.
"""
from __future__ import annotations

import re

from .hlo_ops import _INSTR

_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')

#: stages the aggregation path names inside ``fabric.aggregate``
AGG_STAGES = ("bucket.pack", "encode", "exchange", "vote", "decode",
              "bucket.scatter")


def entry_op_names(hlo: str) -> dict:
    """Instruction name -> the ``op_name`` of its metadata ("" without
    one), for the instructions of the entry computation."""
    out, entry = {}, False
    for line in hlo.splitlines():
        if not line.startswith((" ", "\t")):
            entry = line.startswith("ENTRY")
            continue
        m = _INSTR.match(line) if entry else None
        if m is not None:
            o = _OP_NAME.search(line)
            out[m.group(1)] = o.group(1) if o else ""
    return out


def all_op_names(hlo: str) -> dict:
    """Instruction name -> the ``op_name`` of its metadata ("" without
    one), for the instructions of every computation."""
    out = {}
    for line in hlo.splitlines():
        m = _INSTR.match(line) if line.startswith((" ", "\t")) else None
        if m is not None:
            o = _OP_NAME.search(line)
            out[m.group(1)] = o.group(1) if o else ""
    return out


def scope_of(op_name: str) -> str | None:
    """The step's layer an op belongs to by its scope path: "aggregate"
    (or "aggregate/<stage>" under one of ``AGG_STAGES``), "optimizer",
    "backward", "forward", or None.  Aggregation and optimizer take
    precedence over forward and backward."""
    parts = op_name.split("/")
    if "fabric.aggregate" in parts:
        rest = parts[parts.index("fabric.aggregate") + 1:]
        stage = next((p for p in rest if p in AGG_STAGES), None)
        return "aggregate" if stage is None else f"aggregate/{stage}"
    if "optimizer.apply" in parts:
        return "optimizer"
    if "transpose(jvp(model))" in parts:
        return "backward"
    if "jvp(model)" in parts:
        return "forward"
    return None


def scope_seconds(op_s: dict, hlo: str) -> dict | None:
    """Seconds per scope (see :func:`scope_of`) of the entry
    computation's ops in ``op_s``; None when none of them has a scope."""
    out = {}
    for name, op_name in entry_op_names(hlo).items():
        s = scope_of(op_name)
        if s is not None and name in op_s:
            out[s] = out.get(s, 0.0) + op_s[name]
    return out or None


def kernel_seconds(op_s: dict, op_class: dict, hlo: str) -> dict:
    """Seconds of the traced Mosaic kernels (``op_class`` "kernel", of
    :func:`hlo_ops.classify`) by the scope their instruction's metadata
    names (:func:`scope_of`), in any computation; "" for a kernel under
    no scope."""
    names = all_op_names(hlo)
    out = {}
    for name, s in op_s.items():
        if op_class.get(name) == "kernel":
            scope = scope_of(names.get(name, "")) or ""
            out[scope] = out.get(scope, 0.0) + s
    return out


def in_scope(key: str, scope: str) -> bool:
    """Whether ``key`` of :func:`scope_of` is ``scope`` or one of its
    sub-scopes (``aggregate/encode`` under ``aggregate``)."""
    return key == scope or key.startswith(scope + "/")


def scope_ms(ctx, scope: str):
    """Device ms per traced step under ``scope``, its sub-scopes
    (``aggregate/encode`` under ``aggregate``) included; None when the
    program names no scope."""
    t = ctx.get("trace")
    s = scope_seconds(t["op_s"], ctx["hlo"]) if t else None
    if not s:
        return None
    total = sum(v for k, v in s.items() if in_scope(k, scope))
    return 1e3 * total / ctx["steps_traced"]
