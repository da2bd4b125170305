"""Shared AST plumbing: find jit-compiled functions, their static and
donated arguments, and the `self.<attr> = <local jit fn>` bindings that
route method calls to them (the `_build()` idiom every model uses).

Used by the host-sync, recompile-hazard and use-after-donate rules — one
resolver so the three rules can never disagree about what is traced.
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field

__all__ = ["JitFn", "collect_jit_fns", "collect_attr_bindings",
           "dotted_name", "KNOWN_DONATING_METHODS"]

# Cross-module donation knowledge: public TextModel wrappers whose jitted
# bodies donate buffers at these CALL-SITE positional indices (self
# already bound). The serve engine and the spec loop call these on a
# `model` object the per-module AST cannot see into.
KNOWN_DONATING_METHODS: dict[str, tuple[int, ...]] = {
    "decode_slots": (0, 1, 2, 3, 4),    # layers, toks, pos, rngs, recents
    "spec_slots": (0, 1, 2, 3, 4),
    "prefill_chunk": (0,),              # layers
    # paged variants: pool + rows donated, the block TABLE is not (the
    # engine remaps entries between iterations and keeps its handle)
    "decode_slots_paged": (0, 1, 3, 4, 5, 6),
    "spec_slots_paged": (0, 1, 3, 4, 5, 6),
    "prefill_chunk_paged": (0, 1),
    "row_install": (0,),                # rows
    "row_reset": (0,),
    "slot_assign": (0,),
    "slot_release": (0,),
    "slot_restore": (0,),
    "slot_join": (2, 3, 4, 5),          # toks, pos, rngs, recents
    "verify_tokens": (0,),              # cache
    "prefill": (0,),
    "decode_logits": (0,),
    "forward_hidden": (1,),             # x, CACHE, pos0, ...
}


@dataclass
class JitFn:
    name: str
    node: ast.FunctionDef
    params: list[str]
    static_names: set[str] = field(default_factory=set)
    donate_idx: set[int] = field(default_factory=set)


def _const_strs(node) -> list[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)]
    return []


def _const_ints(node) -> list[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, int)]
    return []


def _is_jax_jit(node) -> bool:
    """`jax.jit` / `jit` as an expression."""
    if isinstance(node, ast.Attribute) and node.attr == "jit":
        return True
    return isinstance(node, ast.Name) and node.id == "jit"


def jit_call_info(call: ast.Call):
    """(static_names, static_nums, donate_nums) from a
    `functools.partial(jax.jit, ...)` or `jax.jit(...)` call; None when
    the call is not a jit wrapper."""
    fn = call.func
    is_partial = (isinstance(fn, ast.Attribute) and fn.attr == "partial") \
        or (isinstance(fn, ast.Name) and fn.id == "partial")
    if is_partial:
        if not (call.args and _is_jax_jit(call.args[0])):
            return None
    elif not _is_jax_jit(fn):
        return None
    statics, snums, dnums = set(), [], []
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            statics.update(_const_strs(kw.value))
        elif kw.arg == "static_argnums":
            snums.extend(_const_ints(kw.value))
        elif kw.arg == "donate_argnums":
            dnums.extend(_const_ints(kw.value))
    return statics, snums, dnums


def _params_of(fn: ast.FunctionDef) -> list[str]:
    a = fn.args
    return [p.arg for p in (a.posonlyargs + a.args)]


def collect_jit_fns(tree: ast.Module) -> dict[str, JitFn]:
    """Every function in the module (at any nesting) compiled by jax.jit:
    decorated defs, plus `name = jax.jit(fn, ...)` assignments where `fn`
    is a local def or lambda."""
    defs: dict[str, ast.FunctionDef] = {}
    out: dict[str, JitFn] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            defs[node.name] = node
            for dec in node.decorator_list:
                info = None
                if isinstance(dec, ast.Call):
                    info = jit_call_info(dec)
                elif _is_jax_jit(dec):
                    info = (set(), [], [])
                if info is None:
                    continue
                params = _params_of(node)
                statics, snums, dnums = info
                statics |= {params[i] for i in snums if i < len(params)}
                out[node.name] = JitFn(node.name, node, params, statics,
                                       {i for i in dnums if i < len(params)})
                break
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign) or not isinstance(node.value,
                                                              ast.Call):
            continue
        info = jit_call_info(node.value)
        if info is None or not node.value.args:
            continue
        wrapped = node.value.args[0]
        fnode = params = None
        if isinstance(wrapped, ast.Name) and wrapped.id in defs:
            fnode = defs[wrapped.id]
            params = _params_of(fnode)
        elif isinstance(wrapped, ast.Lambda):
            fnode = wrapped
            params = [p.arg for p in wrapped.args.args]
        if fnode is None:
            continue
        statics, snums, dnums = info
        statics |= {params[i] for i in snums if i < len(params)}
        for tgt in node.targets:
            name = dotted_name(tgt)
            if name:
                out[name] = JitFn(name, fnode, params, statics,
                                  {i for i in dnums if i < len(params)})
    return out


def collect_attr_bindings(tree: ast.Module) -> dict[str, str]:
    """`self.X = Y` where Y is a bare local name -> {"self.X": "Y"}: how
    `_build()` publishes its jitted closures as instance attributes."""
    out: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Name):
            for tgt in node.targets:
                name = dotted_name(tgt)
                if name and name.startswith("self."):
                    out[name] = node.value.id
    return out


def dotted_name(node) -> str | None:
    """Name/Attribute chain -> "a.b.c"; None for anything fancier."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def resolve_jit_callee(call: ast.Call, jits: dict[str, JitFn],
                       bindings: dict[str, str]) -> JitFn | None:
    """The JitFn a call dispatches to: a jitted local name, a name bound
    by `name = jax.jit(...)`, or a `self.X` attribute published from
    `_build()`."""
    name = dotted_name(call.func)
    if name is None:
        return None
    if name in jits:
        return jits[name]
    target = bindings.get(name)
    if target is not None and target in jits:
        return jits[target]
    return None
