"""PRNG hygiene rules.

GL101 prng-key-reuse: a PRNG key Name consumed by two ``jax.random.*``
draws without an intervening ``split``/``fold_in`` rebinding produces
correlated randomness — the draws are identical, not independent.  Also
flags a key bound outside a loop but consumed inside it (every iteration
sees the same stream).

GL102 seed-int32-overflow: host-side Python-int arithmetic fed straight
into ``PRNGKey`` can silently wrap int32 for large seeds/offsets (the
PR-3 bug).  The sanctioned forms are ``jax.random.fold_in(key, i)`` or
masking the int64 sum with ``& 0xFFFFFFFF`` before key construction
(`core/explorer.py` ``task_seeds``).
"""
from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from ..core import FileContext, Finding, Rule

# jax.random callables that *derive* keys rather than consume entropy
_NON_CONSUMERS = {"split", "fold_in", "PRNGKey", "key", "key_data",
                  "wrap_key_data", "clone", "key_impl"}


def _is_random_consumer(ctx: FileContext, call: ast.Call) -> bool:
    name = ctx.call_name(call)
    if not name or not name.startswith("jax.random."):
        return False
    return name.rsplit(".", 1)[1] not in _NON_CONSUMERS


def _key_arg(call: ast.Call) -> Optional[str]:
    """The bare-Name key argument of a jax.random consumer, if any."""
    args = [a for a in call.args]
    for kw in call.keywords:
        if kw.arg == "key":
            args.insert(0, kw.value)
    if args and isinstance(args[0], ast.Name):
        return args[0].id
    return None


def _bound_names(target: ast.AST) -> Set[str]:
    out: Set[str] = set()
    for node in ast.walk(target):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Store):
            out.add(node.id)
    return out


class PrngKeyReuse(Rule):
    name = "prng-key-reuse"
    code = "GL101"
    description = ("PRNG key passed to two jax.random draws (or consumed "
                   "inside a loop) without split/fold_in between")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for fn in ctx.functions():
            yield from self._check_scope(ctx, fn)

    def _check_scope(self, ctx: FileContext, fn) -> Iterator[Finding]:
        # events in source order: ('bind'|'consume', name, node, loop_depth)
        events: List[Tuple[str, str, ast.AST, int]] = []

        def visit(node: ast.AST, depth: int) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.Lambda, ast.ClassDef)):
                    continue    # separate scope
                if isinstance(child, (ast.Assign, ast.AugAssign,
                                      ast.AnnAssign)):
                    for sub in ast.walk(child):
                        if isinstance(sub, ast.Call) and \
                                _is_random_consumer(ctx, sub):
                            key = _key_arg(sub)
                            if key:
                                events.append(("consume", key, sub, depth))
                    targets = (child.targets
                               if isinstance(child, ast.Assign)
                               else [child.target])
                    for t in targets:
                        for n in _bound_names(t):
                            events.append(("bind", n, child, depth))
                    continue
                if isinstance(child, ast.Call) and \
                        _is_random_consumer(ctx, child):
                    key = _key_arg(child)
                    if key:
                        events.append(("consume", key, child, depth))
                in_loop = isinstance(child, (ast.For, ast.While))
                if in_loop and isinstance(child, ast.For):
                    for n in _bound_names(child.target):
                        events.append(("bind", n, child, depth + 1))
                visit(child, depth + 1 if in_loop else depth)

        visit(fn, 0)

        last_consume: Dict[str, ast.AST] = {}
        bind_depth: Dict[str, int] = {a.arg: 0 for a in fn.args.args}
        for kind, name, node, depth in events:
            if kind == "bind":
                last_consume.pop(name, None)
                bind_depth[name] = depth
            else:
                if name in last_consume:
                    yield self.finding(
                        ctx, node,
                        f"key '{name}' already consumed at line "
                        f"{last_consume[name].lineno}; split/fold_in before "
                        f"drawing again")
                elif depth > bind_depth.get(name, 0):
                    yield self.finding(
                        ctx, node,
                        f"key '{name}' bound outside this loop but consumed "
                        f"inside it; fold_in the loop index for a fresh key "
                        f"per iteration")
                last_consume[name] = node


def _mentions_seedish(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and "seed" in sub.id.lower():
            return True
        if isinstance(sub, ast.Attribute) and "seed" in sub.attr.lower():
            return True
    return False


def _is_masked(node: ast.AST) -> bool:
    """True for `expr & 0xFFFFFFFF`-style sanctioned masking."""
    return isinstance(node, ast.BinOp) and isinstance(node.op, ast.BitAnd)


class SeedInt32Overflow(Rule):
    name = "seed-int32-overflow"
    code = "GL102"
    description = ("Python-int seed arithmetic fed to PRNGKey (or cast to "
                   "int32) can wrap; use fold_in or mask with 0xFFFFFFFF")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = ctx.call_name(node)
            if name in ("jax.random.PRNGKey", "jax.random.key"):
                if node.args and isinstance(node.args[0], ast.BinOp) \
                        and not _is_masked(node.args[0]):
                    yield self.finding(
                        ctx, node.args[0],
                        "seed arithmetic inside PRNGKey can wrap int32; use "
                        "jax.random.fold_in(PRNGKey(seed), i) or mask with "
                        "& 0xFFFFFFFF")
            elif name in ("numpy.int32", "jax.numpy.int32"):
                if node.args and _mentions_seedish(node.args[0]):
                    yield self.finding(
                        ctx, node,
                        "int32 cast of a seed expression truncates host "
                        "seed arithmetic; keep seeds int64 and mask "
                        "explicitly (& 0xFFFFFFFF) at key-construction time")
