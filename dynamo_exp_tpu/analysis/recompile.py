"""recompile-hazard checker: variant-cache keys must be bucketed.

The engine's compiled-program caches (`_ragged_fns`, the jit-internal
gather/scatter shape cache) key variants
by static shapes. The whole lattice stays O(log) *only* because every
shape-carrying key component passes through a bucket helper
(``ragged_tokens_bucket_for``, ``ragged_page_bucket_for``,
``page_move_bucket_for``, …). One raw dynamic int in a key position —
``self._ragged_fn(len(part), …)`` — compiles a fresh program per
distinct value under real load: a recompile storm the steady-state
guard test only catches for the shapes it happens to drive.

A ``VariantSiteManifest`` names the callables whose argument positions
become cache keys. An argument is accepted when it traces (through
per-function dataflow) to:

- a call to any ``*bucket_for`` helper,
- an int constant, or ``min``/``max`` over accepted values,
- static config (an attribute path containing ``cfg``),
- ``np.full(bucket, …)`` / ``jnp.asarray(bucketed)`` of an accepted
  value (the padded index-vector idiom of the page movers).

Anything else is flagged; deliberate carries (a chained window reusing
the dispatched window's already-bucketed row count) get an inline
``# dynlint: recompile-hazard(reason)``.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass

from .core import Finding, attr_chain, dataflow_units, own_nodes

RULE = "recompile-hazard"

_BUCKET_SUFFIX = "bucket_for"


@dataclass(frozen=True)
class VariantSiteManifest:
    path: str
    # callee name (Name or self.<name>) -> shape-carrying arg positions,
    # and the names of its shape-carrying keywords where it says which
    # they are (``_launch``: the bucket among a dozen that are no key)
    sites: dict[str, tuple[int | str, ...]]


def _callee_name(func: ast.AST) -> str | None:
    if isinstance(func, ast.Name):
        return func.id
    if (
        isinstance(func, ast.Attribute)
        and isinstance(func.value, ast.Name)
        and func.value.id == "self"
    ):
        return func.attr
    return None


class _BucketFlow:
    """Per-function, per-line classification of names holding bucketed
    values.

    Bindings are processed in source order and each one records whether
    the name was bucketed *after* it — so a rebind to anything not
    provably bucketed KILLS the name from that point on (`rows =
    bucket_for(...)` then `rows = len(part)` can't launder the raw
    int), and a bucketed rebind *after* a raw use can't retroactively
    whitewash the earlier dispatch (use sites consult the last binding
    at or before their own line)."""

    def __init__(self, fn: ast.AST):
        # name -> [(bind line, bucketed after this bind)], line-ordered.
        self._history: dict[str, list[tuple[int, bool]]] = {}
        binds: list[tuple[int, int, str, ast.AST | None]] = []
        for node in own_nodes(fn):
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                t = node.targets[0]
                if isinstance(t, ast.Name):
                    binds.append(
                        (node.lineno, node.col_offset, t.id, node.value)
                    )
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                if isinstance(node.target, ast.Name):
                    binds.append(
                        (node.lineno, node.col_offset, node.target.id, None)
                    )
            elif isinstance(node, ast.For) and isinstance(
                node.target, ast.Name
            ):
                binds.append(
                    (node.lineno, node.col_offset, node.target.id, None)
                )
        for line, _, name, value in sorted(binds, key=lambda b: b[:2]):
            bucketed = value is not None and self.ok(value, line)
            self._history.setdefault(name, []).append((line, bucketed))

    def ok(self, node: ast.AST, line: int) -> bool:
        if isinstance(node, ast.Constant):
            return isinstance(node.value, (int, bool))
        if isinstance(node, ast.Name):
            state = False
            for bind_line, bucketed in self._history.get(node.id, ()):
                if bind_line > line:
                    break
                state = bucketed
            return state
        if isinstance(node, ast.Attribute):
            chain = attr_chain(node)
            return "cfg" in chain[:-1] if chain else False
        if isinstance(node, ast.Call):
            chain = attr_chain(node.func)
            if chain and chain[-1].endswith(_BUCKET_SUFFIX):
                return True
            if chain and chain[-1] in ("min", "max") and node.args:
                return all(self.ok(a, line) for a in node.args)
            # np.full(bucket, ...) / jnp.asarray(bucketed): the padded
            # page-id vector whose static length IS the bucket.
            if chain and chain[0] in ("np", "numpy") and chain[-1] == "full":
                return bool(node.args) and self.ok(node.args[0], line)
            if chain and chain[0] in ("jnp", "jax") and chain[-1] in (
                "asarray",
                "array",
            ):
                return bool(node.args) and self.ok(node.args[0], line)
        return False


class RecompileHazardChecker:
    rule = RULE

    def __init__(
        self, manifests: tuple[VariantSiteManifest, ...] | None = None
    ):
        if manifests is None:
            from .zones import VARIANT_SITE_MANIFESTS

            manifests = VARIANT_SITE_MANIFESTS
        self.manifests = manifests

    def check(
        self, rel_path: str, tree: ast.Module, source: str
    ) -> list[Finding]:
        sites: dict[str, tuple[int | str, ...]] = {}
        for m in self.manifests:
            if m.path == rel_path:
                sites.update(m.sites)
        if not sites:
            return []
        findings: list[Finding] = []
        for fn in dataflow_units(tree):
            flow = _BucketFlow(fn)
            for node in own_nodes(fn):
                if not isinstance(node, ast.Call):
                    continue
                callee = _callee_name(node.func)
                if callee not in sites:
                    continue
                # Don't flag the builder's own recursive mentions (the
                # def itself is matched by name, not the call).
                suspect: list[tuple[str, ast.AST]] = []
                named = {p for p in sites[callee] if isinstance(p, str)}
                for pos in sites[callee]:
                    if isinstance(pos, int) and pos < len(node.args):
                        suspect.append((f"arg {pos}", node.args[pos]))
                # Keyword spellings can't be mapped to key positions
                # without the signature, so unless the manifest names the
                # callee's shape-carrying keywords EVERY keyword value
                # must be bucket-derived (the builders are internal and
                # called positionally by convention; a keyword call site
                # that trips this either gets the positional spelling or
                # a reviewed waiver).
                for kw in node.keywords:
                    if kw.arg is not None and (not named or kw.arg in named):
                        suspect.append((f"keyword {kw.arg!r}", kw.value))
                for label, value in suspect:
                    if not flow.ok(value, node.lineno):
                        arg_src = ast.unparse(value)
                        findings.append(
                            Finding(
                                rule=RULE,
                                file=rel_path,
                                line=node.lineno,
                                col=node.col_offset,
                                end_line=node.end_lineno or node.lineno,
                                message=(
                                    f"compiled-variant key {label} of "
                                    f"{callee}(...) is not bucket-derived: "
                                    f"{arg_src!r} — route it through a "
                                    f"*_bucket_for helper"
                                ),
                            )
                        )
        return findings

    def check_source(self, rel_path: str, source: str) -> list[Finding]:
        return self.check(rel_path, ast.parse(source), source)
