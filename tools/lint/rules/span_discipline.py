"""span-discipline: spans are context-managed and near-free on the fast path.

Two invariants of the ISSUE 12 tracing layer:

* **Spans exist only as context managers.** The chaos suites prove every
  trace is balanced (each start has exactly one end on every exit path,
  including KillPoint unwinds) — a property that holds structurally for
  ``with trace.span(...):`` and cannot be proven for manual begin/end
  pairs. The rule flags any call to a manual pairing API
  (``begin_span``/``end_span`` — deliberately not exported by ``trace``,
  so a finding means someone re-grew one) and any ``trace.span(...)`` /
  ``span(...)`` call (or ``phase(...)``, its mode-``on``-only detail
  tier) that is not the context expression of a ``with`` item (assigning
  the manager and entering it by hand re-opens the
  unbalanced-on-exception hole).

* **The dispatch fast path pays nothing for disabled tracing.** Inside
  the modules hosting the ``fast_path_roots`` (``span_hot_modules``
  config: core/tensor.py, dispatch_cache.py, autograd.py,
  step_capture.py) even the disabled-mode probe — a call returning the
  shared no-op manager — is too much per op. Span/instant construction
  there must sit lexically under an ``if ...enabled():`` guard, the same
  discipline ``_op_metrics_hook`` established in PR 1 (hooks are None
  when off; the hot path pays one is-None probe).

``span_impl_paths`` (default ``paddle_tpu/observability/trace.py``) is
exempt — it IS the implementation.
"""

from __future__ import annotations

import ast

from ..astutil import path_matches
from ..engine import FileContext, Rule, register_rule

#: manual begin/end pairing APIs — trace deliberately does not export
#: these; a call site means someone rebuilt manual pairing
_MANUAL_NAMES = {"begin_span", "end_span"}

#: trace-layer constructors that must be guarded in hot modules
_GUARDED_NAMES = {"span", "phase", "instant", "phase_instant", "new_trace",
                  "record"}

#: the context-manager constructors: legal only as a ``with`` item
_SPAN_NAMES = {"span", "phase"}


def _trace_aliases(tree: ast.Module):
    """(names bound to the trace module, directly-imported span-layer
    names) across every import in the file — module-scope and deferred."""
    mod_aliases, direct = set(), set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod == "observability" or mod.endswith(".observability") \
                    or mod == "paddle_tpu.observability":
                for a in node.names:
                    if a.name == "trace":
                        mod_aliases.add(a.asname or "trace")
            elif mod.endswith("observability.trace") or mod == "trace":
                for a in node.names:
                    if a.name in _GUARDED_NAMES | _MANUAL_NAMES:
                        direct.add(a.asname or a.name)
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name.endswith("observability.trace"):
                    mod_aliases.add(a.asname or a.name.split(".")[0])
    return mod_aliases, direct


def _call_kind(call: ast.Call, mod_aliases, direct):
    """The trace-layer function a call targets ("span", "begin_span", ...)
    or None when the call is unrelated."""
    f = call.func
    if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name) \
            and f.value.id in mod_aliases:
        return f.attr if f.attr in _GUARDED_NAMES | _MANUAL_NAMES else None
    if isinstance(f, ast.Name) and f.id in direct:
        return f.id
    # manual pairing is flagged by bare name too: trace does not export
    # begin_span/end_span, so ANY spelling of them is a re-grown pair
    if isinstance(f, ast.Attribute) and f.attr in _MANUAL_NAMES:
        return f.attr
    if isinstance(f, ast.Name) and f.id in _MANUAL_NAMES:
        return f.id
    return None


def _is_enabled_guard(test: ast.AST) -> bool:
    """True when an ``if`` test consults the tracing enabled-probe
    (``...enabled()`` / ``...mode() != "off"``-style calls)."""
    for n in ast.walk(test):
        if isinstance(n, ast.Call):
            f = n.func
            name = f.attr if isinstance(f, ast.Attribute) else \
                getattr(f, "id", "")
            if name in ("enabled", "mode"):
                return True
    return False


@register_rule
class SpanDisciplineRule(Rule):
    name = "span-discipline"
    description = ("spans only via the span() context manager; no span "
                   "construction on the dispatch fast path outside an "
                   "enabled() guard")

    def check(self, ctx: FileContext):
        if path_matches(ctx.path, ctx.config.get(
                "span_impl_paths", ["paddle_tpu/observability/trace.py"])):
            return
        mod_aliases, direct = _trace_aliases(ctx.tree)
        hot = path_matches(ctx.path, ctx.config.get("span_hot_modules", []))
        findings = []

        # every span(...) call that IS a with-item context expression
        with_items = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    with_items.add(id(item.context_expr))

        def visit(node, guarded):
            if isinstance(node, ast.If):
                g = guarded or _is_enabled_guard(node.test)
                for child in node.body:
                    visit(child, g)
                for child in node.orelse:
                    visit(child, guarded)
                return
            if isinstance(node, ast.Call):
                kind = _call_kind(node, mod_aliases, direct)
                if kind in _MANUAL_NAMES:
                    findings.append(ctx.finding(
                        node, self.name,
                        f"manual span pairing `{kind}(...)`: spans exist "
                        f"only as `with trace.span(...):` context managers "
                        f"— balanced begin/end on every exit path is the "
                        f"flight recorder's structural guarantee"))
                elif kind in _SPAN_NAMES and id(node) not in with_items:
                    findings.append(ctx.finding(
                        node, self.name,
                        f"`{kind}(...)` used outside a `with` item: entering "
                        f"the manager by hand re-opens the unbalanced-on-"
                        f"exception hole — write `with trace.{kind}(...):`"))
                elif kind is not None and hot and not guarded:
                    findings.append(ctx.finding(
                        node, self.name,
                        f"`{kind}(...)` on the dispatch fast path without "
                        f"an enabled() guard: this module hosts "
                        f"fast_path_roots, where even the disabled-mode "
                        f"probe is per-op overhead — wrap in "
                        f"`if ...enabled():` (the _op_metrics_hook "
                        f"discipline)"))
            for child in ast.iter_child_nodes(node):
                visit(child, guarded)

        visit(ctx.tree, False)
        return findings
