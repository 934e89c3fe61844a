"""device-access: direct ``jax.devices``/``jax.device_put`` calls belong
to the device module and the backend-fallback module only.

PR 6 added backend-fallback dispatch (``paddle_tpu/core/fallback.py``):
per-op placement decisions — which device an op actually executes on —
now have exactly two sanctioned owners: ``paddle_tpu/device.py`` (the
Place taxonomy, ``set_device``, the memoized device-list probes) and the
fallback module (the CPU degrade path). An ad-hoc
``jax.devices()``/``jax.device_put`` call anywhere else bypasses both: it
pins placement the fallback registry can't see, and it initialises a
backend from a module that a chip-less parent process may import. Route
through ``device.Place``/
``default_jax_device`` or the fallback helpers instead; load-bearing
survivors (the distributed mesh-sharding layer predates this rule) are
grandfathered in the baseline with reasons, per the PR-3 convention.

The rule flags ``jax.devices(...)`` / ``jax.device_put(...)`` attribute
calls (including via ``import jax as <alias>``) and ``from jax import
devices/device_put`` bindings, outside ``device_access_allowed_paths``
(config; default ``paddle_tpu/device.py`` + ``paddle_tpu/core/fallback.py``).
"""

from __future__ import annotations

import ast

from ..astutil import path_matches
from ..engine import FileContext, Rule, register_rule

_CALLEES = ("devices", "device_put")


def _jax_aliases(tree: ast.Module):
    """Names bound to the ``jax`` module by any import in the file."""
    aliases = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "jax":
                    aliases.add(a.asname or "jax")
                elif a.name.startswith("jax.") and a.asname is None:
                    # `import jax.numpy` binds the top-level name `jax`
                    aliases.add("jax")
    return aliases


@register_rule
class DeviceAccessRule(Rule):
    name = "device-access"
    description = ("direct jax.devices()/jax.device_put outside "
                   "paddle_tpu/device.py and core/fallback.py (route "
                   "through device.Place or the fallback helpers)")

    def check(self, ctx: FileContext):
        allowed = ctx.config.get("device_access_allowed_paths",
                                 ["paddle_tpu/device.py",
                                  "paddle_tpu/core/fallback.py"])
        if path_matches(ctx.path, allowed):
            return
        aliases = _jax_aliases(ctx.tree)
        rule = self.name
        findings = []
        for node in ast.walk(ctx.tree):
            if (isinstance(node, ast.Attribute)
                    and node.attr in _CALLEES
                    and isinstance(node.value, ast.Name)
                    and node.value.id in aliases):
                # message stays line- and function-free so every use of
                # one callee in a file collapses to a single counted
                # baseline entry (the text report still carries path:line)
                findings.append(ctx.finding(
                    node, rule,
                    f"direct `jax.{node.attr}` — device placement belongs "
                    f"to paddle_tpu/device.py (Place/jax_device) or the "
                    f"backend-fallback module (core/fallback.py); route "
                    f"through those, or baseline with the reason this "
                    f"site must own placement itself"))
            elif isinstance(node, ast.ImportFrom) and node.module == "jax":
                for a in node.names:
                    if a.name in _CALLEES:
                        findings.append(ctx.finding(
                            node, rule,
                            f"`from jax import {a.name}` — device "
                            f"placement belongs to paddle_tpu/device.py "
                            f"or core/fallback.py; route through those, "
                            f"or baseline with the reason this site must "
                            f"own placement itself"))
        return findings
