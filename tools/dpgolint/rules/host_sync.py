"""DPG003: no implicit device->host transfers in hot-path loop bodies.

The solver and serving drivers are engineered around ONE stacked readback
per eval (``_make_central_metrics`` / the batched metrics program) —
every extra materialization is a device->host round-trip in the
innermost loop.  This pass flags the expressions that implicitly
force a transfer inside ``for``/``while`` bodies of the configured hot
functions:

* ``np.asarray(...)`` / ``np.array(...)`` on anything,
* ``.block_until_ready()`` and ``.item()``,
* ``float(...)`` / ``int(...)`` / ``bool(...)`` applied directly to a
  call result or a subscript/attribute of one (values already fetched to
  host — plain names — don't transfer again and are not flagged),
* any call whose (dotted-tail) name appears in the configured
  ``sync_calls`` list — the project's OWN fetch seams (``rbcd._host_fetch``,
  the one function every sanctioned driver readback routes through since
  the verdict-word loop), so wrapping a transfer in the seam helper does
  not hide it from the rule.

The sanctioned readback seams (the verdict-word, lazy-history and
terminal-epilogue fetches) carry reviewed ``# dpgolint: disable=DPG003``
suppressions; anything else is a hot-loop regression.
"""

from __future__ import annotations

import ast

from ..core import Module, Rule, dotted_name, register, \
    walk_skipping_functions

_NUMPY_FETCHERS = {"asarray", "array", "ascontiguousarray", "copy"}
_CAST_BUILTINS = {"float", "int", "bool"}


def _numpy_aliases(tree: ast.AST) -> set[str]:
    aliases = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "numpy":
                    aliases.add(a.asname or "numpy")
    return aliases


def _forces_fetch(arg: ast.AST) -> bool:
    """Casts transfer only when applied to fresh device values: a call
    result, or a subscript/attribute peeled off one."""
    if isinstance(arg, ast.Call):
        return True
    if isinstance(arg, (ast.Subscript, ast.Attribute)):
        return _forces_fetch(arg.value)
    return False


@register
class HostSyncRule(Rule):
    id = "DPG003"
    name = "host-sync-hazard"
    invariant = ("hot-path loop bodies perform no implicit device->host "
                 "transfers outside the sanctioned readback seams")

    def check(self, module: Module, config) -> list:
        fopts = config.file_options(self.id, module.relpath)
        ropts = config.rule_options(self.id)
        hot = set(fopts.get("hot_functions",
                            ropts.get("hot_functions", [])))
        if not hot:
            return []
        sync_calls = set(fopts.get("sync_calls",
                                   ropts.get("sync_calls", [])))
        np_names = _numpy_aliases(module.tree)
        findings = []
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name in hot:
                findings.extend(self._check_fn(module, node, np_names,
                                               sync_calls))
        return findings

    def _check_fn(self, module: Module, fn: ast.AST, np_names: set[str],
                  sync_calls: set[str]) -> list:
        out = []
        seen: set[int] = set()
        for loop in walk_skipping_functions(fn):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            for node in walk_skipping_functions(loop):
                if not isinstance(node, ast.Call) or id(node) in seen:
                    continue
                seen.add(id(node))
                hit = self._classify(node, np_names, sync_calls)
                if hit:
                    out.append(self.finding(
                        module, node,
                        f"{hit} inside the {fn.name!r} hot loop — implicit "
                        "device->host transfer; batch it into the "
                        "verdict program's device-side history or add a "
                        "reviewed suppression at a sanctioned seam"))
        return out

    def _classify(self, call: ast.Call, np_names: set[str],
                  sync_calls: set[str] = frozenset()) -> str | None:
        name = dotted_name(call.func)
        if name is not None:
            parts = name.split(".")
            if name in sync_calls or parts[-1] in sync_calls:
                return f"{name}(...) [configured sync seam]"
            if len(parts) >= 2 and parts[0] in np_names \
                    and parts[-1] in _NUMPY_FETCHERS:
                return f"{name}(...)"
            if name in _CAST_BUILTINS and call.args \
                    and _forces_fetch(call.args[0]):
                return f"{name}() on a call result"
            if parts[-1] in ("item", "block_until_ready") and len(parts) > 1:
                return f".{parts[-1]}()"
        elif isinstance(call.func, ast.Attribute) \
                and call.func.attr in ("item", "block_until_ready"):
            return f".{call.func.attr}()"
        return None
