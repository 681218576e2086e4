"""compat-shim (RL003): JAX drift-prone APIs route through repro.compat.

Invariant: ``repro/compat.py`` is the ONE import site for the JAX APIs
that have moved between releases and may move again (``shard_map``'s
home and kwarg names, ``make_mesh`` / ``AxisType`` and the ``Auto`` axis
default, and ``Mesh`` as the shim's re-export anchor). Any direct import
or attribute use of those names outside compat.py bypasses the shim: a
bare ``jax.make_mesh`` gives ``Explicit`` axes the engines are not
written for, and the next API move would have to be chased through
every call site.

Flagged outside ``src/repro/compat.py``:

* ``from jax.sharding import Mesh`` / ``AxisType``
* ``from jax.experimental.shard_map import ...`` (any name)
* ``from jax import shard_map / make_mesh``
* attribute uses ``jax.shard_map`` / ``jax.make_mesh`` /
  ``jax.sharding.AxisType`` / ``jax.sharding.Mesh``

``PartitionSpec`` / ``NamedSharding`` have stable homes and stay
importable directly.
"""
from __future__ import annotations

import ast

from tools.lint import astutil
from tools.lint.core import LintPass, Module, Project

_SHIM_FILE = "src/repro/compat.py"
_SHARDING_NAMES = {"Mesh", "AxisType"}
_JAX_TOP_NAMES = {"shard_map", "make_mesh"}
_ATTR_USES = {
    "jax.shard_map",
    "jax.make_mesh",
    "jax.sharding.AxisType",
    "jax.sharding.Mesh",
    "jax.experimental.shard_map.shard_map",
}


class CompatShimPass(LintPass):
    name = "compat-shim"
    code = "RL003"
    guideline = "C-compat"
    description = (
        "drift-prone jax APIs (shard_map/Mesh/AxisType/make_mesh) "
        "imported only via repro.compat"
    )

    def check_module(self, module: Module, project: Project):
        if module.rel.endswith(_SHIM_FILE) or module.rel == "repro/compat.py":
            return
        for node in ast.walk(module.tree):
            if isinstance(node, ast.ImportFrom):
                yield from self._check_import_from(module, node)
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name == "jax.experimental.shard_map":
                        yield self._flag(module, node, alias.name)
            elif isinstance(node, ast.Attribute):
                name = astutil.dotted_name(node)
                if name in _ATTR_USES:
                    yield self._flag(module, node, name)

    def _check_import_from(self, module, node):
        mod = node.module or ""
        for alias in node.names:
            if mod == "jax.sharding" and alias.name in _SHARDING_NAMES:
                yield self._flag(module, node, f"jax.sharding.{alias.name}")
            elif mod == "jax.experimental.shard_map":
                yield self._flag(
                    module, node, f"jax.experimental.shard_map.{alias.name}"
                )
            elif mod == "jax" and alias.name in _JAX_TOP_NAMES:
                yield self._flag(module, node, f"jax.{alias.name}")
            elif mod == "jax.experimental" and alias.name == "shard_map":
                yield self._flag(module, node, "jax.experimental.shard_map")

    def _flag(self, module, node, name):
        short = name.split(".")[-1]
        return self.finding(
            module,
            node,
            f"`{name}` used directly; import `{short}` from "
            "`repro.compat` -- the single API-drift shim site (Auto "
            "mesh axes; one place to absorb the next API move)",
        )
