"""The package's layers import downwards (victoriametrics_tpu/__init__.py's
layer map): nothing in ops, native, utils, models or storage may import
query, httpapi, apps, parallel or ingest, at module level or inside a
function.  The edges that still do are written down in KNOWN_DEBTS and
held by equality, so the list can only shrink: repay a debt and its line
goes, add an edge and the test fails."""

import ast
import os
import subprocess
import sys

import pytest

import victoriametrics_tpu

PKG = "victoriametrics_tpu"
PKG_DIR = os.path.dirname(victoriametrics_tpu.__file__)
LOWER = ("ops", "native", "utils", "models", "storage")
UPPER = ("query", "httpapi", "apps", "parallel", "ingest")

KNOWN_DEBTS = {
    # labels_from_series_key: a series key's text form belongs to storage
    ("storage.storage", "ingest.parsers"),
    # the self-scrape parses its interval and its own exposition text
    ("utils.selfscrape", "query.metricsql.parser"),
    ("utils.selfscrape", "ingest.parsers"),
}


def _is_module(dotted: str) -> bool:
    path = os.path.join(PKG_DIR, *dotted.split("."))
    return os.path.isdir(path) or os.path.isfile(path + ".py")


def _imports_of(path: str, module: str) -> set[str]:
    """Every victoriametrics_tpu module `module` imports anywhere in its
    source, as a dotted name under the package root."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), path)
    # a module's relative imports resolve from its package
    here = module.split(".")
    if os.path.basename(path) != "__init__.py":
        here = here[:-1]
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name.startswith(PKG + "."):
                    out.add(a.name[len(PKG) + 1:])
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = here[:len(here) - (node.level - 1)]
                base = base + (node.module.split(".") if node.module else [])
            elif node.module and (node.module == PKG or
                                  node.module.startswith(PKG + ".")):
                base = node.module.split(".")[1:]
            else:
                continue
            for a in node.names:
                sub = ".".join(base + [a.name])
                out.add(sub if _is_module(sub) else ".".join(base))
    return out


def _upward_edges(layer: str) -> set[tuple[str, str]]:
    edges = set()
    for root, _dirs, files in os.walk(os.path.join(PKG_DIR, layer)):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(root, fn)
            module = os.path.relpath(path, PKG_DIR)[:-3].replace(os.sep, ".")
            if module.endswith(".__init__"):
                module = module[:-len(".__init__")]
            for target in _imports_of(path, module):
                if target.split(".")[0] in UPPER:
                    edges.add((module, target))
    return edges


@pytest.mark.parametrize("layer", LOWER)
def test_lower_layer_imports_no_upper_layer(layer):
    known = {e for e in KNOWN_DEBTS if e[0].split(".")[0] == layer}
    assert _upward_edges(layer) == known


@pytest.mark.parametrize("module", ["storage.storage", "models.tile_cache",
                                    "utils.metrics", "ops.device_rollup"])
def test_importing_a_lower_module_loads_no_upper_layer(module):
    code = (
        "import sys\n"
        f"import {PKG}.{module}\n"
        "print(sorted(m for m in sys.modules\n"
        f"             if m.startswith('{PKG}.')\n"
        f"             and m.split('.')[1] in {UPPER!r}))\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       cwd=os.path.dirname(PKG_DIR))
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == "[]"
