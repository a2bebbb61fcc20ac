"""The direction of imports between the packages of ``pinot_tpu``.

Low to high: ``common``, ``utils`` < ``pql``, ``segment``, ``startree``,
``transport`` < ``engine``, ``parallel`` < ``realtime``, ``server`` <
``broker``, ``controller`` < ``tools``, ``api``.  A module may import
its own level and below, at module level or inside a function.  The
pairs of ``ALLOWED`` are the upward imports the tree still has, each a
debt ROADMAP names (D14); the list may only shrink: a case fails on an
upward import that is not on it, and on a listed pair that no longer
occurs.

The last two walks are not over packages.  One is over calls: every
``lax.sort`` of the package says whether it is stable.  The other holds
the serving ladder to its one place (``engine/ladder.py``): EXPLAIN and
the prewarm worker import no tier's decision, no plan builder and no
kernel builder of their own, and the executor chooses no program.
"""
import ast
import os

import pytest

PACKAGE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "pinot_tpu")
LEVELS = (
    ("common", "utils"),
    ("pql", "segment", "startree", "transport"),
    ("engine", "parallel"),
    ("realtime", "server"),
    ("broker", "controller"),
    ("tools", "api"),
)
LEVEL = {package: i for i, level in enumerate(LEVELS) for package in level}

# (importing module, imported module): ROADMAP D14, in its order
ALLOWED = {
    # the oracle that audits the device shares its arithmetic with the tests' reference
    ("engine.host_fallback", "tools.scan_engine"),
    # the wire codec and the fault injector know the engine's result and error types
    ("common.datatable", "engine.results"),
    ("common.faults", "engine.dispatch"),
    ("common.faults", "transport.tcp"),
    # the audit plane and the plan statistics re-derive plan digests and reduce answers
    ("utils.audit", "engine.plandigest"),
    ("utils.audit", "engine.reduce"),
    ("utils.audit", "pql"),
    ("utils.planstats", "engine.plandigest"),
    # the segment writer builds zone maps; the star-tree builds and answers with the engine's types
    ("segment.format", "engine.zonemap"),
    ("startree.builder", "engine.hll"),
    ("startree.operator", "engine.plan"),
    ("startree.operator", "engine.results"),
    # the serving roles reach up for the broker's freshness type and the controller's resource manager
    ("realtime.llc", "broker.freshness"),
    ("realtime.llc", "controller.resource_manager"),
    ("server.instance", "broker.freshness"),
    ("server.network_starter", "broker.freshness"),
    ("server.network_starter", "controller.resource_manager"),
    ("server.starter", "controller.resource_manager"),
}


def imported_modules(tree: ast.AST):
    """Every ``pinot_tpu`` module a file imports, as ``package.module``."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names = [alias.name for alias in node.names]
        elif isinstance(node, ast.ImportFrom) and not node.level:
            names = [node.module or ""]
            if names[0].count(".") < 2:  # from pinot_tpu[.package] import module
                names = [f"{names[0]}.{alias.name}" for alias in node.names]
        else:
            continue
        for name in names:
            parts = name.split(".")
            if parts[0] != "pinot_tpu" or len(parts) < 2:
                continue
            file = os.path.join(PACKAGE, *parts[1:3])
            is_module = os.path.isfile(file + ".py") or os.path.isdir(file)
            yield ".".join(parts[1:3]) if is_module else parts[1]  # a name the package itself exports


def parsed_modules(folder: str):
    """(path, syntax tree) of every module under ``folder``."""
    for sub, _, files in os.walk(folder):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(sub, name)
                with open(path) as f:
                    yield path, ast.parse(f.read(), path)


def upward_imports(package: str) -> set:
    found = set()
    for path, tree in parsed_modules(os.path.join(PACKAGE, package)):
        module = os.path.relpath(path, PACKAGE)[:-3].replace(os.sep, ".")
        for target in imported_modules(tree):
            if LEVEL.get(target.split(".")[0], -1) > LEVEL[package]:
                found.add((module, target))
    return found


# tools and api stand on top: nothing is above them
@pytest.mark.parametrize("package", [p for level in LEVELS[:-1] for p in level])
def test_no_module_imports_a_package_above_it(package):
    found = upward_imports(package)
    allowed = {pair for pair in ALLOWED if pair[0].split(".")[0] == package}
    assert not found - allowed, f"{package} imports upward: {sorted(found - allowed)}"
    assert not allowed - found, f"turned since: take off ALLOWED and ROADMAP D14: {sorted(allowed - found)}"


def sort_calls_without_is_stable() -> list:
    found = []
    for path, tree in parsed_modules(PACKAGE):
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            callee = ast.unparse(node.func)
            if (callee == "sort" or callee.endswith("lax.sort")) and "is_stable" not in {k.arg for k in node.keywords}:
                found.append(f"{os.path.relpath(path, PACKAGE)}:{node.lineno}")
    return found


def test_every_device_sort_says_whether_it_is_stable():
    """``lax.sort``'s default is ``is_stable=True``, and on the chip a
    stable sort is the unstable one with an operand of row numbers
    carried as the last key: twice the work where the operands are all
    keys (PR 44: 3.3 ns a row against 1.6 over 100.7M packed keys).  A
    call that leaves the choice to the default has not made it."""
    assert not sort_calls_without_is_stable()


# what decides a tier, builds a plan or its inputs, or chooses a program
LADDER_INGREDIENTS = {
    "index_path_decision", "bitsliced_decision", "plan_forced_host", "group_by_host_reason",
    "build_static_plan", "build_query_inputs", "chunk_rows_limit", "plan_chunkable",
}


def test_the_ladder_is_read_not_walked_again():
    """``engine/explain.py`` holds ``build_explain_node`` and
    ``build_prewarm_spec`` (all that ``server/prewarm.py`` asks of it):
    both read ``ladder.TIERS`` and the device tier's derivations.  An
    import of an ingredient there, or a call of one of the executor's
    underscore methods, is a second walk of the order begun; a ``_kernel``
    or ``_block_kernel`` on the executor is a second choice of a plan's
    program beside ``kernel.plan_program``."""
    trees = dict(parsed_modules(os.path.join(PACKAGE, "engine")))
    explain = trees[os.path.join(PACKAGE, "engine", "explain.py")]
    imported = {alias.name for node in ast.walk(explain) if isinstance(node, ast.ImportFrom) for alias in node.names}
    assert not {name for name in imported if name in LADDER_INGREDIENTS or name.startswith("make_packed_")}
    reached = {
        node.attr
        for node in ast.walk(explain)
        if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name) and node.value.id == "executor"
    }
    assert not {name for name in reached if name.startswith("_")}
    executor = trees[os.path.join(PACKAGE, "engine", "executor.py")]
    defined = {node.name for node in ast.walk(executor) if isinstance(node, ast.FunctionDef)}
    assert not defined & {"_kernel", "_block_kernel"}
