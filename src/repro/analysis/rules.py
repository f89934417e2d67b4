"""The repolint rule set: one AST rule per documented repo invariant.

Each rule names the ROADMAP "Standing constraints" entry (or PR decision)
it encodes; CONTRIBUTING.md carries the user-facing table.  Rules are
syntactic on purpose — they encode the *convention* (imports, call paths,
literal shapes), not a type system, so a finding is cheap to confirm by
eye and cheap to suppress with a justification when the convention does
not apply (``# repolint: disable=<rule>``).
"""
from __future__ import annotations

import ast
from typing import List, Optional

from repro.analysis.framework import Rule, Violation, register


def attr_chain(node: ast.AST) -> Optional[str]:
    """Dotted name for Name/Attribute chains (``a.b.c``), else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def terminal_name(node: ast.AST) -> Optional[str]:
    """Rightmost identifier of a Name/Attribute (``a.b.c`` -> ``c``)."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


# ---------------------------------------------------------------------------
# compat-drift — ROADMAP: "New sharding/mesh code must import from
# repro.compat, not raw jax names" (one import surface, PR 3)
# ---------------------------------------------------------------------------

@register
class CompatDriftRule(Rule):
    name = "compat-drift"
    description = ("sharding/mesh/cost_analysis surfaces must go through "
                   "repro.compat, not raw jax.sharding/jax.experimental "
                   "names (one import surface for jax's sharding API)")
    include = ("src/repro/",)
    exclude = ("src/repro/compat.py", "src/repro/analysis/")

    # module prefixes that repro.compat fronts: importing them raw scatters
    # the conventions compat.py keeps in one place
    BRIDGED_MODULES = ("jax.sharding", "jax.experimental")
    # top-level jax names that repro.compat wraps
    BRIDGED_NAMES = {"jax.set_mesh", "jax.shard_map", "jax.make_mesh"}
    # intentionally-raw allowlist: Pallas is a kernel-only surface that
    # compat.py does not front, so kernels import it directly
    PALLAS_DIRS = ("src/repro/kernels/",)
    PALLAS_PREFIX = "jax.experimental.pallas"

    def _in_pallas_dir(self, path: str) -> bool:
        return any(path.startswith(d) for d in self.PALLAS_DIRS)

    def _pallas_ok(self, module: str, path: str) -> bool:
        return (module.startswith(self.PALLAS_PREFIX)
                and self._in_pallas_dir(path))

    def check(self, tree: ast.Module, path: str) -> List[Violation]:
        rule = self
        out: List[Violation] = []

        def bad_module(module: str) -> bool:
            return any(module == m or module.startswith(m + ".")
                       for m in self.BRIDGED_MODULES)

        class V(ast.NodeVisitor):
            def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
                mod = node.module or ""
                if bad_module(mod) and not rule._pallas_ok(mod, path):
                    # `from jax.experimental import pallas` resolves the
                    # allowlisted module via the alias, not the module field
                    if not (rule._in_pallas_dir(path) and all(
                            f"{mod}.{a.name}".startswith(rule.PALLAS_PREFIX)
                            for a in node.names)):
                        out.append(rule.violation(
                            path, node,
                            f"import from {mod!r}: use the repro.compat "
                            f"re-export instead"))
                elif mod == "jax":
                    for alias in node.names:
                        full = f"jax.{alias.name}"
                        if alias.name in ("sharding", "experimental") \
                                or full in rule.BRIDGED_NAMES:
                            out.append(rule.violation(
                                path, node,
                                f"'from jax import {alias.name}': use "
                                f"repro.compat instead"))
                self.generic_visit(node)

            def visit_Import(self, node: ast.Import) -> None:
                for alias in node.names:
                    if bad_module(alias.name) \
                            and not rule._pallas_ok(alias.name, path):
                        out.append(rule.violation(
                            path, node,
                            f"import {alias.name}: use repro.compat "
                            f"instead"))

            def visit_Attribute(self, node: ast.Attribute) -> None:
                chain = attr_chain(node)
                if chain is None:
                    # not a pure a.b.c chain; keep looking inside (e.g.
                    # f().sharding.Mesh holds a nested chain-rooted attr)
                    self.generic_visit(node)
                    return
                if bad_module(chain) or any(
                        chain.startswith(m + ".")
                        for m in rule.BRIDGED_MODULES):
                    if not rule._pallas_ok(chain, path):
                        out.append(rule.violation(
                            path, node,
                            f"{chain}: use the repro.compat re-export "
                            f"instead"))
                elif chain in rule.BRIDGED_NAMES:
                    out.append(rule.violation(
                        path, node,
                        f"{chain}: wrapped by repro.compat — call "
                        f"repro.compat.{chain.split('.', 1)[1]} instead"))
                # never descend: the inner Attributes are this same chain

            def visit_Call(self, node: ast.Call) -> None:
                if isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "cost_analysis":
                    owner = node.func.value
                    if not (isinstance(owner, ast.Name)
                            and owner.id == "compat"):
                        out.append(rule.violation(
                            path, node,
                            "raw .cost_analysis() may return None — use "
                            "compat.cost_analysis(compiled)"))
                self.generic_visit(node)

        V().visit(tree)
        return out


# ---------------------------------------------------------------------------
# env-discipline — ROADMAP: "Platform/env knobs ... belong in
# repro/runtime.py, not ad-hoc os.environ writes" (PR 7)
# ---------------------------------------------------------------------------

def _is_environ(node: ast.AST) -> bool:
    chain = attr_chain(node)
    return chain in ("os.environ", "environ")


@register
class EnvDisciplineRule(Rule):
    name = "env-discipline"
    description = ("process-environment mutation is confined to "
                   "repro/runtime.py; everything else consumes its helpers")
    include = ("src/", "benchmarks/", "tests/")
    exclude = ("src/repro/runtime.py",)

    MUTATORS = {"setdefault", "update", "pop", "clear", "popitem",
                "__setitem__", "__delitem__"}

    def check(self, tree: ast.Module, path: str) -> List[Violation]:
        out: List[Violation] = []
        msg = ("os.environ mutated outside repro/runtime.py — add or use a "
               "runtime.py helper so env setup stays reproducible")
        for node in ast.walk(tree):
            targets: List[ast.AST] = []
            if isinstance(node, ast.Assign):
                targets = list(node.targets)
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            elif isinstance(node, ast.Delete):
                targets = list(node.targets)
            for t in targets:
                if isinstance(t, ast.Subscript) and _is_environ(t.value):
                    out.append(self.violation(path, node, msg))
            if isinstance(node, ast.Call):
                f = node.func
                if isinstance(f, ast.Attribute) and _is_environ(f.value) \
                        and f.attr in self.MUTATORS:
                    out.append(self.violation(path, node, msg))
                elif attr_chain(f) in ("os.putenv", "os.unsetenv"):
                    out.append(self.violation(path, node, msg))
        return out


# ---------------------------------------------------------------------------
# fraction-safety — ROADMAP: "fractional chips are exact 'p/q' Fractions,
# never floats ... a Fraction [or float] in grant_delta/_tenant_used
# corrupts the integer-indexed free-list buckets" (PR 6)
# ---------------------------------------------------------------------------

def _is_floaty(node: ast.AST) -> bool:
    """True when the expression syntactically produces a float: a float
    literal, a float() coercion, or true division anywhere in the tree."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and isinstance(sub.value, float):
            return True
        if isinstance(sub, ast.Call) and isinstance(sub.func, ast.Name) \
                and sub.func.id == "float":
            return True
        if isinstance(sub, ast.BinOp) and isinstance(sub.op, ast.Div):
            return True
    return False


@register
class FractionSafetyRule(Rule):
    name = "fraction-safety"
    description = ("no float literals / float() / true division flowing "
                   "into chips, grant_delta or tenant-usage counters — "
                   "fractional quanta are exact Fractions/ints")
    include = ("src/",)
    exclude = ("src/repro/analysis/",)

    COUNTER_NAMES = {"_tenant_chips", "_tenant_used"}

    def check(self, tree: ast.Module, path: str) -> List[Violation]:
        out: List[Violation] = []
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                if terminal_name(node.func) == "grant_delta":
                    for arg in list(node.args) + [k.value
                                                  for k in node.keywords]:
                        if _is_floaty(arg):
                            out.append(self.violation(
                                path, node,
                                "float-producing expression passed to "
                                "grant_delta() — exclusive-tier grants are "
                                "integer chips"))
                for kw in node.keywords:
                    if kw.arg == "chips" and _is_floaty(kw.value):
                        out.append(self.violation(
                            path, node,
                            "chips= built from a float expression — use "
                            "ints or exact 'p/q' Fractions"))
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                floaty_op = isinstance(node, ast.AugAssign) \
                    and isinstance(node.op, ast.Div)
                for t in targets:
                    if terminal_name(t) == "chips" and \
                            (floaty_op or _is_floaty(node.value)):
                        out.append(self.violation(
                            path, node,
                            "float expression assigned into .chips — "
                            "chip counts are ints or exact Fractions"))
                    elif isinstance(t, ast.Subscript) and \
                            terminal_name(t.value) in self.COUNTER_NAMES and \
                            (floaty_op or _is_floaty(node.value)):
                        out.append(self.violation(
                            path, node,
                            "float expression written into tenant usage "
                            "counters — quota accounting is integer-only "
                            "(fractional quanta never enter it)"))
        return out


# ---------------------------------------------------------------------------
# determinism — ROADMAP: committed trace artifacts must replay
# byte-identically (bench-gated); core/ may not depend on wall clock,
# unseeded RNG, or set iteration order
# ---------------------------------------------------------------------------

@register
class DeterminismRule(Rule):
    name = "determinism"
    description = ("no wall-clock reads, unseeded RNG, or set-ordered "
                   "iteration in core/ (byte-identical replay is gated)")
    include = ("src/repro/core/",)
    # the live control loop runs on real time by design; the replay path
    # (sim/cluster/scheduler/schema/compiler) is what the bench gate pins
    exclude = ("src/repro/core/service.py", "src/repro/core/executor.py")

    WALL_CLOCK = {"time.time", "time.monotonic", "time.perf_counter",
                  "time.time_ns", "datetime.now", "datetime.utcnow"}
    SEEDED_CTORS = {"Random", "SystemRandom", "RandomState", "default_rng",
                    "Generator", "SeedSequence", "PRNGKey"}
    # attributes known (by convention) to hold sets in core/
    KNOWN_SET_NAMES = {"abnormal_nodes"}

    def _set_expr(self, node: ast.AST) -> bool:
        if isinstance(node, (ast.Set, ast.SetComp)):
            return True
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id in ("set", "frozenset"):
            return True
        return terminal_name(node) in self.KNOWN_SET_NAMES

    def check(self, tree: ast.Module, path: str) -> List[Violation]:
        out: List[Violation] = []
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                chain = attr_chain(node.func) or ""
                if chain in self.WALL_CLOCK:
                    out.append(self.violation(
                        path, node,
                        f"{chain}() in core/ — replayed state must come "
                        f"from sim time, not the wall clock"))
                elif chain.startswith("random.") or \
                        chain.startswith("np.random.") or \
                        chain.startswith("numpy.random."):
                    if chain.rsplit(".", 1)[-1] not in self.SEEDED_CTORS:
                        out.append(self.violation(
                            path, node,
                            f"{chain}() uses the global unseeded RNG — "
                            f"construct a seeded Random/RandomState"))
                elif isinstance(node.func, ast.Name) \
                        and node.func.id == "list" and node.args \
                        and self._set_expr(node.args[0]):
                    out.append(self.violation(
                        path, node,
                        "list(<set>) materializes hash order — use "
                        "sorted(...) for a deterministic sequence"))
            iters: List[ast.AST] = []
            if isinstance(node, (ast.For, ast.AsyncFor)):
                iters = [node.iter]
            elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                                   ast.GeneratorExp)):
                iters = [g.iter for g in node.generators]
            for it in iters:
                if self._set_expr(it):
                    out.append(self.violation(
                        path, it,
                        "iterating a set in core/ follows hash order, "
                        "which varies across processes — iterate "
                        "sorted(...) instead"))
        return out


# ---------------------------------------------------------------------------
# hook-discipline — ROADMAP: "feed every bind_queues/job_* hook from new
# driver code or the ordered views drift from the sort-based oracle";
# cluster counters/buckets are maintained only inside _mutate-guarded paths
# ---------------------------------------------------------------------------

@register
class HookDisciplineRule(Rule):
    name = "hook-discipline"
    description = ("cluster/policy bookkeeping fields are written only by "
                   "their owning modules' guarded paths (_mutate, the "
                   "job_* hooks); drivers call the public API")
    include = ("src/",)
    # the owners: every write inside them sits on a guarded path that the
    # parity suites (check_counters, test_policy_queues) pin
    exclude = ("src/repro/core/cluster.py", "src/repro/core/scheduler.py",
               "src/repro/analysis/")

    NODE_FIELDS = {"used", "healthy", "draining", "speed", "fail_count",
                   "mig_free", "shared_free"}
    BOOKKEEPING = {"_free_total", "_pod_free", "_used_total",
                   "_healthy_chips", "_healthy_exc", "_tier_free",
                   "_tier_used", "_tier_cap", "_frag", "_node_gen",
                   "_node_jobs", "_node_hkey", "_pod_hkey", "_buckets",
                   "_rbuckets", "_fbuckets", "_rfbuckets", "_fgen",
                   "_frac_alloc", "_health_counts", "_tenant_chips",
                   "abnormal_nodes"}
    CONTAINER_MUTATORS = {"add", "discard", "remove", "clear", "update",
                          "pop", "popitem", "append", "extend", "insert",
                          "setdefault"}

    def _flag_attr(self, attr: str) -> Optional[str]:
        if attr in self.NODE_FIELDS:
            return (f"direct write to Node.{attr} outside cluster.py — "
                    f"route through Cluster._mutate / set_speed / drain / "
                    f"fail_node so counters and buckets stay in sync")
        if attr in self.BOOKKEEPING:
            return (f"direct write to bookkeeping field {attr} outside its "
                    f"owning module — use the public mutation API (the "
                    f"indexed views and counters desync silently)")
        return None

    def check(self, tree: ast.Module, path: str) -> List[Violation]:
        out: List[Violation] = []
        for node in ast.walk(tree):
            targets: List[ast.AST] = []
            if isinstance(node, ast.Assign):
                targets = list(node.targets)
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            for t in targets:
                for el in (t.elts if isinstance(t, (ast.Tuple, ast.List))
                           else [t]):
                    spot = el
                    if isinstance(spot, ast.Subscript):
                        spot = spot.value
                    if isinstance(spot, ast.Attribute):
                        msg = self._flag_attr(spot.attr)
                        if msg:
                            out.append(self.violation(path, node, msg))
            if isinstance(node, ast.Call):
                f = node.func
                if isinstance(f, ast.Name) and f.id == "setattr" \
                        and len(node.args) >= 2 \
                        and isinstance(node.args[1], ast.Constant) \
                        and isinstance(node.args[1].value, str):
                    msg = self._flag_attr(node.args[1].value)
                    if msg:
                        out.append(self.violation(path, node, msg))
                elif isinstance(f, ast.Attribute) \
                        and f.attr in self.CONTAINER_MUTATORS \
                        and isinstance(f.value, ast.Attribute):
                    msg = self._flag_attr(f.value.attr)
                    if msg:
                        out.append(self.violation(path, node, msg))
        return out


# ---------------------------------------------------------------------------
# slow-marker — ROADMAP: "Keep tier-1 well under 120 s; mark heavy tests
# slow" — tests that materialize month/year-scale presets must opt out of
# the default selection
# ---------------------------------------------------------------------------

@register
class SlowMarkerRule(Rule):
    name = "slow-marker"
    description = ("tests that synthesize/install month- or year-scale "
                   "presets must carry @pytest.mark.slow (tier-1 wall "
                   "budget)")
    include = ("tests/",)

    HEAVY_PREFIXES = ("month-", "year-")
    # calls that actually materialize/replay the preset (config-shape
    # checks on a heavy preset are cheap and stay in tier-1)
    MATERIALIZERS = {"synthesize", "synthesize_stream", "install",
                     "install_stream", "feed", "read_tail", "run"}

    def _module_slow(self, tree: ast.Module) -> bool:
        for node in tree.body:
            if isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == "pytestmark"
                    for t in node.targets):
                for sub in ast.walk(node.value):
                    if attr_chain(sub) == "pytest.mark.slow":
                        return True
        return False

    def _fn_slow(self, fn: ast.FunctionDef) -> bool:
        for dec in fn.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            if attr_chain(target) == "pytest.mark.slow":
                return True
        return False

    def check(self, tree: ast.Module, path: str) -> List[Violation]:
        if self._module_slow(tree):
            return []
        out: List[Violation] = []
        fns = [n for n in ast.walk(tree)
               if isinstance(n, ast.FunctionDef)
               and n.name.startswith("test_")]
        for fn in fns:
            if self._fn_slow(fn):
                continue
            heavy_call = None
            materializes = False
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                name = terminal_name(node.func)
                if name == "scale_preset" and node.args \
                        and isinstance(node.args[0], ast.Constant) \
                        and isinstance(node.args[0].value, str) \
                        and node.args[0].value.startswith(
                            self.HEAVY_PREFIXES):
                    heavy_call = node
                elif name in self.MATERIALIZERS:
                    materializes = True
            if heavy_call is not None and materializes:
                out.append(self.violation(
                    path, heavy_call,
                    f"{fn.name} materializes a month/year-scale preset "
                    f"without @pytest.mark.slow — tier-1 must stay under "
                    f"its wall budget"))
        return out


# ---------------------------------------------------------------------------
# block-discipline — ISSUE 10 / ROADMAP direction 5: kernel block sizes are
# owned by the autotune table (kernels/autotune.py); hard-coded literals at
# call sites bypass the tuned dispatch and silently pin yesterday's blocks
# ---------------------------------------------------------------------------

@register
class BlockDisciplineRule(Rule):
    name = "block-discipline"
    description = ("no hard-coded block_q=/block_k=/block_rows= integer "
                   "literals at call sites — block choices route through "
                   "kernels/autotune.py (kernel signature defaults are the "
                   "documented fallbacks and are not call sites)")
    include = ("src/", "benchmarks/", "tests/")
    # the table module owns the defaults; analysis/ embeds fixture code
    exclude = ("src/repro/kernels/autotune.py", "src/repro/analysis/")

    BLOCK_KWARGS = {"block_q", "block_k", "block_rows"}

    def check(self, tree: ast.Module, path: str) -> List[Violation]:
        out: List[Violation] = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            for kw in node.keywords:
                if kw.arg in self.BLOCK_KWARGS \
                        and isinstance(kw.value, ast.Constant) \
                        and isinstance(kw.value.value, int):
                    out.append(self.violation(
                        path, node,
                        f"hard-coded {kw.arg}={kw.value.value} at a call "
                        f"site — route block choices through the autotune "
                        f"table (repro.kernels.autotune) so tuning applies "
                        f"everywhere"))
        return out
