"""The lint rule catalog: each rule codifies one JAX/TPU hazard class this
repo has actually hit (see the module docstring of :mod:`sheeprl_tpu.analysis`
for the incident list). Rules are AST visitors over the package's parsed
sources — **no sheeprl_tpu module is imported** by any rule (the engine must
stay fast and never initialize jax), with one deliberate exception:
``cfg-key-resolves`` composes the repo's own YAML config tree through
``sheeprl_tpu.config`` (pure YAML, no jax).

Each rule yields findings shaped like ``obs/diagnose.py``'s:
``{rule, severity, file, line, summary, suggestion}``.

Adding a rule: subclass :class:`Rule`, set ``name``/``severity``, implement
``run(package)``, append it to :data:`ALL_RULES`, document it in
``howto/static_analysis.md``, and give it a positive + negative fixture test in
``tests/test_analysis/test_rules.py``.
"""

from __future__ import annotations

import ast
import re
from typing import Any, Dict, Iterator, List, Optional, Sequence, Set, Tuple

Finding = Dict[str, Any]

SEVERITIES = ("critical", "warning", "info")


def dotted_name(node: ast.AST) -> Optional[str]:
    """``jax.lax.platform_dependent`` for an Attribute/Name chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _set_parents(tree: ast.AST) -> None:
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            child._lint_parent = node  # type: ignore[attr-defined]


def _enclosing_functions(node: ast.AST) -> List[ast.AST]:
    """Innermost-first chain of enclosing function defs (requires _set_parents)."""
    out: List[ast.AST] = []
    cur = getattr(node, "_lint_parent", None)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.append(cur)
        cur = getattr(cur, "_lint_parent", None)
    return out


def _local_defs(tree: ast.AST) -> Dict[str, List[ast.FunctionDef]]:
    defs: Dict[str, List[ast.FunctionDef]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, []).append(node)
    return defs


def _called_names(tree: ast.AST) -> Set[str]:
    names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
            names.add(node.func.id)
    return names


def _unwrap_partial(node: ast.AST) -> ast.AST:
    """``functools.partial(f, ...)`` / ``partial(f, ...)`` -> ``f``."""
    if isinstance(node, ast.Call):
        fn = dotted_name(node.func)
        if fn in ("partial", "functools.partial") and node.args:
            return node.args[0]
    return node


def _is_jax_jit(node: ast.AST) -> bool:
    return dotted_name(node) in ("jax.jit", "jit")


def _literal_int_tuple(node: ast.AST) -> Optional[Tuple[int, ...]]:
    try:
        value = ast.literal_eval(node)
    except (ValueError, TypeError, SyntaxError):
        return None
    if isinstance(value, int):
        return (value,)
    if isinstance(value, (tuple, list)) and all(isinstance(v, int) for v in value):
        return tuple(value)
    return None


class Rule:
    """Base rule. ``run(package)`` yields findings; ``package`` is the
    :class:`~sheeprl_tpu.analysis.engine.Package` of parsed sources."""

    name: str = ""
    severity: str = "warning"
    doc: str = ""

    def run(self, package) -> Iterator[Finding]:  # pragma: no cover - interface
        raise NotImplementedError

    def finding(
        self,
        module,
        node: Optional[ast.AST],
        summary: str,
        suggestion: str,
        severity: Optional[str] = None,
    ) -> Finding:
        return {
            "rule": self.name,
            "severity": severity or self.severity,
            "file": module.rel,
            "line": int(getattr(node, "lineno", 0) or 0),
            "summary": summary,
            "suggestion": suggestion,
        }


class JaxDevicesRule(Rule):
    """``jax.devices()`` outside ``parallel/fabric.py``.

    ``jax.devices()`` spans ALL processes of a multi-process run: on a
    multi-host pod, index 0 is rank 0's device — a non-rank-0 actor that grabs
    ``jax.devices()[0]`` is addressing ANOTHER process's chip (the PR 10
    serving-actor bug). ``parallel/fabric.py`` owns the only deliberate
    global-view call sites (mesh construction)."""

    name = "jax-devices-global-view"
    severity = "warning"
    doc = "jax.devices() outside parallel/fabric.py (use jax.local_devices())"

    ALLOWED_FILES = ("sheeprl_tpu/parallel/fabric.py",)

    def run(self, package) -> Iterator[Finding]:
        for module in package.modules:
            if module.rel in self.ALLOWED_FILES:
                continue
            for node in ast.walk(module.tree):
                if isinstance(node, ast.Call) and dotted_name(node.func) == "jax.devices":
                    yield self.finding(
                        module,
                        node,
                        "jax.devices() addresses the GLOBAL device list — on a "
                        "multi-process run index 0 may be another process's chip",
                        "use jax.local_devices() (or thread the device through "
                        "parallel/fabric.py, the one module allowed a global view)",
                    )


class PallasDotPrecisionRule(Rule):
    """Pallas kernel ``dot``s must pin an explicit ``precision=``.

    Mosaic only lowers DEFAULT/HIGHEST dot precisions, and the repo's global
    default is "high" (bf16_3x): an unpinned kernel dot inherits it and the
    whole kernel fails to lower for TPU (the PR 10 GRU bug, caught by the AOT
    suite). The rule finds the kernel functions (first argument of each
    ``pallas_call``, ``functools.partial`` unwrapped) and flags dot-family
    calls without a ``precision=`` keyword, plus bare ``@`` matmuls (which
    cannot pin one at all)."""

    name = "pallas-dot-precision"
    severity = "critical"
    doc = "Pallas kernel dot/matmul without an explicit precision="

    _DOT_ATTRS = ("dot", "dot_general", "matmul", "einsum")

    def run(self, package) -> Iterator[Finding]:
        for module in package.modules:
            if "pallas_call" not in module.source:
                continue
            kernels: Set[str] = set()
            for node in ast.walk(module.tree):
                if isinstance(node, ast.Call) and (dotted_name(node.func) or "").endswith("pallas_call"):
                    if node.args:
                        target = _unwrap_partial(node.args[0])
                        name = dotted_name(target)
                        if name:
                            kernels.add(name.split(".")[-1])
            if not kernels:
                continue
            defs = _local_defs(module.tree)
            for kernel_name in sorted(kernels):
                for kernel in defs.get(kernel_name, []):
                    for node in ast.walk(kernel):
                        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.MatMult):
                            yield self.finding(
                                module,
                                node,
                                f"bare `@` matmul in Pallas kernel {kernel_name!r} "
                                "cannot pin a dot precision",
                                "use jnp.dot(..., precision=jax.lax.Precision.DEFAULT) "
                                "so the kernel never inherits the global bf16_3x default "
                                "Mosaic refuses to lower",
                            )
                            continue
                        if not isinstance(node, ast.Call):
                            continue
                        fn = dotted_name(node.func) or ""
                        if fn.split(".")[-1] not in self._DOT_ATTRS:
                            continue
                        if not any(kw.arg == "precision" for kw in node.keywords):
                            yield self.finding(
                                module,
                                node,
                                f"{fn}(...) in Pallas kernel {kernel_name!r} has no "
                                "explicit precision= and inherits the global matmul "
                                "precision (bf16_3x), which Mosaic cannot lower",
                                "pin precision=jax.lax.Precision.DEFAULT (MXU-native) "
                                "or HIGHEST inside the kernel",
                            )


def _donated_programs(tree: ast.AST) -> Dict[str, Tuple[int, ...]]:
    """name -> donated argnums, for both spellings used in the repo:
    ``@partial(jax.jit, donate_argnums=...)`` on a def, and
    ``name = jax.jit(fn, donate_argnums=...)`` / ``self._x = jax.jit(...)``."""
    donated: Dict[str, Tuple[int, ...]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for deco in node.decorator_list:
                if not isinstance(deco, ast.Call):
                    continue
                fn = dotted_name(deco.func)
                is_partial_jit = fn in ("partial", "functools.partial") and deco.args and _is_jax_jit(deco.args[0])
                if not (is_partial_jit or _is_jax_jit(deco.func)):
                    continue
                for kw in deco.keywords:
                    if kw.arg == "donate_argnums":
                        nums = _literal_int_tuple(kw.value)
                        if nums:
                            donated[node.name] = nums
        elif isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            call = node.value
            if not _is_jax_jit(call.func):
                continue
            nums: Optional[Tuple[int, ...]] = None
            for kw in call.keywords:
                if kw.arg == "donate_argnums":
                    nums = _literal_int_tuple(kw.value)
            if not nums:
                continue
            for target in node.targets:
                if isinstance(target, ast.Name):
                    donated[target.id] = nums
                elif isinstance(target, ast.Attribute):
                    donated[target.attr] = nums
    return donated


class AsarrayDonationRule(Rule):
    """``np.asarray`` feeding a donated argument.

    On the CPU backend ``np.asarray`` of a device array hands out a zero-copy
    HOST VIEW that pins the underlying buffer — XLA then silently refuses the
    donation and the train state is copied every step (the PR 1 regression the
    donation tests pin). The rule resolves each module's donated programs
    (``donate_argnums`` spellings) and flags call sites whose DONATED argument
    positions receive ``np.asarray``/``np.array`` results, directly or through
    a local variable."""

    name = "asarray-into-donated"
    severity = "warning"
    doc = "np.asarray host view passed at a donated argument position"

    _NP_CONV = ("np.asarray", "np.array", "numpy.asarray", "numpy.array")

    def _is_np_conversion(self, node: ast.AST) -> bool:
        return isinstance(node, ast.Call) and dotted_name(node.func) in self._NP_CONV

    def run(self, package) -> Iterator[Finding]:
        for module in package.modules:
            if "donate_argnums" not in module.source:
                continue
            donated = _donated_programs(module.tree)
            if not donated:
                continue
            _set_parents(module.tree)
            for node in ast.walk(module.tree):
                if not isinstance(node, ast.Call):
                    continue
                callee: Optional[str] = None
                if isinstance(node.func, ast.Name) and node.func.id in donated:
                    callee = node.func.id
                elif isinstance(node.func, ast.Attribute) and node.func.attr in donated:
                    callee = node.func.attr
                if callee is None:
                    continue
                # variables assigned from np conversions in the enclosing function
                host_views: Set[str] = set()
                scopes = _enclosing_functions(node)
                if scopes:
                    for n in ast.walk(scopes[0]):
                        if isinstance(n, ast.Assign) and self._is_np_conversion(n.value):
                            for target in n.targets:
                                if isinstance(target, ast.Name):
                                    host_views.add(target.id)
                for pos in donated[callee]:
                    if pos >= len(node.args):
                        continue
                    arg = node.args[pos]
                    bad = self._is_np_conversion(arg) or (
                        isinstance(arg, ast.Name) and arg.id in host_views
                    )
                    if bad:
                        yield self.finding(
                            module,
                            node,
                            f"donated argument {pos} of {callee!r} is an "
                            "np.asarray/np.array host view — the pinned buffer "
                            "silently disables donation",
                            "snapshot with jnp.array (a device copy) before feeding "
                            "a donated program; see tests/test_algos/test_donation.py",
                        )


class HostSyncInJitRule(Rule):
    """Host-sync calls inside functions reachable from a jitted program.

    ``.item()``, ``np.array``/``np.asarray``, ``time.time`` and ``print`` on a
    traced value either fail at trace time or (worse) silently bake a
    trace-time constant into the compiled program; inside a jitted fused loop
    they are always a bug. The rule collects each module's jit roots (both
    decorator spellings and ``jax.jit(fn)`` wrapping of a local def), walks the
    intra-module call graph, and flags host-sync calls in the reachable set."""

    name = "host-sync-in-jit"
    severity = "warning"
    doc = "host-sync call (.item()/np.array/time.time/print) reachable from a jitted program"

    _TIME_CALLS = ("time.time", "time.perf_counter", "time.monotonic")
    _NP_CONV = ("np.asarray", "np.array", "numpy.asarray", "numpy.array")

    def _jit_roots(self, module) -> List[ast.FunctionDef]:
        roots: List[ast.FunctionDef] = []
        defs = _local_defs(module.tree)
        for name_defs in defs.values():
            for node in name_defs:
                for deco in node.decorator_list:
                    if _is_jax_jit(deco):
                        roots.append(node)
                    elif isinstance(deco, ast.Call):
                        fn = dotted_name(deco.func)
                        if _is_jax_jit(deco.func):
                            roots.append(node)
                        elif fn in ("partial", "functools.partial") and deco.args and _is_jax_jit(deco.args[0]):
                            roots.append(node)
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call) and _is_jax_jit(node.func) and node.args:
                target = _unwrap_partial(node.args[0])
                # only bare local names: `jax.jit(self._env.reset)` wraps ANOTHER
                # object's method, not the local def that happens to share the name
                if isinstance(target, ast.Name):
                    for d in defs.get(target.id, []):
                        roots.append(d)
        return roots

    def run(self, package) -> Iterator[Finding]:
        for module in package.modules:
            if "jit" not in module.source:
                continue
            roots = self._jit_roots(module)
            if not roots:
                continue
            defs = _local_defs(module.tree)
            reachable: List[ast.FunctionDef] = []
            seen: Set[int] = set()
            frontier = list(roots)
            while frontier:
                fn = frontier.pop()
                if id(fn) in seen:
                    continue
                seen.add(id(fn))
                reachable.append(fn)
                for called in _called_names(fn):
                    for d in defs.get(called, []):
                        if id(d) not in seen:
                            frontier.append(d)
            flagged: Set[int] = set()
            for fn in reachable:
                for node in ast.walk(fn):
                    if not isinstance(node, ast.Call) or id(node) in flagged:
                        continue
                    name = dotted_name(node.func) or ""
                    what = None
                    if isinstance(node.func, ast.Attribute) and node.func.attr == "item" and not node.args:
                        what = ".item() device sync"
                    elif name in self._TIME_CALLS:
                        what = f"{name}() wall-clock read (a trace-time constant inside jit)"
                    elif name == "print":
                        what = "print() host callback"
                    elif name in self._NP_CONV:
                        what = f"{name}() host transfer"
                    elif isinstance(node.func, ast.Attribute) and node.func.attr == "block_until_ready":
                        what = "block_until_ready() device sync"
                    if what is not None:
                        flagged.add(id(node))
                        yield self.finding(
                            module,
                            node,
                            f"{what} inside {fn.name!r}, which is reachable from a "
                            "jitted program",
                            "keep host syncs outside the jitted program (or use "
                            "jax.debug.print / jnp equivalents); waive with a reason "
                            "if this path provably runs at trace time only",
                        )


class TelemetryEventSchemaRule(Rule):
    """Every emitted telemetry event type must be registered in ``obs/schema.py``.

    The stream's consumers parse with defaults, so an unregistered event type
    would not crash anything — it would silently fall out of every detector
    (the PR 11 drift class). This is the same census the PR 11 grep test ran,
    as an AST rule: ``emit``/``emit_event``/``_emit`` call sites with a literal
    event name are checked against the schema's declared event tables."""

    name = "telemetry-event-unregistered"
    severity = "critical"
    doc = "emit site whose event name is absent from obs/schema.py"

    _EMITTERS = ("emit", "emit_event", "_emit")

    def __init__(self, registered_names: Optional[Set[str]] = None) -> None:
        self._registered_override = registered_names

    def registered_names(self, package) -> Optional[Set[str]]:
        if self._registered_override is not None:
            return set(self._registered_override)
        schema = package.module("sheeprl_tpu/obs/schema.py")
        if schema is None:
            return None
        names: Set[str] = set()
        for node in ast.walk(schema.tree):
            # both spellings: `_X = {...}` and the annotated `_X: Dict[...] = {...}`
            if isinstance(node, ast.Assign):
                targets = {t.id for t in node.targets if isinstance(t, ast.Name)}
                value = node.value
            elif isinstance(node, ast.AnnAssign) and isinstance(node.target, ast.Name):
                targets = {node.target.id}
                value = node.value
            else:
                continue
            if not targets & {"_STRICT_EVENTS", "_OPEN_EVENTS"}:
                continue
            if isinstance(value, ast.Dict):
                for key in value.keys:
                    if isinstance(key, ast.Constant) and isinstance(key.value, str):
                        names.add(key.value)
        return names or None

    def emitted_events(self, package) -> List[Tuple[Any, ast.Call, str]]:
        """All (module, call, event_name) literal emit sites in the package —
        shared with the schema census test so the two checkers cannot drift."""
        sites: List[Tuple[Any, ast.Call, str]] = []
        for module in package.modules:
            for node in ast.walk(module.tree):
                if not isinstance(node, ast.Call):
                    continue
                fn = (dotted_name(node.func) or "").split(".")[-1]
                if fn not in self._EMITTERS or not node.args:
                    continue
                first = node.args[0]
                if isinstance(first, ast.Constant) and isinstance(first.value, str):
                    sites.append((module, node, first.value))
        return sites

    def run(self, package) -> Iterator[Finding]:
        registered = self.registered_names(package)
        if registered is None:
            return  # no schema in this tree (fixture packages) and no override
        for module, node, event in self.emitted_events(package):
            if module.rel == "sheeprl_tpu/obs/schema.py":
                continue
            if event not in registered:
                yield self.finding(
                    module,
                    node,
                    f"telemetry event {event!r} is emitted but not registered in "
                    "obs/schema.py — consumers would silently ignore it",
                    "declare the event's field table in obs/schema.py (and bump "
                    "SCHEMA_VERSION if the change is breaking)",
                )


class LoopHooksRule(Rule):
    """Every registered algorithm entrypoint must thread the telemetry and
    resilience hook sets.

    PR 2/3 threaded 4 telemetry hooks (build, observe_train, step, close) and
    4 resilience hooks (build, step, preempt poll, finalize) through all
    training loops, and the learning-health plane added ``observe_learn`` (the
    fused program's ``Learn/*`` stats threading) as a fifth telemetry hook; a
    NEW algo registered without them trains blind (no phases/MFU/diagnosis, no
    learning-health detectors) and cannot be preempted safely. The rule finds
    every ``@register_algorithm``-decorated def, follows its intra-package call
    graph (local defs + ``from sheeprl_tpu... import`` helpers, so delegation
    through ``run_dreamer``/``run_anakin`` counts), and requires each hook to
    appear somewhere in the reachable set. A loop where a hook is structurally
    N/A (e.g. a driver with no train rounds of its own) waives it per file in
    ``analysis/waivers.toml`` with a reason, like any other rule."""

    name = "loop-hooks-incomplete"
    severity = "critical"
    doc = "registered algo entrypoint missing telemetry/resilience hooks"

    TELEMETRY_HOOKS = (
        "build_telemetry",
        "observe_train",
        "observe_learn",
        "telemetry.step",
        "telemetry.close",
    )
    RESILIENCE_HOOKS = (
        "build_resilience",
        "resilience.step",
        "preempt_requested",
        "resilience.finalize",
    )
    _MAX_DEPTH = 6

    def _entrypoints(self, package) -> List[Tuple[Any, ast.FunctionDef]]:
        out = []
        for module in package.modules:
            if "register_algorithm" not in module.source:
                continue
            for node in ast.walk(module.tree):
                if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                for deco in node.decorator_list:
                    target = deco.func if isinstance(deco, ast.Call) else deco
                    if (dotted_name(target) or "").split(".")[-1] == "register_algorithm":
                        out.append((module, node))
        return out

    def _imports(self, module) -> Dict[str, Tuple[str, str]]:
        """local name -> (source module rel path, original name) for
        ``from sheeprl_tpu.x.y import z [as w]`` imports."""
        imports: Dict[str, Tuple[str, str]] = {}
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.ImportFrom) or not node.module:
                continue
            rel = node.module.replace(".", "/") + ".py"
            for alias in node.names:
                imports[alias.asname or alias.name] = (rel, alias.name)
        return imports

    def _module_aliases(self, package, module) -> Dict[str, str]:
        """local alias -> module rel path, for module-object imports
        (``from sheeprl_tpu.algos.dreamer_v1 import dreamer_v1 as dv1``,
        ``import sheeprl_tpu.x.y as z``) — so delegation spelled as an
        attribute call (``dv1.main(...)``) is followed too."""
        aliases: Dict[str, str] = {}
        for node in ast.walk(module.tree):
            if isinstance(node, ast.ImportFrom) and node.module:
                for alias in node.names:
                    rel = f"{node.module.replace('.', '/')}/{alias.name}.py"
                    if package.module(rel) is not None:
                        aliases[alias.asname or alias.name] = rel
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    rel = alias.name.replace(".", "/") + ".py"
                    if package.module(rel) is not None:
                        aliases[alias.asname or alias.name.split(".")[0]] = rel
        return aliases

    def _module_tables(self, package, mod):
        """Per-module (defs, imports, aliases), cached — the tables are pure
        functions of the parsed tree, and recomputing them per visited function
        made the traversal quadratic (~7 s on this tree; cached it is linear)."""
        cached = self._tables_cache.get(mod.rel)
        if cached is None:
            cached = (
                _local_defs(mod.tree),
                self._imports(mod),
                self._module_aliases(package, mod),
            )
            self._tables_cache[mod.rel] = cached
        return cached

    def _reachable(self, package, module, entry: ast.FunctionDef) -> List[ast.AST]:
        reachable: List[ast.AST] = []
        seen: Set[Tuple[str, str]] = set()
        frontier: List[Tuple[Any, ast.AST, int]] = [(module, entry, 0)]
        while frontier:
            mod, fn, depth = frontier.pop()
            key = (mod.rel, getattr(fn, "name", "<module>"))
            if key in seen:
                continue
            seen.add(key)
            reachable.append(fn)
            if depth >= self._MAX_DEPTH:
                continue
            defs, imports, aliases = self._module_tables(package, mod)
            for called in _called_names(fn):
                for d in defs.get(called, []):
                    frontier.append((mod, d, depth + 1))
                if called in imports:
                    rel, original = imports[called]
                    target_mod = package.module(rel)
                    if target_mod is not None:
                        target_defs = self._module_tables(package, target_mod)[0]
                        for d in target_defs.get(original, []):
                            frontier.append((target_mod, d, depth + 1))
            # attribute calls through module aliases: dv1.main(...)
            for node in ast.walk(fn):
                if (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in aliases
                ):
                    target_mod = package.module(aliases[node.func.value.id])
                    if target_mod is not None:
                        target_defs = self._module_tables(package, target_mod)[0]
                        for d in target_defs.get(node.func.attr, []):
                            frontier.append((target_mod, d, depth + 1))
        return reachable

    def _hooks_present(self, reachable: Sequence[ast.AST]) -> Set[str]:
        present: Set[str] = set()
        for fn in reachable:
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                if isinstance(node.func, ast.Name):
                    if node.func.id in ("build_telemetry", "build_resilience"):
                        present.add(node.func.id)
                elif isinstance(node.func, ast.Attribute):
                    attr = node.func.attr
                    owner = dotted_name(node.func.value) or ""
                    owner_leaf = owner.split(".")[-1]
                    if attr in ("observe_train", "observe_learn", "preempt_requested"):
                        present.add(attr)
                    if attr in ("step", "close", "finalize") and (
                        "telemetry" in owner_leaf or "resilience" in owner_leaf
                    ):
                        kind = "telemetry" if "telemetry" in owner_leaf else "resilience"
                        present.add(f"{kind}.{attr}")
        return present

    def run(self, package) -> Iterator[Finding]:
        self._tables_cache: Dict[str, Tuple[Any, Any, Any]] = {}
        for module, entry in self._entrypoints(package):
            reachable = self._reachable(package, module, entry)
            present = self._hooks_present(reachable)
            missing_telemetry = [h for h in self.TELEMETRY_HOOKS if h not in present]
            missing_resilience = [h for h in self.RESILIENCE_HOOKS if h not in present]
            missing = missing_telemetry + missing_resilience
            if missing:
                yield self.finding(
                    module,
                    entry,
                    f"registered entrypoint {entry.name!r} does not thread "
                    f"{len(missing)} required loop hook(s): {', '.join(missing)}",
                    "thread the telemetry hooks (build_telemetry / observe_train / "
                    "observe_learn / telemetry.step / telemetry.close) and resilience "
                    "hooks (build_resilience / resilience.step / preempt_requested / "
                    "resilience.finalize) — see any existing loop, e.g. sac.py; waive "
                    "per file in analysis/waivers.toml where a hook is structurally N/A",
                )


class CfgKeyResolvesRule(Rule):
    """``cfg.<group>.<key>`` attribute chains must resolve against the composed
    YAML config tree.

    The config layer is plain ``dotdict``s: a typo'd or removed key raises
    ``AttributeError`` only when that exact line runs — on a 25-minute TPU
    workload, possibly an hour in. The rule composes every experiment through
    the repo's own composer, unions the resulting trees (a key present in ANY
    exp is valid — algo groups legitimately differ), collects every attribute
    STORE on a ``cfg`` chain package-wide (keys the code itself creates), and
    flags Load chains that resolve against neither."""

    name = "cfg-key-unresolved"
    severity = "warning"
    doc = "cfg.<group>.<key> access that resolves in no composed config"

    # dict/dotdict methods that terminate a chain without naming a config key
    _METHODS = {
        "get", "keys", "items", "values", "pop", "setdefault", "update", "copy",
        "as_dict", "clear",
    }

    def __init__(self, union_tree: Optional[Dict[str, Any]] = None) -> None:
        self._union_override = union_tree

    def _compose_union(self, package) -> Optional[Dict[str, Any]]:
        if self._union_override is not None:
            return self._union_override
        configs_dir = package.root / "sheeprl_tpu" / "configs"
        if not configs_dir.is_dir():
            return None
        try:
            from sheeprl_tpu.config.composer import Composer
        except Exception:
            return None
        composer = Composer()
        union: Dict[str, Any] = {}

        def merge(dst: Dict[str, Any], src: Dict[str, Any]) -> None:
            for k, v in src.items():
                if isinstance(v, dict):
                    node = dst.setdefault(k, {})
                    if isinstance(node, dict):
                        merge(node, v)
                else:
                    dst.setdefault(k, v if v is not None else True)

        composed_any = False
        for exp in composer.available("exp"):
            overrides = [f"exp={exp}", "run_name=lint", "env.id=lint"]
            cfg = None
            # mandatory `???` values (the finetuning exps' exploration_ckpt_path)
            # abort composition; fill each one reported and retry so those exps
            # still contribute their key tree to the union
            for _attempt in range(6):
                try:
                    cfg = composer.compose(overrides)
                    break
                except Exception as exc:
                    msg = str(exc)
                    m = re.search(r"mandatory config value ([\w.]+) is not set", msg)
                    if m is None:
                        break
                    overrides = overrides + [f"{m.group(1)}=lint"]
            if cfg is None:
                continue
            composed_any = True
            merge(union, dict(cfg))
        return union if composed_any else None

    def _stored_paths(self, package) -> Set[str]:
        stored: Set[str] = set()
        for module in package.modules:
            for node in ast.walk(module.tree):
                target: Optional[ast.AST] = None
                if isinstance(node, ast.Assign):
                    for t in node.targets:
                        target = t
                        path = self._chain(target, require_ctx=None)
                        if path:
                            stored.add(path)
                elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                    path = self._chain(node.target, require_ctx=None)
                    if path:
                        stored.add(path)
        return stored

    def _chain(self, node: ast.AST, require_ctx=ast.Load) -> Optional[str]:
        parts: List[str] = []
        cur = node
        while isinstance(cur, ast.Attribute):
            parts.append(cur.attr)
            cur = cur.value
        if not (isinstance(cur, ast.Name) and cur.id == "cfg" and parts):
            return None
        parts = list(reversed(parts))
        # chains ending in a dict method name a PARENT key only
        while parts and parts[-1] in self._METHODS:
            parts.pop()
        if not parts:
            return None
        return ".".join(parts)

    def run(self, package) -> Iterator[Finding]:
        union = self._compose_union(package)
        if union is None:
            return
        stored = self._stored_paths(package)
        for module in package.modules:
            if "cfg." not in module.source:
                continue
            _set_parents(module.tree)
            reported: Set[Tuple[int, str]] = set()
            for node in ast.walk(module.tree):
                if not isinstance(node, ast.Attribute) or not isinstance(node.ctx, ast.Load):
                    continue
                parent = getattr(node, "_lint_parent", None)
                if isinstance(parent, ast.Attribute):
                    continue  # only the maximal chain
                path = self._chain(node)
                if path is None:
                    continue
                segments = path.split(".")
                cursor: Any = union
                resolved: List[str] = []
                for seg in segments:
                    if not isinstance(cursor, dict):
                        break  # below a leaf value: out of the YAML tree's scope
                    if seg in cursor:
                        cursor = cursor[seg]
                        resolved.append(seg)
                        continue
                    if not resolved:
                        # unknown top-level attr (cfg.checkpoint_path, cfg.serve):
                        # runtime-built roots the eval/serve tiers assemble in
                        # code — the rule's claim is about <group>.<key> drift,
                        # which needs a group the YAML tree actually knows
                        break
                    missing_path = ".".join(resolved + [seg])
                    if any(
                        s == missing_path or s.startswith(missing_path + ".")
                        for s in stored
                    ):
                        break  # the code itself creates this key somewhere
                    key = (node.lineno, missing_path)
                    if key not in reported:
                        reported.add(key)
                        yield self.finding(
                            module,
                            node,
                            f"cfg.{missing_path} resolves in none of the composed "
                            "configs and is never assigned in code — config/code "
                            "drift",
                            "fix the key, add it to the config group's YAML, or "
                            "waive with a reason if it is created dynamically",
                        )
                    break


def default_rules() -> List[Rule]:
    return [
        JaxDevicesRule(),
        PallasDotPrecisionRule(),
        AsarrayDonationRule(),
        HostSyncInJitRule(),
        TelemetryEventSchemaRule(),
        LoopHooksRule(),
        CfgKeyResolvesRule(),
    ]


ALL_RULES = default_rules
