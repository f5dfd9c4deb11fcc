"""The project rule catalog (R1..R8).

Every rule is distilled from a real incident in this repo's history;
docs/static_analysis.md maps each id to the PR that motivated it and
shows the suppression syntax.  Matchers are deliberately narrow: a lint
that cries wolf gets disabled, so each rule targets the exact shape of
the bug class it retires and leaves neighboring idioms alone (the same
philosophy as the reference's per-op tagging: precise reasons, no
blanket bans).
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from .engine import (
    Finding, ProjectRule, Rule, Severity, SourceFile, dotted_name, str_const,
    walk_no_nested_functions,
)


def _functions(tree: ast.AST) -> Iterator[ast.AST]:
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


class ImportTimeJnpRule(Rule):
    """R1: no jnp/jax.numpy value construction at module import time.

    Module-level device values are created before tests/conftest pin the
    platform, can capture a tracer when the module first loads under a
    jit trace, and silently pin HBM for the process lifetime (the PR-2
    tracer-leak class).  Build device constants inside the function (XLA
    constant-folds them) or lazily.
    """

    id = "R1"
    name = "import-time-jnp"
    description = ("no jnp.*/jax.numpy value construction at module "
                   "import time")

    def check(self, sf: SourceFile) -> Iterator[Finding]:
        # walk module scope, descending into classes/ifs/trys but never
        # into function or lambda bodies
        stack: List[ast.AST] = list(sf.tree.body)
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                continue
            if isinstance(node, ast.Call):
                name = dotted_name(node.func) or ""
                if name.startswith("jnp.") or name.startswith("jax.numpy."):
                    yield self.finding(
                        sf, node,
                        f"`{name}(...)` at module import time builds a "
                        "device value before the platform/test harness is "
                        "configured (tracer-leak class); construct it "
                        "inside the consuming function")
            stack.extend(ast.iter_child_nodes(node))


_SEM_SEG = re.compile(r"sem", re.IGNORECASE)


def _is_sem_call(node: ast.Call, method: Tuple[str, ...]) -> bool:
    name = dotted_name(node.func)
    if name is None:
        return False
    parts = name.split(".")
    if parts[-1] not in method:
        return False
    return any(_SEM_SEG.search(seg) for seg in parts[:-1])


class SemaphoreReleaseRule(Rule):
    """R2: a function that acquires a semaphore must release it in a
    ``finally`` of the same function.

    Coarse, per-function: one sem-release inside any ``finally`` clears
    every sem-acquire in that function.  Deliberate cross-function
    pairings (the engine's H2D-acquire / D2H-release protocol) are
    baseline entries with the pairing spelled out — the rule exists so a
    NEW unpaired acquire can't land silently (the PR-3/4 leak class).
    """

    id = "R2"
    name = "semaphore-release-finally"
    description = ("semaphore.acquire without a release in a finally "
                   "reachable from the same function")

    def check(self, sf: SourceFile) -> Iterator[Finding]:
        for fn in _functions(sf.tree):
            acquires = [
                n for n in walk_no_nested_functions(fn)
                if isinstance(n, ast.Call)
                and _is_sem_call(n, ("acquire",))]
            if not acquires:
                continue
            releases_in_finally = False
            for n in walk_no_nested_functions(fn):
                if isinstance(n, ast.Try) and n.finalbody:
                    for fin_stmt in n.finalbody:
                        for m in ast.walk(fin_stmt):
                            if isinstance(m, ast.Call) and _is_sem_call(
                                    m, ("release", "release_all")):
                                releases_in_finally = True
            if releases_in_finally:
                continue
            for acq in acquires:
                yield self.finding(
                    sf, acq,
                    "semaphore acquired with no release in a finally of "
                    "this function — an error between acquire and release "
                    "leaks the permit and wedges device admission")


class UnboundedWaitRule(Rule):
    """R3: no unbounded blocking primitive in non-test code.

    The PR-4 watchdog delivers ``PartitionTimeout`` via
    ``PyThreadState_SetAsyncExc``, which only lands when the target
    thread re-enters the interpreter — a thread parked in an unbounded
    C-level wait never does.  Every wait must carry a timeout (slice
    loops re-check in bounded steps).
    """

    id = "R3"
    name = "unbounded-wait"
    description = ("Condition/Event.wait(), thread.join() or queue.get() "
                   "without a timeout defeats the partition watchdog")

    _QUEUE_RE = re.compile(r"(queue$|^q$|_q$)", re.IGNORECASE)

    def check(self, sf: SourceFile) -> Iterator[Finding]:
        for node in ast.walk(sf.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)):
                continue
            attr = node.func.attr
            has_args = bool(node.args) or bool(node.keywords)
            if attr in ("wait", "join") and not has_args:
                yield self.finding(
                    sf, node,
                    f"unbounded .{attr}() blocks in C and cannot receive "
                    "the watchdog's async PartitionTimeout; pass a timeout "
                    "(loop over bounded slices if needed)")
            elif attr == "get" and not has_args:
                recv = dotted_name(node.func.value) or ""
                last = recv.split(".")[-1]
                if self._QUEUE_RE.search(last):
                    yield self.finding(
                        sf, node,
                        "queue .get() without timeout parks the thread "
                        "beyond the watchdog's reach; use "
                        "get(timeout=...) in a bounded loop")


class SwallowBaseExceptionRule(Rule):
    """R4: no handler that can swallow KeyboardInterrupt/SystemExit.

    The fault classification (fault/errors.py) promises KI/SE are never
    retried or absorbed by recovery; a ``except:`` or ``except
    BaseException:`` that neither re-raises nor exits the process breaks
    that promise.  (Plain ``except Exception`` cannot catch KI/SE and is
    not flagged.)
    """

    id = "R4"
    name = "swallow-base-exception"
    description = ("bare except / except BaseException that can absorb "
                   "KeyboardInterrupt/SystemExit")

    _BROAD = ("BaseException", "KeyboardInterrupt", "SystemExit")

    def _is_broad(self, type_node: Optional[ast.AST]) -> bool:
        if type_node is None:
            return True  # bare except:
        if isinstance(type_node, ast.Tuple):
            return any(self._is_broad(e) for e in type_node.elts)
        name = dotted_name(type_node) or ""
        return name.split(".")[-1] in self._BROAD

    def check(self, sf: SourceFile) -> Iterator[Finding]:
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if not self._is_broad(node.type):
                continue
            propagates = False
            for m in walk_no_nested_functions(node):
                if isinstance(m, ast.Raise):
                    if m.exc is None:
                        propagates = True  # bare re-raise
                    elif node.name and isinstance(m.exc, ast.Name) \
                            and m.exc.id == node.name:
                        propagates = True  # raise e (same object)
                elif isinstance(m, ast.Call):
                    cname = dotted_name(m.func) or ""
                    if cname in ("os._exit", "sys.exit"):
                        propagates = True
            if not propagates:
                what = "bare except" if node.type is None else \
                    f"except {ast.unparse(node.type)}"
                yield self.finding(
                    sf, node,
                    f"{what} absorbs KeyboardInterrupt/SystemExit (no "
                    "bare re-raise / raise of the caught object / "
                    "process exit on any path); narrow to Exception or "
                    "re-raise non-Exception classes")


class DonationHygieneRule(Rule):
    """R5: donation and compilation go through ``instrumented_jit``.

    ``donate_argnums`` on a raw ``jax.jit`` bypasses the registry's
    donation audit (donatedBytes accounting, cache-bypass for donating
    programs, ``donation_supported()`` platform gate) — a donated buffer
    later re-read by a cached/spill-catalog path is silent corruption.
    Raw ``jax.jit`` anywhere also under-counts compileCount/
    dispatchCount, so the compile-economics metrics lie.
    """

    id = "R5"
    name = "donation-hygiene"
    description = ("donate_argnums outside instrumented_jit, or raw "
                   "jax.jit bypassing the compile registry")

    ALLOWED_FILE = "spark_rapids_tpu/utils/compile_registry.py"

    def check(self, sf: SourceFile) -> Iterator[Finding]:
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func) or ""
            donating = [k for k in node.keywords
                        if k.arg in ("donate_argnums", "donate_argnames")]
            if donating and not name.endswith(("instrumented_jit",
                                               "plan_jit")):
                yield self.finding(
                    sf, node,
                    f"`{name}(..., {donating[0].arg}=...)` donates outside "
                    "instrumented_jit: no donatedBytes accounting, no "
                    "donation_supported() gate, and the compile cache may "
                    "serve a donating executable to a non-donating call "
                    "site")
            elif name == "jax.jit" and sf.path != self.ALLOWED_FILE:
                yield self.finding(
                    sf, node,
                    "raw jax.jit bypasses the compile registry "
                    "(compileCount/dispatchCount metrics, shape-bucket "
                    "policy, persistent-cache wiring); use "
                    "utils.compile_registry.instrumented_jit")


class SyncUnderRuntimeLockRule(Rule):
    """R6: no blocking device sync while holding ``DeviceRuntime._lock``.

    Every thread in the process serializes on that lock via
    ``DeviceRuntime.get()/generation()``; a device sync inside it against
    a sick device turns one wedged transfer into a whole-process hang —
    the exact failure device-lost recovery exists to prevent (recover()
    deliberately rescues the catalog OUTSIDE the lock).
    """

    id = "R6"
    name = "sync-under-runtime-lock"
    description = ("blocking device sync (device_get/block_until_ready/"
                   "device_to_host) while holding DeviceRuntime._lock")

    _SYNC_ATTRS = ("block_until_ready", "device_get")
    _SYNC_NAMES = ("device_to_host",)

    def check(self, sf: SourceFile) -> Iterator[Finding]:
        # map each With node to whether its context is DeviceRuntime._lock
        runtime_classes = {
            node for node in ast.walk(sf.tree)
            if isinstance(node, ast.ClassDef) and node.name == "DeviceRuntime"
        }
        in_runtime: Set[int] = set()
        for cls in runtime_classes:
            for n in ast.walk(cls):
                in_runtime.add(id(n))
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.With):
                continue
            holds = False
            for item in node.items:
                name = dotted_name(item.context_expr) or ""
                if name == "DeviceRuntime._lock":
                    holds = True
                elif name in ("cls._lock", "self._lock") \
                        and id(node) in in_runtime:
                    holds = True
            if not holds:
                continue
            for m in walk_no_nested_functions(node):
                if not isinstance(m, ast.Call):
                    continue
                cname = dotted_name(m.func) or ""
                last = cname.split(".")[-1]
                if last in self._SYNC_ATTRS or cname in self._SYNC_NAMES:
                    yield self.finding(
                        sf, m,
                        f"`{cname}` blocks on the device while holding "
                        "DeviceRuntime._lock — a sick device wedges every "
                        "thread in get()/generation(); move the sync "
                        "outside the lock (see DeviceRuntime.recover)")


_CONF_REGISTER_FNS = ("conf_bool", "conf_int", "conf_float", "conf_str",
                      "conf_bytes")
# a conf KEY, not prose that merely mentions one: dotted identifier
# segments only, optionally ending at a dangling "." (prefix literal)
_CONF_KEY_RE = re.compile(r"^spark\.(rapids|sql)\.[A-Za-z0-9_.]*$")


class ConfRegistrySyncRule(ProjectRule):
    """R7: every ``spark.rapids.*``/``spark.sql.*`` literal resolves to a
    registered ConfEntry, and every registered entry is referenced.

    Registration sites are calls to the ``conf_*`` constructors; dynamic
    per-op keys are recognized by their f-string prefixes
    (``f"spark.rapids.sql.exec.{name}"`` et al).  A registered entry
    counts as referenced when its holder variable is loaded anywhere or
    its key literal appears outside the registration call (docstrings
    never count).  Dead confs are docs that lie; unregistered literals
    are knobs that silently no-op.
    """

    id = "R7"
    name = "conf-registry-sync"
    description = ("spark.rapids.* literals out of sync with the "
                   "config.py registry (unregistered use / dead conf)")

    def check_project(self, files: Sequence[SourceFile],
                      repo_root: str) -> Iterator[Finding]:
        registered: Dict[str, Tuple[str, int]] = {}  # key -> (path, line)
        reg_vars: Dict[str, str] = {}  # key -> holder variable name
        reg_literal_nodes: Set[int] = set()
        dynamic_prefixes: Set[str] = set()
        docstrings: Set[int] = set()
        name_loads: Dict[str, int] = {}

        for sf in files:
            for scope in ast.walk(sf.tree):
                if isinstance(scope, (ast.Module, ast.ClassDef,
                                      ast.FunctionDef,
                                      ast.AsyncFunctionDef)) \
                        and scope.body \
                        and isinstance(scope.body[0], ast.Expr) \
                        and str_const(scope.body[0].value) is not None:
                    docstrings.add(id(scope.body[0].value))
            for node in ast.walk(sf.tree):
                if isinstance(node, ast.Call):
                    fname = (dotted_name(node.func) or "").split(".")[-1]
                    if fname in _CONF_REGISTER_FNS and node.args:
                        key = str_const(node.args[0])
                        if key is not None:
                            registered[key] = (sf.path, node.lineno)
                            reg_literal_nodes.add(id(node.args[0]))
                elif isinstance(node, ast.Assign):
                    if isinstance(node.value, ast.Call):
                        fname = (dotted_name(node.value.func) or ""
                                 ).split(".")[-1]
                        if fname in _CONF_REGISTER_FNS and node.value.args:
                            key = str_const(node.value.args[0])
                            if key is not None and node.targets and \
                                    isinstance(node.targets[0], ast.Name):
                                reg_vars[key] = node.targets[0].id
                elif isinstance(node, ast.Name) and \
                        isinstance(node.ctx, ast.Load):
                    name_loads[node.id] = name_loads.get(node.id, 0) + 1
                elif isinstance(node, ast.JoinedStr) and node.values:
                    head = str_const(node.values[0])
                    if head and _CONF_KEY_RE.match(head):
                        dynamic_prefixes.add(head)

        # pass 2: literal usages outside registrations/docstrings
        literal_uses: Dict[str, List[Tuple[str, int]]] = {}
        for sf in files:
            for node in ast.walk(sf.tree):
                s = str_const(node)
                if s is None or not _CONF_KEY_RE.match(s):
                    continue
                if id(node) in reg_literal_nodes or id(node) in docstrings:
                    continue
                literal_uses.setdefault(s, []).append(
                    (sf.path, node.lineno))

        for key, sites in sorted(literal_uses.items()):
            if key.endswith("."):
                # prefix literal (startswith checks / f-string bases):
                # must cover at least one registered or dynamic key
                if any(k.startswith(key) for k in registered) or \
                        key in dynamic_prefixes:
                    continue
                for path, line in sites:
                    yield Finding(self.id, path, line,
                                  f"conf prefix `{key}` matches no "
                                  "registered key", self.severity)
            elif key not in registered and not any(
                    key.startswith(p) for p in dynamic_prefixes):
                for path, line in sites:
                    yield Finding(
                        self.id, path, line,
                        f"conf key `{key}` is not registered in the "
                        "config registry — setting it silently no-ops "
                        "and it never reaches docs/configs.md",
                        self.severity)

        for key, (path, line) in sorted(registered.items()):
            var = reg_vars.get(key)
            # the holder variable's own Store doesn't count; conf_* calls
            # register plenty of vars loaded exactly once (property
            # wrappers), so any Load at all marks the entry alive
            alive = bool(var and name_loads.get(var, 0) > 0)
            alive = alive or key in literal_uses
            if not alive:
                yield Finding(
                    self.id, path, line,
                    f"dead conf: `{key}` is registered (and documented in "
                    "docs/configs.md) but no code reads it — wire it or "
                    "remove it", self.severity)


_DOC_TOKEN_RE = re.compile(r"^\|\s*`([A-Za-z_][A-Za-z0-9_.]*)`")


class MetricsKeySyncRule(ProjectRule):
    """R8: ``session.last_metrics`` keys and ``docs/metrics.md`` agree.

    Source of truth is the set of keys session.execute assigns into
    ``last_metrics``: docs/metrics.md must table every one of them and
    must not document keys that don't exist.
    """

    id = "R8"
    name = "metrics-key-sync"
    description = ("session.last_metrics keys / docs/metrics.md out of "
                   "sync")

    DOC = "docs/metrics.md"

    def check_project(self, files: Sequence[SourceFile],
                      repo_root: str) -> Iterator[Finding]:
        by_path = {sf.path: sf for sf in files}
        session = by_path.get("spark_rapids_tpu/session.py")
        if session is None:
            return

        session_keys: Dict[str, int] = {}
        for node in ast.walk(session.tree):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Subscript) and \
                            isinstance(t.value, ast.Attribute) and \
                            t.value.attr == "last_metrics":
                        k = str_const(t.slice)
                        if k is not None:
                            session_keys[k] = node.lineno

        doc_path = os.path.join(repo_root, self.DOC)
        if not os.path.exists(doc_path):
            yield Finding(
                self.id, self.DOC, 0,
                f"{self.DOC} is missing: the metrics contract "
                "(session.last_metrics keys) must be documented there",
                self.severity)
            return
        with open(doc_path, encoding="utf-8") as f:
            doc_lines = f.read().splitlines()
        doc_tokens: Dict[str, int] = {}
        for i, ln in enumerate(doc_lines, start=1):
            m = _DOC_TOKEN_RE.match(ln.strip())
            if m:
                doc_tokens[m.group(1)] = i

        for k, line in sorted(session_keys.items()):
            if k not in doc_tokens:
                yield Finding(
                    self.id, "spark_rapids_tpu/session.py", line,
                    f"session.last_metrics key `{k}` is undocumented in "
                    f"{self.DOC}", self.severity)
        for k, line in sorted(doc_tokens.items()):
            if k not in session_keys:
                yield Finding(
                    self.id, self.DOC, line,
                    f"{self.DOC} documents `{k}` but "
                    "session.last_metrics never carries it",
                    self.severity)


class PallasKernelTierRule(Rule):
    """R9: every ``pl.pallas_call`` lives in the kernel tier.

    A bare ``pallas_call`` outside ``kernels/pallas_tier.py`` /
    ``kernels/pallas_strings.py`` bypasses the tier's contract: no conf
    gate, no TPU/interpret backend predicate, no automatic bit-identical
    XLA fallback, no ``pallas`` obs span for rapidsprof, and no
    ``pallasFallbackCount`` accounting — a kernel that fails to lower
    then kills the query instead of degrading.
    """

    id = "R9"
    name = "pallas-kernel-tier"
    description = ("pl.pallas_call outside the registered kernel tier "
                   "(kernels/pallas_tier.py, kernels/pallas_strings.py)")

    ALLOWED_FILES = (
        "spark_rapids_tpu/kernels/pallas_tier.py",
        "spark_rapids_tpu/kernels/pallas_strings.py",
    )

    def check(self, sf: SourceFile) -> Iterator[Finding]:
        if sf.path in self.ALLOWED_FILES:
            return
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func) or ""
            if name == "pallas_call" or name.endswith(".pallas_call"):
                yield self.finding(
                    sf, node,
                    f"`{name}` outside the kernel tier: route through "
                    "kernels.pallas_tier.run (conf gate, backend "
                    "predicate, bit-identical XLA fallback, `pallas` obs "
                    "span, pallasFallbackCount metric)")


ALL_RULES = (
    ImportTimeJnpRule,
    SemaphoreReleaseRule,
    UnboundedWaitRule,
    SwallowBaseExceptionRule,
    DonationHygieneRule,
    SyncUnderRuntimeLockRule,
    ConfRegistrySyncRule,
    MetricsKeySyncRule,
    PallasKernelTierRule,
)


def default_rules() -> List[Rule]:
    return [cls() for cls in ALL_RULES]
