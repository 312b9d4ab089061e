"""dynalint rules DT001-DT016: this repo's real async/JAX hazard classes
(DT017-DT020, the recompile/dispatch-discipline pass, live in compiles.py
and register here).

Each rule is deliberately narrow: it encodes a bug class this codebase has
actually exhibited (blocking WAL I/O on the hub event loop, silent
``except Exception`` swallows around KV transfers, host-device syncs on
the tick loop), not a general style guide.  False-positive pressure is
handled three ways, in order of preference: fix the code, add an inline
``# dynalint: disable=RULE -- justification``, or baseline it.
"""

from __future__ import annotations

import ast
import fnmatch
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from .core import Finding, ModuleInfo, ProjectRule, Rule
from .hotpath import HOT_PATH_MANIFEST

# ---------------------------------------------------------------------------
# AST helpers
# ---------------------------------------------------------------------------


def dotted_name(node: ast.AST) -> Optional[str]:
    """'time.sleep' for Attribute chains over Names; None when the base is
    an arbitrary expression (call result, subscript, ...)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class FunctionInfo:
    def __init__(self, node: ast.AST, qualname: str, cls: Optional[str]):
        self.node = node
        self.qualname = qualname
        self.cls = cls  # enclosing class name, if a method

    @property
    def is_async(self) -> bool:
        return isinstance(self.node, ast.AsyncFunctionDef)

    @property
    def name(self) -> str:
        return self.node.name


def collect_functions(tree: ast.Module) -> List[FunctionInfo]:
    """All function defs in ``tree`` with qualnames.  Memoized on the tree
    object: five rules walk the same module, and the tier-1 gates re-lint
    the whole package several times per test session -- one shared pass
    (ModuleInfo objects are themselves cached by analysis/callgraph.py)."""
    memo = getattr(tree, "_dynalint_functions", None)
    if memo is not None:
        return memo
    out: List[FunctionInfo] = []

    def walk(node: ast.AST, prefix: str, cls: Optional[str]) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qn = f"{prefix}{child.name}"
                out.append(FunctionInfo(child, qn, cls))
                walk(child, qn + ".", cls)
            elif isinstance(child, ast.ClassDef):
                walk(child, f"{prefix}{child.name}.", child.name)
            else:
                walk(child, prefix, cls)

    walk(tree, "", None)
    try:
        tree._dynalint_functions = out  # type: ignore[attr-defined]
    except AttributeError:
        pass
    return out


def own_body_walk(fn: ast.AST) -> Iterator[ast.AST]:
    """Walk a function's statements without descending into nested
    function/lambda scopes (their bodies run elsewhere -- executors,
    callbacks -- so async-context rules must not see them)."""
    stack: List[ast.AST] = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def _body_contains_await(nodes: Sequence[ast.AST]) -> bool:
    """Await anywhere in these statements, nested sync scopes excluded."""
    stack: List[ast.AST] = list(nodes)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.Await, ast.AsyncFor, ast.AsyncWith)):
            return True
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))
    return False


# ---------------------------------------------------------------------------
# DT001: blocking calls inside async def
# ---------------------------------------------------------------------------

_BLOCKING_CALLS: Dict[str, str] = {
    "time.sleep": "use asyncio.sleep",
    "open": "use asyncio.to_thread / run_in_executor",
    "io.open": "use asyncio.to_thread / run_in_executor",
    "os.fsync": "use asyncio.to_thread / run_in_executor",
    "os.fdatasync": "use asyncio.to_thread / run_in_executor",
    "subprocess.run": "use asyncio.create_subprocess_exec",
    "subprocess.call": "use asyncio.create_subprocess_exec",
    "subprocess.check_call": "use asyncio.create_subprocess_exec",
    "subprocess.check_output": "use asyncio.create_subprocess_exec",
    "subprocess.getoutput": "use asyncio.create_subprocess_exec",
    "socket.create_connection": "use asyncio.open_connection",
}

_FILE_METHODS = {
    "read", "readline", "readlines", "write", "writelines", "flush", "seek",
}
_SOCKET_METHODS = {
    "connect", "accept", "recv", "recvfrom", "send", "sendall", "sendto",
    "makefile",
}


def _open_bound_names(fn: ast.AST) -> Set[str]:
    """Names bound to sync file handles inside this function:
    ``f = open(...)`` and ``with open(...) as f``."""
    out: Set[str] = set()
    for node in own_body_walk(fn):
        if isinstance(node, ast.Assign):
            if (
                isinstance(node.value, ast.Call)
                and dotted_name(node.value.func) in ("open", "io.open")
            ):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        out.add(t.id)
        elif isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                if (
                    isinstance(item.context_expr, ast.Call)
                    and dotted_name(item.context_expr.func)
                    in ("open", "io.open")
                    and isinstance(item.optional_vars, ast.Name)
                ):
                    out.add(item.optional_vars.id)
    return out


def _socket_bound_names(fn: ast.AST) -> Set[str]:
    out: Set[str] = set()
    for node in own_body_walk(fn):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            d = dotted_name(node.value.func)
            if d in ("socket.socket", "socket.create_connection"):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        out.add(t.id)
    return out


def _direct_blocking_ops(fn: ast.AST) -> List[Tuple[ast.Call, str]]:
    """(call node, description) for every lexically-direct blocking call in
    this function's own scope."""
    out: List[Tuple[ast.Call, str]] = []
    file_names = _open_bound_names(fn)
    sock_names = _socket_bound_names(fn)
    for node in own_body_walk(fn):
        if not isinstance(node, ast.Call):
            continue
        d = dotted_name(node.func)
        if d in _BLOCKING_CALLS:
            out.append((node, f"blocking call '{d}()' ({_BLOCKING_CALLS[d]})"))
            continue
        if isinstance(node.func, ast.Attribute):
            attr = node.func.attr
            base = node.func.value
            base_name = base.id if isinstance(base, ast.Name) else None
            if base_name in file_names and attr in _FILE_METHODS:
                out.append(
                    (node, f"sync file I/O '{base_name}.{attr}()' on a "
                           "handle from open()")
                )
            elif base_name in sock_names and attr in _SOCKET_METHODS:
                out.append(
                    (node, f"blocking socket op '{base_name}.{attr}()'")
                )
            elif (
                attr == "result"
                and not node.args
                and not node.keywords
                and isinstance(base, (ast.Name, ast.Attribute))
            ):
                out.append(
                    (node, f"'{dotted_name(node.func)}()' -- Future.result() "
                           "blocks the loop; await the future instead")
                )
            elif isinstance(base, ast.Call) and dotted_name(base.func) in (
                "open", "io.open",
            ):
                out.append(
                    (node, f"sync file I/O 'open(...).{attr}()'")
                )
    return out


class BlockingInAsync(Rule):
    id = "DT001"
    name = "blocking-call-in-async"
    severity = "error"
    description = (
        "Blocking calls (time.sleep, sync open/read/write, subprocess, "
        "socket ops, Future.result()) inside 'async def', directly or via a "
        "sync helper defined in the same module, stall the event loop."
    )

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        functions = collect_functions(module.tree)
        # name -> FunctionInfos, for intra-module transitive resolution
        by_name: Dict[str, List[FunctionInfo]] = {}
        for fi in functions:
            by_name.setdefault(fi.name, []).append(fi)

        direct: Dict[int, List[Tuple[ast.Call, str]]] = {
            id(fi.node): _direct_blocking_ops(fi.node) for fi in functions
        }

        def resolve(call: ast.Call, caller: FunctionInfo) -> Optional[FunctionInfo]:
            d = dotted_name(call.func)
            if d is None:
                return None
            if "." not in d:  # bare name: module-level function only
                for cand in by_name.get(d, ()):
                    if cand.cls is None:
                        return cand
                return None
            base, _, meth = d.rpartition(".")
            if base in ("self", "cls") and caller.cls is not None:
                for cand in by_name.get(meth, ()):
                    if cand.cls == caller.cls:
                        return cand
            return None

        # transitive: does fn (or a same-module sync callee chain) block?
        memo: Dict[int, Optional[str]] = {}

        def blocks(fi: FunctionInfo, stack: Set[int]) -> Optional[str]:
            key = id(fi.node)
            if key in memo:
                return memo[key]
            if key in stack:
                return None
            stack.add(key)
            verdict: Optional[str] = None
            ops = direct[key]
            if ops:
                node, desc = ops[0]
                verdict = f"{desc} at line {node.lineno}"
            else:
                for sub in own_body_walk(fi.node):
                    if not isinstance(sub, ast.Call):
                        continue
                    callee = resolve(sub, fi)
                    if callee is None or callee.is_async:
                        continue
                    inner = blocks(callee, stack)
                    if inner is not None:
                        verdict = f"'{callee.name}()' -> {inner}"
                        break
            stack.discard(key)
            memo[key] = verdict
            return verdict

        for fi in functions:
            if not fi.is_async:
                continue
            for node, desc in direct[id(fi.node)]:
                yield self.finding(
                    module, node, f"{desc} in async function", fi.qualname
                )
            for sub in own_body_walk(fi.node):
                if not isinstance(sub, ast.Call):
                    continue
                callee = resolve(sub, fi)
                if callee is None or callee.is_async:
                    continue
                chain = blocks(callee, set())
                if chain is not None:
                    yield self.finding(
                        module, sub,
                        f"async function calls sync helper "
                        f"'{callee.name}()' which blocks: {chain}",
                        fi.qualname,
                    )


# ---------------------------------------------------------------------------
# DT002: threading lock held across await
# ---------------------------------------------------------------------------


class ThreadingLockAcrossAwait(Rule):
    id = "DT002"
    name = "threading-lock-across-await"
    severity = "error"
    description = (
        "A threading.Lock/RLock acquired in an async scope that awaits "
        "while holding it can deadlock the loop (the release may need the "
        "loop thread) and blocks every other coroutine meanwhile."
    )

    def _lock_names(self, tree: ast.Module) -> Set[str]:
        names: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                d = dotted_name(node.value.func)
                if d in ("threading.Lock", "threading.RLock"):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            names.add(t.id)
                        elif isinstance(t, ast.Attribute):
                            names.add(t.attr)
        return names

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        locks = self._lock_names(module.tree)
        if not locks:
            return
        for fi in collect_functions(module.tree):
            if not fi.is_async:
                continue
            for node in own_body_walk(fi.node):
                if isinstance(node, ast.With):
                    for item in node.items:
                        ref = self._lock_ref(item.context_expr, locks)
                        if ref and _body_contains_await(node.body):
                            yield self.finding(
                                module, node,
                                f"threading lock '{ref}' held across "
                                "'await' in async function (use "
                                "asyncio.Lock or release before awaiting)",
                                fi.qualname,
                            )
                elif isinstance(node, ast.Call):
                    if (
                        isinstance(node.func, ast.Attribute)
                        and node.func.attr == "acquire"
                    ):
                        ref = self._lock_ref(node.func.value, locks)
                        if ref:
                            yield self.finding(
                                module, node,
                                f"blocking acquire() of threading lock "
                                f"'{ref}' in async function",
                                fi.qualname,
                            )

    @staticmethod
    def _lock_ref(expr: ast.AST, locks: Set[str]) -> Optional[str]:
        d = dotted_name(expr)
        if d is None:
            return None
        last = d.rpartition(".")[2]
        return d if last in locks else None


# ---------------------------------------------------------------------------
# DT003: silent except swallow
# ---------------------------------------------------------------------------

_LOG_METHOD_NAMES = {
    "debug", "info", "warning", "warn", "error", "exception", "critical",
    "log", "print_exc",
}
_LOG_FUNC_NAMES = {"print", "log_once", "log_throttled", "warn_once"}
_BROAD = {"Exception", "BaseException"}


class SilentExceptSwallow(Rule):
    id = "DT003"
    name = "silent-except-swallow"
    severity = "warning"
    description = (
        "'except Exception' / bare 'except' whose body neither logs, "
        "re-raises, nor uses the caught exception silently destroys the "
        "only evidence of a failure."
    )

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        functions = collect_functions(module.tree)
        qual_by_node = {id(fi.node): fi.qualname for fi in functions}

        def enclosing_qualname(handler: ast.excepthandler) -> str:
            best = ""
            for fi in functions:
                n = fi.node
                if (
                    n.lineno <= handler.lineno
                    and handler.lineno <= (n.end_lineno or n.lineno)
                ):
                    best = qual_by_node[id(n)]
            return best

        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Try):
                continue
            for handler in node.handlers:
                if not self._is_broad(handler.type):
                    continue
                if self._is_handled(handler):
                    continue
                what = (
                    "bare 'except:'" if handler.type is None
                    else "'except Exception'"
                )
                yield self.finding(
                    module, handler,
                    f"{what} swallows the error silently: log it "
                    "(log_throttled for hot paths), re-raise, or use the "
                    "bound exception",
                    enclosing_qualname(handler),
                )

    @staticmethod
    def _is_broad(t: Optional[ast.AST]) -> bool:
        if t is None:
            return True
        if isinstance(t, ast.Name):
            return t.id in _BROAD
        if isinstance(t, ast.Tuple):
            return any(
                isinstance(e, ast.Name) and e.id in _BROAD for e in t.elts
            )
        return False

    @staticmethod
    def _is_handled(handler: ast.excepthandler) -> bool:
        bound = handler.name
        for node in handler.body:
            for sub in ast.walk(node):
                if isinstance(sub, ast.Raise):
                    return True
                if bound and isinstance(sub, ast.Name) and sub.id == bound:
                    return True
                if isinstance(sub, ast.Call):
                    f = sub.func
                    if (
                        isinstance(f, ast.Attribute)
                        and f.attr in _LOG_METHOD_NAMES
                    ):
                        return True
                    if isinstance(f, ast.Name) and f.id in _LOG_FUNC_NAMES:
                        return True
        return False


# ---------------------------------------------------------------------------
# Hot-path resolution shared by DT004/DT005
# ---------------------------------------------------------------------------


def _manifest_match(relpath: str, *names: str) -> bool:
    """Whether any of ``names`` matches a HOT_PATH_MANIFEST pattern for a
    module at ``relpath`` -- the ONE manifest matcher (decorator-based
    hotness is separate; see _is_hot).  Module keys match in either
    orientation (threads._module_key_match): a subdirectory-rooted run
    reporting ``engine/step.py`` hits the ``dynamo_tpu/engine/step.py``
    entry too."""
    from .threads import _module_key_match

    for suffix, patterns in HOT_PATH_MANIFEST.items():
        if _module_key_match(relpath, suffix):
            for pat in patterns:
                if any(fnmatch.fnmatchcase(n, pat) for n in names):
                    return True
    return False


def _is_hot(module: ModuleInfo, fi: FunctionInfo) -> bool:
    for dec in fi.node.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        d = dotted_name(target)
        if d is not None and d.rpartition(".")[2] == "hot_path":
            return True
    return _manifest_match(module.relpath, fi.qualname, fi.name)


def _hot_functions(module: ModuleInfo) -> List[FunctionInfo]:
    """Hot-marked functions; nested defs inherit hotness (jit closures)."""
    functions = collect_functions(module.tree)
    hot = [fi for fi in functions if _is_hot(module, fi)]
    hot_ids = {id(fi.node) for fi in hot}
    out = list(hot)
    for fi in functions:
        if id(fi.node) in hot_ids:
            continue
        for h in hot:
            hn = h.node
            if (
                hn.lineno < fi.node.lineno
                and (fi.node.end_lineno or fi.node.lineno)
                <= (hn.end_lineno or hn.lineno)
            ):
                out.append(fi)
                break
    return out


_LIST_LITERALS = (ast.List, ast.Tuple, ast.ListComp, ast.GeneratorExp,
                  ast.Constant, ast.Dict, ast.Set)


# ---------------------------------------------------------------------------
# DT004: host-device sync in hot paths
# ---------------------------------------------------------------------------


class HostSyncInHotPath(Rule):
    id = "DT004"
    name = "host-device-sync-in-hot-path"
    severity = "warning"
    description = (
        "np.asarray / jax.device_get / .block_until_ready() in a function "
        "marked @hot_path (or in the hot-path manifest) serializes the "
        "pipelined device queue behind a device->host round trip."
    )

    _NP_CTORS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        for fi in _hot_functions(module):
            for node in own_body_walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                d = dotted_name(node.func)
                if d == "jax.device_get":
                    yield self.finding(
                        module, node,
                        "jax.device_get in hot path forces a host sync",
                        fi.qualname,
                    )
                elif (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr == "block_until_ready"
                ):
                    yield self.finding(
                        module, node,
                        ".block_until_ready() in hot path forces a host sync",
                        fi.qualname,
                    )
                elif d in self._NP_CTORS and node.args:
                    arg = node.args[0]
                    # literals / comprehensions are host-side construction
                    # (cheap, no device sync) -- DT005's concern, not ours
                    if not isinstance(arg, _LIST_LITERALS):
                        yield self.finding(
                            module, node,
                            f"{d}(...) on a non-literal in hot path may "
                            "force a device->host transfer",
                            fi.qualname,
                        )


# ---------------------------------------------------------------------------
# DT005: jnp.asarray over request-shaped Python lists in hot paths
# ---------------------------------------------------------------------------


class RecompileHazardInHotPath(Rule):
    id = "DT005"
    name = "recompile-hazard-in-hot-path"
    severity = "warning"
    description = (
        "jnp.asarray over a dynamically-sized Python list (list comp / "
        "list() call) in a hot path bakes the list length into the traced "
        "shape: every distinct request size triggers an XLA recompile. "
        "Pad to a bucketed shape first."
    )

    _JNP_CTORS = {
        "jnp.asarray", "jnp.array", "jax.numpy.asarray", "jax.numpy.array",
    }

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        for fi in _hot_functions(module):
            assigns: Dict[str, ast.AST] = {}
            for node in own_body_walk(fi.node):
                if isinstance(node, ast.Assign):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            assigns[t.id] = node.value
            for node in own_body_walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                d = dotted_name(node.func)
                if d not in self._JNP_CTORS or not node.args:
                    continue
                arg: ast.AST = node.args[0]
                if isinstance(arg, ast.Name) and arg.id in assigns:
                    arg = assigns[arg.id]
                if isinstance(arg, ast.ListComp) or (
                    isinstance(arg, ast.Call)
                    and isinstance(arg.func, ast.Name)
                    and arg.func.id == "list"
                ):
                    yield self.finding(
                        module, node,
                        f"{d}(...) over a dynamically-sized list in hot "
                        "path: distinct lengths recompile the step",
                        fi.qualname,
                    )


# ---------------------------------------------------------------------------
# DT006: codec frame-kind exhaustiveness
# ---------------------------------------------------------------------------


class CodecFrameKindExhaustive(Rule):
    id = "DT006"
    name = "codec-frame-kind-exhaustive"
    severity = "error"
    description = (
        "Every frame kind in runtime/transports/codec.py FRAME_KINDS must "
        "have both an encoder (encode_<kind>*/write_<kind>*) and a decoder "
        "(decode_<kind>*/read_<kind>*) function, so a new wire format "
        "cannot ship half-implemented.  The kind must be the FIRST name "
        "token after the verb: encode_chunk_frame implements 'chunk', not "
        "'frame'."
    )

    CODEC_SUFFIX = "runtime/transports/codec.py"
    _ENC = ("encode", "write")
    _DEC = ("decode", "read")

    @staticmethod
    def _implements(func_name: str, verbs: Tuple[str, ...], kind: str) -> bool:
        """True when ``func_name`` is ``<verb>_<kind>`` or
        ``<verb>_<kind>_...`` -- an exact token match, so one kind's codec
        cannot satisfy another kind whose name it merely contains."""
        parts = func_name.split("_")
        return len(parts) >= 2 and parts[0] in verbs and parts[1] == kind

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        if not module.relpath.endswith(self.CODEC_SUFFIX):
            return
        kinds_node: Optional[ast.Assign] = None
        kinds: List[str] = []
        func_names = [
            n.name for n in module.tree.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        for node in module.tree.body:
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id == "FRAME_KINDS":
                        kinds_node = node
                        if isinstance(node.value, (ast.Tuple, ast.List)):
                            kinds = [
                                e.value for e in node.value.elts
                                if isinstance(e, ast.Constant)
                                and isinstance(e.value, str)
                            ]
        if kinds_node is None:
            yield Finding(
                rule=self.id, severity=self.severity, path=module.relpath,
                line=1, col=1, qualname="",
                message="codec module must declare a FRAME_KINDS registry "
                        "(tuple of frame-kind names) for exhaustiveness "
                        "checking",
                source_line=module.source_line(1),
            )
            return
        for kind in kinds:
            has_enc = any(
                self._implements(f, self._ENC, kind) for f in func_names
            )
            has_dec = any(
                self._implements(f, self._DEC, kind) for f in func_names
            )
            if not has_enc:
                yield self.finding(
                    module, kinds_node,
                    f"frame kind '{kind}' has no encoder "
                    f"(encode_{kind}*/write_{kind}* function)",
                )
            if not has_dec:
                yield self.finding(
                    module, kinds_node,
                    f"frame kind '{kind}' has no decoder "
                    f"(decode_{kind}*/read_{kind}* function)",
                )


# ---------------------------------------------------------------------------
# DT007: metrics-registry hygiene
# ---------------------------------------------------------------------------


class MetricsRegistryHygiene(Rule):
    id = "DT007"
    name = "metrics-registry-hygiene"
    severity = "error"
    description = (
        "prometheus_client metric families (Counter/Gauge/Histogram/"
        "Summary/Info/Enum) must be minted through runtime/metrics.py "
        "MetricsRegistry; inline construction elsewhere bypasses the "
        "get-or-create cache (duplicate-registration errors when tests run "
        "several engines per process) and the documented name catalog."
    )

    REGISTRY_SUFFIX = "runtime/metrics.py"
    _METRIC_CLASSES = {
        "Counter", "Gauge", "Histogram", "Summary", "Info", "Enum",
    }

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        if module.relpath.endswith(self.REGISTRY_SUFFIX):
            return
        # only names provably bound to prometheus_client count: a bare
        # Counter(...) from collections must never trip this rule
        aliases: Dict[str, str] = {}  # local name -> canonical class name
        prom_modules: Set[str] = set()  # names referring to the module
        for node in ast.walk(module.tree):
            if isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                if mod == "prometheus_client" or mod.startswith(
                    "prometheus_client."
                ):
                    for a in node.names:
                        if a.name in self._METRIC_CLASSES:
                            aliases[a.asname or a.name] = a.name
            elif isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "prometheus_client" or a.name.startswith(
                        "prometheus_client."
                    ):
                        prom_modules.add(a.asname or a.name.split(".")[0])
        if not aliases and not prom_modules:
            return

        functions = collect_functions(module.tree)

        def enclosing_qualname(node: ast.AST) -> str:
            best = ""
            for fi in functions:
                n = fi.node
                if (
                    n.lineno <= node.lineno
                    and node.lineno <= (n.end_lineno or n.lineno)
                ):
                    best = fi.qualname
            return best

        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            d = dotted_name(node.func)
            if d is None:
                continue
            if d in aliases:
                cls = aliases[d]
            elif "." in d:
                base, _, last = d.rpartition(".")
                if base in prom_modules and last in self._METRIC_CLASSES:
                    cls = last
                else:
                    continue
            else:
                continue
            yield self.finding(
                module, node,
                f"prometheus {cls}(...) constructed outside "
                f"runtime/metrics.py: mint the family through "
                f"MetricsRegistry.{cls.lower()}() so names stay in the "
                "registry catalog",
                enclosing_qualname(node),
            )


# ---------------------------------------------------------------------------
# DT008: fire-and-forget tasks
# ---------------------------------------------------------------------------


class FireAndForgetTask(Rule):
    id = "DT008"
    name = "fire-and-forget-task"
    severity = "warning"
    description = (
        "asyncio.create_task()/ensure_future() whose handle is neither "
        "stored nor given a done-callback: the event loop holds only a "
        "weak reference (the task can be garbage-collected mid-await) and "
        "an exception inside it is silently swallowed until interpreter "
        "shutdown.  Store the handle (and discard on done), or chain "
        ".add_done_callback(...)."
    )

    _SPAWNERS = {"create_task", "ensure_future"}

    def _is_spawn(self, call: ast.AST) -> bool:
        if not isinstance(call, ast.Call):
            return False
        d = dotted_name(call.func)
        if d is None:
            return False
        base, _, last = d.rpartition(".")
        if last not in self._SPAWNERS:
            return False
        if not base:
            return True  # bare name: from asyncio import create_task
        # only asyncio itself and event-loop handles spawn unreferenced
        # tasks; TaskGroup.create_task (the group holds the reference and
        # surfaces crashes) and unrelated .create_task methods are clean
        root = base.rpartition(".")[2]
        return root == "asyncio" or root.endswith("loop")

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        functions = collect_functions(module.tree)

        def enclosing_qualname(node: ast.AST) -> str:
            best = ""
            for fi in functions:
                n = fi.node
                if (
                    n.lineno <= node.lineno
                    and node.lineno <= (n.end_lineno or n.lineno)
                ):
                    best = fi.qualname
            return best

        for node in ast.walk(module.tree):
            # the discarded-result shape is precisely an expression
            # statement whose value IS the spawn call; assignments,
            # arguments (tasks.add(create_task(...))) and chained
            # .add_done_callback(...) all keep or register the handle
            if not isinstance(node, ast.Expr):
                continue
            call = node.value
            if isinstance(call, ast.Await):
                continue  # awaited inline: not fire-and-forget
            if not self._is_spawn(call):
                continue
            fn = dotted_name(call.func)
            yield self.finding(
                module, call,
                f"'{fn}(...)' result discarded: store the task handle "
                "(with a done-callback discard) or chain "
                ".add_done_callback() so crashes inside it surface",
                enclosing_qualname(call),
            )


# ---------------------------------------------------------------------------
# DT009: synchronous device<->host transfers in offload-engine modules
# ---------------------------------------------------------------------------


class OffloadSyncTransfer(Rule):
    id = "DT009"
    name = "offload-sync-transfer"
    severity = "error"
    description = (
        "Synchronous device<->host transfers (jax.device_get / "
        "jax.device_put / np.asarray-family on array args / "
        ".block_until_ready()) inside an offload-engine module "
        "(*/offload.py) are forbidden outside the designated copy helpers "
        "named in the module's COPY_HELPERS tuple: tier puts/gets run on "
        "threads the admission path may wait on, so one accidental "
        "blocking transfer turns the offload plane back into a tick-loop "
        "stall.  Materialize through the designated helper (which runs "
        "only on the offload thread) instead."
    )

    OFFLOAD_SUFFIX = "/offload.py"
    _SYNC_FNS = {"jax.device_get", "jax.device_put"}
    _CTORS = {
        "np.asarray", "np.array", "numpy.asarray", "numpy.array",
        "jnp.asarray", "jnp.array", "jax.numpy.asarray", "jax.numpy.array",
    }

    @staticmethod
    def _copy_helpers(module: ModuleInfo) -> Set[str]:
        """Function names -- or dotted qualnames like
        ``RemoteTier._put``, pinning one method of a class whose other
        methods stay checked -- listed in the module-level
        ``COPY_HELPERS`` assignment (tuple/list/set of string
        literals)."""
        out: Set[str] = set()
        for node in module.tree.body:
            if not isinstance(node, ast.Assign):
                continue
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "COPY_HELPERS":
                    if isinstance(node.value, (ast.Tuple, ast.List, ast.Set)):
                        out.update(
                            e.value for e in node.value.elts
                            if isinstance(e, ast.Constant)
                            and isinstance(e.value, str)
                        )
        return out

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        if not (
            module.relpath.endswith(self.OFFLOAD_SUFFIX)
            or module.relpath == "offload.py"
        ):
            return
        helpers = self._copy_helpers(module)
        for fi in collect_functions(module.tree):
            if fi.name in helpers or fi.qualname in helpers:
                continue
            for node in own_body_walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                d = dotted_name(node.func)
                if d in self._SYNC_FNS:
                    yield self.finding(
                        module, node,
                        f"{d}(...) outside the designated COPY_HELPERS "
                        "blocks an offload path on a device transfer",
                        fi.qualname,
                    )
                elif (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr == "block_until_ready"
                ):
                    yield self.finding(
                        module, node,
                        ".block_until_ready() outside the designated "
                        "COPY_HELPERS blocks an offload path on the device",
                        fi.qualname,
                    )
                elif d in self._CTORS and node.args:
                    arg = node.args[0]
                    if not isinstance(arg, _LIST_LITERALS):
                        yield self.finding(
                            module, node,
                            f"{d}(...) on a non-literal outside the "
                            "designated COPY_HELPERS may materialize a "
                            "device array synchronously",
                            fi.qualname,
                        )


# ---------------------------------------------------------------------------
# DT010: jitted step entry points missing from the hot-path manifest
# ---------------------------------------------------------------------------


class HotPathManifestDrift(Rule):
    id = "DT010"
    name = "hot-path-manifest-drift"
    severity = "error"
    description = (
        "A jitted entry point in a step/kernel/parallel module "
        "(engine/step.py, engine/kv_cache.py, ops/*.py, parallel/*.py) is "
        "covered by neither "
        "an @hot_path decorator nor a HOT_PATH_MANIFEST pattern.  "
        "DT004/DT005 scan exactly the marked surface, so an unlisted "
        "jax.jit entry point silently loses host-sync and "
        "recompile-hazard coverage -- manifest drift: the kernel was "
        "added, the manifest was not.  (This class of drift is real: the "
        "manifest carried a paged_attention* pattern that matched nothing "
        "after a rename, dropping coverage of paged_decode_attention_v2; "
        "and the sharded-serving refactor's assignment-form wrappers -- "
        "``step = partial(jax.jit, ...)(_impl)`` -- dropped the raw "
        "bodies until the assignment form below was added.)  Add the "
        "function to HOT_PATH_MANIFEST or decorate it with @hot_path."
    )

    _JIT_NAMES = {"jax.jit", "jit"}
    _PARTIALS = {"partial", "functools.partial"}

    @classmethod
    def _applies(cls, relpath: str) -> bool:
        if relpath.endswith(("engine/step.py", "engine/kv_cache.py")):
            return True
        head, _, fname = relpath.rpartition("/")
        return fname.endswith(".py") and (
            head in ("ops", "parallel", "spec")
            or head.endswith("/ops")
            or head.endswith("/parallel")
            # the speculative-decoding package grew jitted entry points
            # (the model drafter's forward): same drift class, same rule
            or head.endswith("/spec")
        )

    @classmethod
    def _is_jitted(cls, fi: FunctionInfo) -> bool:
        for dec in fi.node.decorator_list:
            if dotted_name(dec) in cls._JIT_NAMES:
                return True
            if isinstance(dec, ast.Call):
                d = dotted_name(dec.func)
                if d in cls._JIT_NAMES:
                    return True
                if d in cls._PARTIALS and dec.args:
                    if dotted_name(dec.args[0]) in cls._JIT_NAMES:
                        return True
        return False

    @classmethod
    def _jit_wrapped_impl(cls, call: ast.AST) -> Optional[str]:
        """The wrapped function's dotted name for assignment-form jits:
        ``jax.jit(impl, ...)`` or ``partial(jax.jit, ...)(impl)``; None
        for anything else."""
        if not isinstance(call, ast.Call) or not call.args:
            return None
        if dotted_name(call.func) in cls._JIT_NAMES:
            return dotted_name(call.args[0])
        inner = call.func
        if (
            isinstance(inner, ast.Call)
            and dotted_name(inner.func) in cls._PARTIALS
            and inner.args
            and dotted_name(inner.args[0]) in cls._JIT_NAMES
        ):
            return dotted_name(call.args[0])
        return None

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        if not self._applies(module.relpath):
            return
        functions = {
            fi.qualname: fi for fi in collect_functions(module.tree)
        }
        for fi in functions.values():
            if fi.qualname != fi.name:
                continue  # entry points are module top-level
            if not self._is_jitted(fi):
                continue
            if _is_hot(module, fi):
                continue
            yield self.finding(
                module, fi.node,
                f"jitted entry point {fi.name!r} is in neither "
                "HOT_PATH_MANIFEST nor @hot_path-decorated: DT004/DT005 "
                "will not scan it (manifest drift)",
                fi.qualname,
            )
        # assignment-form wrappers: ``step = partial(jax.jit, ...)(impl)``
        # (the raw-impl split the sharded serving path re-jits).  Covered
        # when the assigned name OR the raw impl is manifest/hot-marked.
        for node in module.tree.body:
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            target = node.targets[0]
            if not isinstance(target, ast.Name):
                continue
            impl = self._jit_wrapped_impl(node.value)
            if impl is None:
                continue
            if _manifest_match(module.relpath, target.id):
                continue
            impl_fi = functions.get(impl.rpartition(".")[2])
            if impl_fi is not None and _is_hot(module, impl_fi):
                continue
            yield self.finding(
                module, node,
                f"jit-wrapped entry point {target.id!r} (raw impl "
                f"{impl!r}) is in neither HOT_PATH_MANIFEST nor "
                "@hot_path-decorated: DT004/DT005 will not scan its body "
                "(manifest drift)",
                target.id,
            )


# ---------------------------------------------------------------------------
# DT011: multichip jit entry points must declare in/out shardings
# ---------------------------------------------------------------------------


class MultichipShardingsDeclared(Rule):
    id = "DT011"
    name = "multichip-shardings-undeclared"
    severity = "error"
    description = (
        "A call-form ``jax.jit(fn, ...)`` in a parallel/ module (the "
        "sharded-serving re-jit surface, e.g. make_sharded_steps) omits "
        "``in_shardings`` or ``out_shardings``.  These re-jits exist "
        "precisely to pin placements: with the declarations missing, "
        "GSPMD falls back to propagation-from-operands, and one "
        "host-built operand (a fresh batch array, a scratch buffer) can "
        "silently flip the whole recurrent state -- including the paged "
        "KV pool -- to fully replicated.  A replicated KV pool is not an "
        "error anywhere: decode still produces correct tokens while "
        "every chip stores every page and pays an all-gather per step.  "
        "Declare both kwargs (an explicit ``None`` means 'deliberately "
        "unconstrained' and satisfies the rule); decorator-form jits in "
        "parallel/ that shard internally via shard_map are out of scope."
    )

    _JIT_NAMES = {"jax.jit", "jit"}

    @classmethod
    def _applies(cls, relpath: str) -> bool:
        head, _, fname = relpath.rpartition("/")
        return fname.endswith(".py") and (
            head == "parallel" or head.endswith("/parallel")
        )

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        if not self._applies(module.relpath):
            return
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            if dotted_name(node.func) not in self._JIT_NAMES:
                continue
            if not node.args:
                continue  # partial(jax.jit, ...): jit is the arg, not callee
            kw = {k.arg for k in node.keywords if k.arg}
            missing = sorted({"in_shardings", "out_shardings"} - kw)
            if missing:
                target = dotted_name(node.args[0]) or "<expr>"
                yield self.finding(
                    module, node,
                    f"jax.jit({target}, ...) in a parallel/ module omits "
                    f"{' and '.join(missing)}: placement falls back to "
                    "operand propagation and the KV pool can be silently "
                    "replicated across the mesh",
                    target,
                )


# ---------------------------------------------------------------------------
# DT012: ad-hoc perf_counter timing in engine/ hot paths
# ---------------------------------------------------------------------------


class AdHocTimingInEngine(Rule):
    id = "DT012"
    name = "adhoc-timing-in-engine"
    severity = "error"
    description = (
        "A direct ``time.perf_counter()`` / ``perf_counter_ns()`` call in "
        "an ``engine/`` module.  The tick loop now has a first-class "
        "timing plane (runtime/profiling.TickProfiler: phase marks, "
        "dispatch-gap accounting, the tick ring) and a metrics registry; "
        "ad-hoc stopwatch pairs in the hot path measure one thing for one "
        "debug session, drift from the exported numbers, and stay behind "
        "as per-tick overhead.  Route the measurement through the "
        "profiler (``tick.mark(...)`` / ``observe_phase``) or a registry "
        "family; the pre-existing justified sites (dispatch stamps that "
        "feed ``dynamo_*`` histograms) carry inline suppressions.  "
        "``field(default_factory=time.perf_counter)`` references are out "
        "of scope -- they are stamps consumed by metrics code, not "
        "stopwatch pairs."
    )

    _CLOCK_NAMES = {
        "time.perf_counter", "perf_counter",
        "time.perf_counter_ns", "perf_counter_ns",
    }

    @staticmethod
    def _applies(relpath: str) -> bool:
        head, _, fname = relpath.rpartition("/")
        return fname.endswith(".py") and (
            head == "engine" or head.endswith("/engine")
        )

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        if not self._applies(module.relpath):
            return
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            if dotted_name(node.func) not in self._CLOCK_NAMES:
                continue
            yield self.finding(
                module, node,
                "ad-hoc perf_counter timing in engine/: route through "
                "TickProfiler (tick.mark/observe_phase) or a "
                "runtime/metrics.py family so the number is exported, "
                "not stranded",
            )


# ---------------------------------------------------------------------------
# DT013: blocking work on the tick thread outside the async-commit helpers
# ---------------------------------------------------------------------------


class BlockingOnTickThread(Rule):
    id = "DT013"
    name = "blocking-on-tick-thread"
    severity = "error"
    description = (
        "A blocking device fetch (``jax.device_get`` / "
        "``.block_until_ready()``), a detokenization call, or a stream-"
        "fanout queue put (``.put_nowait``) in a tick-loop module "
        "(engine/engine.py, mocker/engine.py) outside the functions named "
        "in the module-level ``TICK_COMMIT_HELPERS`` tuple.  The async "
        "dispatch pipeline (ISSUE 13) keeps the tick thread free of "
        "host-blocking work: device results materialize only inside the "
        "designated commit helpers (where readiness was already probed or "
        "the pipeline chose to block), and detok/stream fanout ride the "
        "bounded off-tick worker.  A stray blocking call anywhere else in "
        "the tick body silently re-serializes the host between two device "
        "dispatches -- exactly the regression BENCH_r05 measured.  Move "
        "the call into a designated helper or route it through the "
        "fanout/commit planes."
    )

    _MODULES = ("engine/engine.py", "mocker/engine.py")
    _SYNC_FNS = {"jax.device_get"}
    _BLOCKING_ATTRS = {"block_until_ready"}
    _FANOUT_ATTRS = {"put_nowait"}
    _DETOK_ATTRS = {"detokenize", "decode_stream"}

    @classmethod
    def _applies(cls, relpath: str) -> bool:
        return any(
            relpath == m or relpath.endswith("/" + m) for m in cls._MODULES
        )

    @staticmethod
    def _helpers(module: ModuleInfo) -> Set[str]:
        """Function names listed in the module-level
        ``TICK_COMMIT_HELPERS`` tuple (the COPY_HELPERS pattern)."""
        out: Set[str] = set()
        for node in module.tree.body:
            if not isinstance(node, ast.Assign):
                continue
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "TICK_COMMIT_HELPERS":
                    if isinstance(node.value, (ast.Tuple, ast.List, ast.Set)):
                        out.update(
                            e.value for e in node.value.elts
                            if isinstance(e, ast.Constant)
                            and isinstance(e.value, str)
                        )
        return out

    def check(self, module: ModuleInfo) -> Iterator[Finding]:
        if not self._applies(module.relpath):
            return
        helpers = self._helpers(module)
        for fi in collect_functions(module.tree):
            if fi.name in helpers:
                continue
            for node in own_body_walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                d = dotted_name(node.func)
                attr = (
                    node.func.attr
                    if isinstance(node.func, ast.Attribute)
                    else None
                )
                if d in self._SYNC_FNS or attr in self._BLOCKING_ATTRS:
                    yield self.finding(
                        module, node,
                        f"blocking device fetch ({d or attr}) outside the "
                        "designated TICK_COMMIT_HELPERS serializes the "
                        "tick thread behind the device",
                        fi.qualname,
                    )
                elif attr in self._FANOUT_ATTRS:
                    yield self.finding(
                        module, node,
                        "stream-fanout put outside the designated "
                        "TICK_COMMIT_HELPERS: route events through the "
                        "fanout worker/_dispatch plane",
                        fi.qualname,
                    )
                elif attr in self._DETOK_ATTRS:
                    yield self.finding(
                        module, node,
                        "detokenization on the tick thread: detok belongs "
                        "to the Backend operator / fanout worker, never "
                        "between two device dispatches",
                        fi.qualname,
                    )


# ---------------------------------------------------------------------------
# DT014/DT015/DT016: interprocedural thread-role rules (analysis/threads.py)
# ---------------------------------------------------------------------------


def _thread_analysis(index):
    """One ThreadRoleAnalysis per ProjectIndex, shared by DT014-DT016."""
    from .threads import ThreadRoleAnalysis

    memo = getattr(index, "_dynalint_thread_roles", None)
    if memo is None:
        memo = ThreadRoleAnalysis(index)
        index._dynalint_thread_roles = memo
    return memo


class SharedMutableAttributeRace(ProjectRule):
    id = "DT014"
    name = "shared-mutable-attribute-race"
    severity = "error"
    description = (
        "An instance attribute written from one thread role and "
        "read/written from a conflicting role with no common lockset.  "
        "Roles come from analysis/threads.py (thread-entry discovery + "
        "call-graph propagation + THREAD_ROLE_MANIFEST); a lockset is the "
        "set of 'with self._lock:' regions covering the access (plus the "
        "*_locked-suffix convention for helpers called under the class "
        "lock).  Attributes whose type is a designed handoff (queue.Queue, "
        "asyncio.Queue, Event, executors) and writes in __init__ (before "
        "any thread exists) are exempt.  Justify a reviewed exception with "
        "@thread_confined('role') on the mis-roled function or an inline "
        "'# dynalint: disable=DT014 -- why' at the reported write."
    )

    def check_project(self, index) -> Iterator[Finding]:
        from .threads import rolesets_conflict

        analysis = _thread_analysis(index)
        from .threads import collect_attr_accesses

        for ci in index.classes.values():
            accesses = collect_attr_accesses(ci, index)
            by_attr: Dict[str, List] = {}
            for a in accesses:
                if analysis.roles_of(a.fn):
                    by_attr.setdefault(a.attr, []).append(a)
            for attr in sorted(by_attr):
                acc = by_attr[attr]
                writes = [a for a in acc if a.kind == "write"]
                if not writes:
                    continue
                hit = None
                for w in writes:
                    wr = analysis.roles_of(w.fn)
                    for other in acc:
                        if other is w:
                            # a multi-role function racing itself still
                            # needs the single-access case below
                            pair = rolesets_conflict(wr, wr)
                            if pair is None:
                                continue
                        else:
                            pair = rolesets_conflict(
                                wr, analysis.roles_of(other.fn)
                            )
                        if pair is None:
                            continue
                        if w.locks & other.locks:
                            continue
                        hit = (w, other, pair)
                        break
                    if hit:
                        break
                if hit is None:
                    continue
                w, other, (r1, r2) = hit
                # anchor at the UNLOCKED side so the justification (an
                # inline suppression) sits on the access that needs it
                anchor, remote, ra, rb = w, other, r1, r2
                if w.locks and not other.locks and other is not w:
                    anchor, remote, ra, rb = other, w, r2, r1
                module = index.modules.get(ci.relpath)
                src = ""
                if module is not None:
                    src = module.source_line(anchor.line)
                where = (
                    "itself (the function runs under conflicting roles)"
                    if remote is anchor else
                    f"{remote.fn.qualname} [{rb}] at line {remote.line} "
                    f"({remote.kind})"
                )
                yield Finding(
                    rule=self.id, severity=self.severity, path=ci.relpath,
                    line=anchor.line, col=anchor.col,
                    qualname=anchor.fn.qualname, source_line=src,
                    message=(
                        f"attribute '{attr}' of {ci.name}: {anchor.kind} "
                        f"in {anchor.fn.qualname} [{ra}] races "
                        f"{where} with no common lock: roles {ra}/{rb} "
                        "run in parallel -- guard both sides with one "
                        "lock, confine to a single role, or hand off "
                        "through a queue"
                    ),
                )


class CrossThreadPublication(ProjectRule):
    id = "DT015"
    name = "cross-thread-publication-hazard"
    severity = "warning"
    description = (
        "A live mutable container attribute (self.<list/dict/set/deque>) "
        "passed directly into Thread(target=..., args=...), "
        "executor.submit(...), run_in_executor(...), asyncio.to_thread"
        "(...) or a queue put: the receiving thread iterates/reads the "
        "SAME object the owner keeps mutating (RuntimeError: dict changed "
        "size during iteration -- or silently torn reads).  Snapshot at "
        "the boundary (list(x), dict(x), x.copy()) or document the "
        "handoff with an inline suppression."
    )

    _COPY_WRAPPERS = {
        "list", "dict", "set", "tuple", "sorted", "frozenset", "bytes",
    }

    def _is_live_container(self, expr: ast.AST, ci) -> Optional[str]:
        """The attribute name if ``expr`` is a bare self.<container-attr>."""
        if (
            isinstance(expr, ast.Attribute)
            and isinstance(expr.value, ast.Name)
            and expr.value.id == "self"
            and expr.attr in ci.container_attrs
        ):
            return expr.attr
        return None

    def check_project(self, index) -> Iterator[Finding]:
        analysis = _thread_analysis(index)
        # thread/executor handoffs: every argument of the entry call
        for entry in analysis.entries:
            ci = index.class_of(entry.caller)
            if ci is None:
                continue
            args = list(entry.site.args) + [
                kw.value for kw in entry.site.keywords
            ]
            for arg in args:
                for sub in self._publication_args(arg):
                    attr = self._is_live_container(sub, ci)
                    if attr is None:
                        continue
                    module = index.modules.get(entry.caller.relpath)
                    yield Finding(
                        rule=self.id, severity=self.severity,
                        path=entry.caller.relpath,
                        line=sub.lineno, col=sub.col_offset + 1,
                        qualname=entry.caller.qualname,
                        source_line=(
                            module.source_line(sub.lineno)
                            if module else ""
                        ),
                        message=(
                            f"live mutable attribute 'self.{attr}' "
                            f"({ci.container_attrs[attr]}) passed into a "
                            f"{entry.kind} boundary: the worker sees "
                            "every later mutation mid-flight -- snapshot "
                            f"it (e.g. list(self.{attr})) or document "
                            "the handoff"
                        ),
                    )
        # queue puts
        for fn in index.functions.values():
            ci = index.class_of(fn)
            if ci is None:
                continue
            for node in _walk_own(fn.node):
                if not isinstance(node, ast.Call):
                    continue
                func = node.func
                if not (
                    isinstance(func, ast.Attribute)
                    and func.attr in ("put", "put_nowait")
                ):
                    continue
                recv = func.value
                recv_attr = (
                    recv.attr
                    if isinstance(recv, ast.Attribute)
                    and isinstance(recv.value, ast.Name)
                    and recv.value.id == "self"
                    else None
                )
                if recv_attr is None or recv_attr not in ci.safe_attrs:
                    # only a receiver provably bound to a queue type is a
                    # handoff boundary; session.put(url, ...) is not
                    continue
                for arg in node.args:
                    attr = self._is_live_container(arg, ci)
                    if attr is None:
                        continue
                    module = index.modules.get(fn.relpath)
                    yield Finding(
                        rule=self.id, severity=self.severity,
                        path=fn.relpath, line=arg.lineno,
                        col=arg.col_offset + 1, qualname=fn.qualname,
                        source_line=(
                            module.source_line(arg.lineno) if module else ""
                        ),
                        message=(
                            f"live mutable attribute 'self.{attr}' "
                            f"({ci.container_attrs[attr]}) put on a "
                            "queue: the consumer reads the SAME object "
                            "the producer keeps mutating -- snapshot it "
                            f"(e.g. list(self.{attr})) before the put"
                        ),
                    )

    def _publication_args(self, arg: ast.AST) -> List[ast.AST]:
        """Expressions inside one entry argument that are published as-is:
        the argument itself, or tuple/list elements (Thread args=(...)).
        Copy wrappers (list(x), x.copy(), x[:]) neutralize the hazard."""
        if isinstance(arg, (ast.Tuple, ast.List)):
            out: List[ast.AST] = []
            for el in arg.elts:
                out.extend(self._publication_args(el))
            return out
        if isinstance(arg, ast.Call):
            d = dotted_name(arg.func)
            if d in self._COPY_WRAPPERS:
                return []
            if (
                isinstance(arg.func, ast.Attribute)
                and arg.func.attr == "copy"
            ):
                return []
            return []  # other call results: fresh objects, not live attrs
        if isinstance(arg, ast.Subscript):
            return []  # x[:] or an element -- not the live container
        return [arg]


class ThreadRoleManifestDrift(ProjectRule):
    id = "DT016"
    name = "thread-role-manifest-drift"
    severity = "error"
    description = (
        "A thread entry point (threading.Thread(target=...), "
        "executor.submit, run_in_executor, asyncio.to_thread) whose "
        "target gets NO role: the executor has no thread_name_prefix, "
        "the target is a handle inference cannot resolve, and no "
        "THREAD_ROLE_MANIFEST pattern covers it.  DT014 scans exactly "
        "the roled surface, so an unroled entry silently loses race "
        "coverage for everything it runs -- manifest drift: the thread "
        "was added, the role model was not.  Name the executor "
        "(thread_name_prefix=...), or add the entry to "
        "THREAD_ROLE_MANIFEST (analysis/threads.py)."
    )

    def check_project(self, index) -> Iterator[Finding]:
        analysis = _thread_analysis(index)
        for entry in analysis.entries:
            if entry.covered:
                continue
            module = index.modules.get(entry.caller.relpath)
            src = (
                module.source_line(entry.site.lineno) if module else ""
            )
            if entry.role is None:
                why = (
                    "no role: the executor/thread carries no "
                    "thread_name_prefix and no manifest entry names it"
                )
            else:
                why = (
                    f"target '{entry.target_text}' cannot be resolved to "
                    "a project function and no manifest pattern covers it"
                )
            yield Finding(
                rule=self.id, severity=self.severity,
                path=entry.caller.relpath, line=entry.site.lineno,
                col=entry.site.col_offset + 1,
                qualname=entry.caller.qualname, source_line=src,
                message=(
                    f"{entry.kind} entry '{entry.target_text}' is not "
                    f"covered by thread-role inference ({why}): add a "
                    "THREAD_ROLE_MANIFEST entry or name the executor so "
                    "DT014 can see what runs there"
                ),
            )


def _walk_own(fn: ast.AST) -> Iterator[ast.AST]:
    from .callgraph import own_scope_walk

    return own_scope_walk(fn)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

from .compiles import RECOMPILE_RULES  # noqa: E402  (needs Rule/core loaded)

ALL_RULES: List[Rule] = [
    BlockingInAsync(),
    ThreadingLockAcrossAwait(),
    SilentExceptSwallow(),
    HostSyncInHotPath(),
    RecompileHazardInHotPath(),
    CodecFrameKindExhaustive(),
    MetricsRegistryHygiene(),
    FireAndForgetTask(),
    OffloadSyncTransfer(),
    HotPathManifestDrift(),
    MultichipShardingsDeclared(),
    AdHocTimingInEngine(),
    BlockingOnTickThread(),
    SharedMutableAttributeRace(),
    CrossThreadPublication(),
    ThreadRoleManifestDrift(),
    # DT017-DT020 (compiles.py): recompile hazards + dispatch discipline
    *RECOMPILE_RULES,
]


def get_rules(select: Optional[Sequence[str]] = None) -> List[Rule]:
    if not select:
        return list(ALL_RULES)
    wanted = {s.strip().upper() for s in select if s.strip()}
    unknown = wanted - {r.id for r in ALL_RULES}
    if unknown:
        raise ValueError(f"unknown rule ids: {sorted(unknown)}")
    return [r for r in ALL_RULES if r.id in wanted]
