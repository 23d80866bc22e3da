"""The jaxlint rule catalog.

Every rule is a function ``check(module, ctx) -> list[Finding]`` registered
through the :func:`rule` decorator. Rules are pure AST analyses — no jax
import, no execution — tuned for the invariants this codebase's hot paths
live and die by (see README "Static analysis" for the catalog and the
rationale behind each).

Adding a rule::

    @rule("JX09", "my-rule", "error", "one-line summary")
    def check_my_rule(module, ctx):
        return [finding(RULES["my-rule"], module, node, "message") ...]

and add a fixture pair (one firing snippet, one clean/suppressed) to
``tests/test_jaxlint.py::RULE_FIXTURES``.
"""

import ast
import dataclasses

from pyrecover_tpu.analysis.callgraph import dotted_name
from pyrecover_tpu.analysis.engine import Finding

RULES = {}


@dataclasses.dataclass
class Rule:
    id: str
    name: str
    severity: str
    summary: str
    check: object


def rule(rule_id, name, severity, summary):
    def deco(fn):
        RULES[name] = Rule(rule_id, name, severity, summary, fn)
        return fn

    return deco


def finding(r, module, node, message):
    return Finding(
        rule=r.name, rule_id=r.id, severity=r.severity, path=module.relpath,
        line=getattr(node, "lineno", 1),
        col=getattr(node, "col_offset", 0) + 1, message=message,
    )


# ---- shared helpers ---------------------------------------------------------

# calls that *produce or transform* device values (used for taint/device-work)
DEVICE_PREFIXES = ("jnp.", "jax.numpy.", "jax.lax.", "jax.random.", "jax.nn.")
DEVICE_EXACT = {
    "jax.device_put", "jax.vjp", "jax.grad", "jax.value_and_grad",
    "jax.vmap", "jax.pmap", "jax.checkpoint",
}
TIME_CALLS = {"time.perf_counter", "time.monotonic", "time.time"}


def _is_device_call(call, bound_names=()):
    d = dotted_name(call.func)
    if d is None:
        return False
    if d in DEVICE_EXACT or d.startswith(DEVICE_PREFIXES):
        return True
    return d in bound_names


def _stmts_in(module, fn_node):
    """Statements belonging directly to ``fn_node`` (not to nested defs),
    in source order — the rules' linear approximation of program order."""
    out = [
        n for n in ast.walk(fn_node)
        if isinstance(n, ast.stmt) and n is not fn_node
        and module.enclosing_function(n) is fn_node
    ]
    out.sort(key=lambda n: (n.lineno, n.col_offset))
    return out


def _calls_in(module, root, fn_node):
    for n in ast.walk(root):
        if isinstance(n, ast.Call) and module.enclosing_function(n) is fn_node:
            yield n


def _innermost_stmt(module, node):
    for anc in module.ancestors(node):
        if isinstance(anc, ast.stmt):
            return anc
    return None


def _stmt_calls(module, stmt, fn_node):
    """Calls whose innermost enclosing statement is ``stmt`` itself —
    ``_stmts_in`` lists compound statements AND their children, so a
    per-statement scan that walked the whole subtree would visit nested
    calls once per nesting level (and attribute them to the wrong line)."""
    for n in ast.walk(stmt):
        if (
            isinstance(n, ast.Call)
            and module.enclosing_function(n) is fn_node
            and _innermost_stmt(module, n) is stmt
        ):
            yield n


def _target_names(stmt):
    """Flattened Name targets of an assignment statement."""
    targets = []
    if isinstance(stmt, ast.Assign):
        targets = stmt.targets
    elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
        targets = [stmt.target]
    elif isinstance(stmt, ast.For):
        targets = [stmt.target]
    names = []

    def flat(t):
        if isinstance(t, ast.Name):
            names.append(t.id)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                flat(e)

    for t in targets:
        flat(t)
    return names


def _module_functions(module, ctx):
    return ctx.index.by_module.get(module, [])


# ---- JX01: host syncs in the hot loop ---------------------------------------

_SYNC_CASTS = {"float", "int", "bool"}
_HOST_ARRAY_FNS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}


def _in_loop(module, node, fn_node):
    for anc in module.ancestors(node):
        if anc is fn_node:
            return False
        if isinstance(anc, (ast.For, ast.While)):
            return True
    return False


def _host_sync_desc(call):
    """Describe the host↔device sync a call forces, or None."""
    func = call.func
    if isinstance(func, ast.Attribute) and func.attr == "item" and not call.args:
        return ".item() materializes a device value on the host"
    d = dotted_name(func)
    if d == "jax.device_get":
        return "jax.device_get() forces a device->host transfer"
    if (
        isinstance(func, ast.Name) and func.id in _SYNC_CASTS
        and len(call.args) == 1 and not call.keywords
        and isinstance(call.args[0], (ast.Name, ast.Subscript))
    ):
        return (
            f"{func.id}() on a device value blocks until the dispatch "
            "queue drains"
        )
    if d in _HOST_ARRAY_FNS and call.args and isinstance(
        call.args[0], (ast.Name, ast.Subscript, ast.Attribute)
    ):
        return f"{d}() on a device value copies it to the host"
    return None


@rule(
    "JX01", "host-sync-in-hot-loop", "error",
    "host↔device sync inside a loop of a function reachable from the "
    "train step",
)
def check_host_sync(module, ctx):
    out = []
    for fn in ctx.hot_functions:
        if fn.module is not module:
            continue
        for call in _calls_in(module, fn.node, fn.node):
            if not _in_loop(module, call, fn.node):
                continue
            desc = _host_sync_desc(call)
            if desc:
                out.append(finding(
                    RULES["host-sync-in-hot-loop"], module, call,
                    f"{desc} inside the hot loop ({fn.qualname}); batch it "
                    "to a sync point or annotate the deliberate sync",
                ))
    return out


# ---- JX02: PRNG key reuse ---------------------------------------------------

_KEY_PRODUCERS = {"key", "PRNGKey", "split", "fold_in", "wrap_key_data"}


def _jax_random_fn(module, ctx, call):
    """Name of the jax.random function a call refers to, else None."""
    d = dotted_name(call.func)
    froms = ctx.index.from_imports.get(module, {})
    aliases = ctx.index.import_aliases.get(module, {})
    if d:
        if d.startswith("jax.random."):
            return d[len("jax.random."):]
        head, _, tail = d.partition(".")
        if tail and "." not in tail:
            if froms.get(head) == ("jax", "random") or \
                    aliases.get(head) == "jax.random":
                return tail
    if isinstance(call.func, ast.Name):
        imp = froms.get(call.func.id)
        if imp is not None and imp[0] == "jax.random":
            return imp[1]
    return None


@rule(
    "JX02", "prng-key-reuse", "error",
    "the same PRNG key consumed by jax.random more than once without "
    "split/fold_in",
)
def check_prng_reuse(module, ctx):
    out = []
    for fn in _module_functions(module, ctx):
        uses = {}  # key var -> lineno of its (single allowed) consumption
        for stmt in _stmts_in(module, fn.node):
            for call in _stmt_calls(module, stmt, fn.node):
                rf = _jax_random_fn(module, ctx, call)
                if rf is None or rf in {"key", "PRNGKey"}:
                    continue
                # every other jax.random.* call CONSUMES its key argument
                # (split/fold_in included — after either, the original key
                # must never feed a sampler again)
                if call.args and isinstance(call.args[0], ast.Name):
                    name = call.args[0].id
                    if name in uses:
                        out.append(finding(
                            RULES["prng-key-reuse"], module, call,
                            f"PRNG key '{name}' already consumed at line "
                            f"{uses[name]}; reusing it yields correlated "
                            "randomness — split/fold_in first",
                        ))
                    else:
                        uses[name] = call.lineno
            for name in _target_names(stmt):
                # rebound (fresh key from split/key, or something else
                # entirely): either way the old consumption no longer counts
                uses.pop(name, None)
    return out


# ---- JX03: read after donation ----------------------------------------------


def _donated_positions(call):
    """Donated argnums of a ``jax.jit(...)`` call, else None."""
    if dotted_name(call.func) not in {"jax.jit", "jit"}:
        return None
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return (v.value,)
            if isinstance(v, (ast.Tuple, ast.List)):
                return tuple(
                    e.value for e in v.elts
                    if isinstance(e, ast.Constant) and isinstance(e.value, int)
                )
    return None


@rule(
    "JX03", "donated-buffer-reuse", "error",
    "a buffer passed through a donated argnum is read after the call "
    "invalidated it",
)
def check_donated_reuse(module, ctx):
    out = []
    factory_map = dict(ctx.config.donating_factories)
    for fn in _module_functions(module, ctx):
        donating = {}  # local callable name -> donated positions
        # decorator form: @partial(jax.jit, donate_argnums=...) — the
        # partial call carries the donate keyword, its first arg is jit
        for nested in _module_functions(module, ctx):
            if nested.parent is not None and nested.parent.node is not fn.node:
                continue
            for dec in nested.node.decorator_list:
                if not (
                    isinstance(dec, ast.Call)
                    and dotted_name(dec.func) in {"partial", "functools.partial"}
                    and dec.args and dotted_name(dec.args[0]) in {"jax.jit", "jit"}
                ):
                    continue
                jit_like = ast.Call(
                    func=ast.Name(id="jit", ctx=ast.Load()),
                    args=[], keywords=dec.keywords,
                )
                pos = _donated_positions(jit_like)
                if pos:
                    donating[nested.name] = tuple(pos)
        stmts = _stmts_in(module, fn.node)
        donated = {}  # var name -> (donation lineno, callee name)
        for stmt in stmts:
            # does this statement donate anything / create a donating fn?
            for call in _stmt_calls(module, stmt, fn.node):
                pos = _donated_positions(call)
                if pos is not None and isinstance(stmt, ast.Assign):
                    for name in _target_names(stmt):
                        donating[name] = pos
                    continue
                if isinstance(call.func, ast.Name):
                    cname = call.func.id
                    if cname in factory_map and isinstance(stmt, ast.Assign):
                        for name in _target_names(stmt):
                            donating[name] = tuple(factory_map[cname])
                        continue
                    if cname in donating:
                        rebound = set(_target_names(stmt))
                        for p in donating[cname]:
                            if p < len(call.args) and isinstance(
                                call.args[p], ast.Name
                            ):
                                a = call.args[p].id
                                if a not in rebound:
                                    donated[a] = (stmt.lineno, cname)
            # reads of donated names in this statement (after donation line)
            for node in ast.walk(stmt):
                if (
                    isinstance(node, ast.Name)
                    and isinstance(node.ctx, ast.Load)
                    and node.id in donated
                    and _innermost_stmt(module, node) is stmt
                    and node.lineno > donated[node.id][0]
                ):
                    dline, callee = donated.pop(node.id)
                    out.append(finding(
                        RULES["donated-buffer-reuse"], module, node,
                        f"'{node.id}' was donated to '{callee}' at line "
                        f"{dline}; its buffer is invalid after the call",
                    ))
            # rebinds clear donation tracking
            for name in _target_names(stmt):
                donated.pop(name, None)
    return out


# ---- JX04: Python branching on traced values under jit ----------------------


def _is_static_guard(test):
    """Branches jit resolves at trace time: ``x is None``, isinstance."""
    if isinstance(test, ast.Compare) and all(
        isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops
    ):
        return True
    if isinstance(test, ast.Call) and dotted_name(test.func) in {
        "isinstance", "callable", "hasattr"
    }:
        return True
    return False


def _device_expr(e, taint):
    """True when evaluating ``e`` involves a (likely) traced array value.
    Static metadata (.shape/.ndim/.dtype/len()) kills the taint."""
    if isinstance(e, ast.Name):
        return e.id in taint
    if isinstance(e, ast.Call):
        d = dotted_name(e.func)
        if d and (d in DEVICE_EXACT or d.startswith(DEVICE_PREFIXES)):
            return True
        if d in {"len", "isinstance", "getattr", "hasattr", "type"}:
            return False
        args = list(e.args) + [k.value for k in e.keywords]
        return any(_device_expr(a, taint) for a in args)
    if isinstance(e, ast.Attribute):
        if e.attr in {"shape", "ndim", "dtype", "size", "sharding"}:
            return False
        return _device_expr(e.value, taint)
    if isinstance(e, ast.Subscript):
        return _device_expr(e.value, taint)
    if isinstance(e, ast.BinOp):
        return _device_expr(e.left, taint) or _device_expr(e.right, taint)
    if isinstance(e, ast.UnaryOp):
        return _device_expr(e.operand, taint)
    if isinstance(e, ast.Compare):
        return _device_expr(e.left, taint) or any(
            _device_expr(c, taint) for c in e.comparators
        )
    if isinstance(e, ast.BoolOp):
        return any(_device_expr(v, taint) for v in e.values)
    if isinstance(e, ast.IfExp):
        return any(
            _device_expr(x, taint) for x in (e.test, e.body, e.orelse)
        )
    if isinstance(e, (ast.Tuple, ast.List)):
        return any(_device_expr(x, taint) for x in e.elts)
    return False


@rule(
    "JX04", "traced-python-branch", "error",
    "Python if/while on a traced value inside jit — concretization error "
    "or silent trace-time constant",
)
def check_traced_branch(module, ctx):
    out = []
    for fn in _module_functions(module, ctx):
        if not fn.is_jit:
            continue
        taint = set()
        for stmt in _stmts_in(module, fn.node):
            if isinstance(stmt, (ast.If, ast.While)) and not _is_static_guard(
                stmt.test
            ):
                if _device_expr(stmt.test, taint):
                    kind = "while" if isinstance(stmt, ast.While) else "if"
                    out.append(finding(
                        RULES["traced-python-branch"], module, stmt,
                        f"Python '{kind}' on a traced value inside a "
                        "jit-compiled function — use jax.lax.cond/"
                        "jax.lax.while_loop or jnp.where",
                    ))
            if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                value = getattr(stmt, "value", None)
                if value is not None:
                    tainted = _device_expr(value, taint)
                    for name in _target_names(stmt):
                        if tainted:
                            taint.add(name)
                        else:
                            taint.discard(name)
    return out


# ---- JX05: side effects under jit -------------------------------------------

_WALLCLOCK = {
    "time.time", "time.monotonic", "time.perf_counter", "time.process_time",
    "datetime.datetime.now", "datetime.datetime.utcnow",
}


@rule(
    "JX05", "side-effect-in-jit", "error",
    "host side effect under jit runs at trace time only (once per "
    "compilation, not per step)",
)
def check_side_effects(module, ctx):
    out = []
    r = RULES["side-effect-in-jit"]
    for fn in _module_functions(module, ctx):
        if not fn.is_jit:
            continue
        for node in ast.walk(fn.node):
            if module.enclosing_function(node) is not fn.node:
                continue
            if isinstance(node, ast.Call):
                d = dotted_name(node.func)
                if d == "print":
                    out.append(finding(
                        r, module, node,
                        "print() under jit fires at trace time only — use "
                        "jax.debug.print for per-step output",
                    ))
                elif d in _WALLCLOCK:
                    out.append(finding(
                        r, module, node,
                        f"{d}() under jit is baked in as a trace-time "
                        "constant — time on the host, around the jitted "
                        "call",
                    ))
                elif d and (
                    d.startswith("np.random.") or d.startswith("numpy.random.")
                ):
                    out.append(finding(
                        r, module, node,
                        f"{d}() under jit produces one trace-time sample — "
                        "use jax.random with an explicit key",
                    ))
                elif d in {"open", "input"}:
                    out.append(finding(
                        r, module, node,
                        f"{d}() under jit is a trace-time-only host side "
                        "effect",
                    ))
            elif isinstance(node, (ast.Global, ast.Nonlocal)):
                out.append(finding(
                    r, module, node,
                    "mutating enclosing Python state under jit happens at "
                    "trace time only — thread state through the function "
                    "instead",
                ))
    return out


# ---- JX06: non-hashable static args -----------------------------------------

_MUTABLE_DISPLAYS = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp,
                     ast.SetComp)


def _static_info(call):
    """(argnums tuple, argnames tuple) declared on a jax.jit call."""
    nums, names = (), ()
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                nums = (v.value,)
            elif isinstance(v, (ast.Tuple, ast.List)):
                nums = tuple(
                    e.value for e in v.elts
                    if isinstance(e, ast.Constant) and isinstance(e.value, int)
                )
        elif kw.arg == "static_argnames":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                names = (v.value,)
            elif isinstance(v, (ast.Tuple, ast.List)):
                names = tuple(
                    e.value for e in v.elts
                    if isinstance(e, ast.Constant) and isinstance(e.value, str)
                )
    return nums, names


@rule(
    "JX06", "nonhashable-static-arg", "error",
    "a list/dict/set passed (or defaulted) for a static jit argument — "
    "unhashable, raises or silently retriggers compilation",
)
def check_static_args(module, ctx):
    out = []
    r = RULES["nonhashable-static-arg"]
    # jitted callables with static decls: name -> (argnums, argnames)
    statics = {}
    for node in ast.walk(module.tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            info = _static_info(node.value) if dotted_name(
                node.value.func
            ) in {"jax.jit", "jit"} else ((), ())
            if info != ((), ()):
                for name in _target_names(node):
                    statics[name] = info
    for fn in _module_functions(module, ctx):
        for dec in fn.node.decorator_list:
            if isinstance(dec, ast.Call) and dotted_name(dec.func) in {
                "partial", "functools.partial"
            } and dec.args and dotted_name(dec.args[0]) in {"jax.jit", "jit"}:
                info = _static_info(dec)
                if info != ((), ()):
                    statics[fn.name] = info
                    # mutable DEFAULTS on static-by-name params
                    args = fn.node.args
                    defaults = dict(zip(
                        [a.arg for a in args.args][-len(args.defaults):],
                        args.defaults,
                    )) if args.defaults else {}
                    for pname in info[1]:
                        dflt = defaults.get(pname)
                        if isinstance(dflt, _MUTABLE_DISPLAYS):
                            out.append(finding(
                                r, module, dflt,
                                f"static arg '{pname}' defaults to a "
                                "mutable value — use a tuple/frozenset",
                            ))
    # call sites
    for node in ast.walk(module.tree):
        if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)):
            continue
        info = statics.get(node.func.id)
        if info is None:
            continue
        nums, names = info
        for p in nums:
            if p < len(node.args) and isinstance(
                node.args[p], _MUTABLE_DISPLAYS
            ):
                out.append(finding(
                    r, module, node.args[p],
                    f"mutable value passed at static_argnums position {p} "
                    f"of '{node.func.id}' — static args must be hashable",
                ))
        for kw in node.keywords:
            if kw.arg in names and isinstance(kw.value, _MUTABLE_DISPLAYS):
                out.append(finding(
                    r, module, kw.value,
                    f"mutable value passed for static arg '{kw.arg}' of "
                    f"'{node.func.id}' — static args must be hashable",
                ))
    return out


# ---- JX07: timing spans that never sync -------------------------------------

_SYNC_MARKERS = {"block_until_ready", "item"}


def _is_sync_call(call):
    func = call.func
    if isinstance(func, ast.Attribute) and func.attr in _SYNC_MARKERS:
        return True
    d = dotted_name(func)
    if d in {"jax.block_until_ready", "jax.device_get"} or d in _HOST_ARRAY_FNS:
        return True
    if (
        isinstance(func, ast.Name) and func.id in _SYNC_CASTS
        and len(call.args) == 1
    ):
        return True
    return False


@rule(
    "JX07", "untimed-device-work", "warning",
    "a perf_counter/monotonic span around async-dispatched device work "
    "without block_until_ready — it times the enqueue, not the compute",
)
def check_untimed_device_work(module, ctx):
    out = []
    r = RULES["untimed-device-work"]
    for fn in _module_functions(module, ctx):
        stmts = _stmts_in(module, fn.node)
        timer_start = {}  # name -> lineno of latest start
        bound = set()  # names bound to jitted/device-step callables
        calls = []  # (lineno, call) in order
        for stmt in stmts:
            for call in _stmt_calls(module, stmt, fn.node):
                calls.append(call)
                d = dotted_name(call.func)
                if isinstance(stmt, ast.Assign):
                    if d in TIME_CALLS and not call.args:
                        for name in _target_names(stmt):
                            timer_start[name] = stmt.lineno
                    if d in {"jax.jit", "jit"} or (
                        isinstance(call.func, ast.Name)
                        and call.func.id in ctx.config.device_step_factories
                    ):
                        bound.update(_target_names(stmt))
        seen_lines = set()
        for node in ast.walk(fn.node):
            if module.enclosing_function(node) is not fn.node:
                continue
            if not (isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub)):
                continue
            right = node.right
            if not (isinstance(right, ast.Name) and right.id in timer_start):
                continue
            start, read = timer_start[right.id], node.lineno
            if read <= start or read in seen_lines:
                continue
            window = [c for c in calls if start < c.lineno <= read]
            device = [c for c in window if _is_device_call(c, bound)]
            if not device:
                continue
            last_device = max(c.lineno for c in device)
            synced = any(
                _is_sync_call(c) for c in window if c.lineno >= last_device
            )
            if not synced:
                seen_lines.add(read)
                out.append(finding(
                    r, module, node,
                    f"span '{right.id}' (started line {start}) times device "
                    f"work dispatched at line {last_device} without "
                    "block_until_ready — under async dispatch this measures "
                    "enqueue cost, not device time",
                ))
    return out


# ---- JX09: PartitionSpec literals naming unknown mesh axes ------------------

_PSPEC_SOURCES = {"jax.sharding", "jax.interpreters.pxla"}


def _pspec_aliases(module, ctx):
    """Local names bound to PartitionSpec via from-imports (the
    ``from jax.sharding import PartitionSpec as P`` convention)."""
    froms = ctx.index.from_imports.get(module, {})
    return {
        name for name, (mod, orig) in froms.items()
        if orig == "PartitionSpec" and mod in _PSPEC_SOURCES
    }


@rule(
    "JX09", "pspec-unknown-axis", "error",
    "a PartitionSpec literal names a mesh axis outside the AXIS_* "
    "catalog — the axis is silently dropped and the dim replicated",
)
def check_pspec_axes(module, ctx):
    known = ctx.config.pspec_axes
    aliases = _pspec_aliases(module, ctx)
    out = []
    r = RULES["pspec-unknown-axis"]
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        d = dotted_name(node.func)
        is_pspec = (
            d is not None
            and (d == "PartitionSpec" or d.endswith(".PartitionSpec"))
        ) or (isinstance(node.func, ast.Name) and node.func.id in aliases)
        if not is_pspec:
            continue
        for arg in node.args:
            elts = (
                arg.elts if isinstance(arg, (ast.Tuple, ast.List)) else [arg]
            )
            for e in elts:
                if (
                    isinstance(e, ast.Constant)
                    and isinstance(e.value, str)
                    and e.value not in known
                ):
                    out.append(finding(
                        r, module, e,
                        f"PartitionSpec axis {e.value!r} is not a mesh "
                        f"axis ({', '.join(sorted(known))}) — "
                        "_filter_spec_for_mesh drops unknown names and "
                        "the dimension replicates silently",
                    ))
    return out


# ---- JX10: durable writes that skip the tmp+fsync+rename discipline ---------

_WRITE_MODES = {"w", "wb", "w+", "wb+", "a", "ab", "a+", "ab+", "x", "xb"}
_PATH_WRITE_ATTRS = {"write_text", "write_bytes"}
_RENAME_DOTTED = {"os.replace", "os.rename"}
_TMPISH = ("tmp", "temp")


def _open_write_mode(call):
    """The write mode of an ``open()`` call, else None (default mode is
    read; ``os.fdopen`` is exempt — its fd came from ``tempfile``)."""
    if dotted_name(call.func) != "open":
        return None
    mode = None
    if len(call.args) >= 2:
        mode = call.args[1]
    for kw in call.keywords:
        if kw.arg == "mode":
            mode = kw.value
    if isinstance(mode, ast.Constant) and isinstance(mode.value, str) and \
            mode.value in _WRITE_MODES:
        return mode.value
    return None


def _mentions_tmp(expr):
    """True when the write-target expression references a tmp-ish name or
    literal — the staged half of the commit discipline."""
    for node in ast.walk(expr):
        text = None
        if isinstance(node, ast.Name):
            text = node.id
        elif isinstance(node, ast.Attribute):
            text = node.attr
        elif isinstance(node, ast.Constant) and isinstance(node.value, str):
            text = node.value
        if text is not None and any(t in text.lower() for t in _TMPISH):
            return True
    return False


@rule(
    "JX10", "torn-write", "error",
    "a durable-path write skips the tmp+fsync+atomic-rename commit "
    "discipline — a crash mid-write (or mid-publish, without fsync) "
    "leaves a torn file the next resume half-trusts",
)
def check_torn_write(module, ctx):
    out = []
    r = RULES["torn-write"]
    for fn in _module_functions(module, ctx):
        writes = []  # (node, target expr, desc)
        renames = []
        for call in _calls_in(module, fn.node, fn.node):
            d = dotted_name(call.func)
            mode = _open_write_mode(call)
            if mode is not None and call.args:
                writes.append((call, call.args[0], f"open(..., '{mode}')"))
            elif isinstance(call.func, ast.Attribute) and \
                    call.func.attr in _PATH_WRITE_ATTRS:
                writes.append(
                    (call, call.func.value, f".{call.func.attr}()")
                )
            if d in _RENAME_DOTTED:
                renames.append((call, d))
        # durability may live in a sibling nested def of the same commit
        # routine (the vanilla writer's _fsync_once/_rename_once split) —
        # judge fsync presence over the OUTERMOST enclosing function
        outer = fn
        while outer.parent is not None:
            outer = outer.parent
        has_fsync = any(
            isinstance(c, ast.Call) and (
                dotted_name(c.func) == "os.fsync"
                or (isinstance(c.func, ast.Attribute)
                    and c.func.attr == "fsync")
            )
            for c in ast.walk(outer.node)
        )
        if renames:
            # the function IS a commit site: the rename must be preceded
            # by durability, or a power cut after the publish leaves the
            # final name pointing at unsynced pages
            if not has_fsync:
                for call, d in renames:
                    out.append(finding(
                        r, module, call,
                        f"{d}() publishes without an fsync in the same "
                        "commit path — flush+fsync the staged file (and "
                        "ideally the directory) before the atomic rename",
                    ))
            continue  # staged writes belong to the discipline
        for call, target, desc in writes:
            if _mentions_tmp(target):
                continue  # writing the staged half; publish is elsewhere
            out.append(finding(
                r, module, call,
                f"{desc} writes a durable path in place — a crash "
                "mid-write leaves a torn file; stage to a tmp sibling, "
                "fsync, then os.replace (or annotate the deliberately "
                "tear-tolerant site)",
            ))
    return out


# ---- JX08: legacy/private jax spellings -------------------------------------
# One installation (ENV_LOCK.txt pins jax 0.9.0): the public spellings all
# exist, so there is no shim layer and nothing is exempt.

_LEGACY_MODULES = {
    "jax.experimental.shard_map": "use jax.shard_map",
    "jax.experimental.maps":
        "the maps/xmap surface is retired; use jax.shard_map",
    "jax.experimental.pjit":
        "pjit is jax.jit now; sharding comes from the mesh context",
}


@rule(
    "JX08", "legacy-jax-spelling", "error",
    "legacy or private jax spelling where the installed jax has a public one",
)
def check_legacy_spelling(module, ctx):
    out = []
    r = RULES["legacy-jax-spelling"]

    def legacy_msg(name):
        for mod, msg in _LEGACY_MODULES.items():
            if name == mod or name.startswith(mod + "."):
                return msg
        if name == "jax._src" or name.startswith("jax._src."):
            return (
                "jax._src is private API with no stability guarantee — "
                "use the public spelling"
            )
        return None

    seen = set()
    for node in ast.walk(module.tree):
        names = []
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            names = [base] + [f"{base}.{a.name}" for a in node.names]
        elif isinstance(node, ast.Attribute):
            d = dotted_name(node)
            if d:
                names = [d]
            if node.attr == "thread_resources":
                names.append("jax.experimental.maps")
        for name in names:
            msg = legacy_msg(name)
            key = (node.lineno, msg)
            if msg and key not in seen:
                seen.add(key)
                out.append(finding(r, module, node, f"'{name}': {msg}"))
    return out
