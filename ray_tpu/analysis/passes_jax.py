"""graftlint JAX passes: host-sync-in-hot-path and jit-boundary hygiene.

host-sync guards the engine-loop design invariant from PR 6: dispatch
phases are host-cost-only, and the device sync lives in the designated
harvest methods (``_harvest_one`` / ``_apply_verify`` / the tier flush).
Since PR 31 that includes the eager device ops that wait without saying
so: ``jnp.stack`` / ``jnp.concatenate`` of device values and a tuple-
unpacked ``random.split`` run on the calling thread, each behind whatever
the device is doing.
jit-hygiene guards against the mid-traffic-recompile class PR 6 had to
build runtime detection for: jitted callables that close over mutable
``self`` state or branch in Python on traced values re-trace silently
when that state drifts.
"""

from __future__ import annotations

import ast
import re
from typing import Optional

from ray_tpu.analysis.core import ModuleSource, Pass, iter_functions, register

# Engine hot-path methods: the loop's admit/prefill/dispatch family.
# Harvest-designated methods (_harvest_one, _apply_verify), warmup, and
# the tier spill/restore slow paths are exempt by name.
HOT_METHOD_RE = re.compile(
    r"^(_admit|_prefill|_prefill_chunks|_arm_slot|_decode_step|_spec_step|"
    r"_dispatch_verify|_select_block|_record_token|_flush_slot_patches|"
    r"_propose_locked|_shed_expired_waiting|_step|_loop|submit)$")

# modules the host-sync pass applies to (the paged engine + its kin)
HOT_PATH_RE = re.compile(r"serve/llm/")


def _is_random_split(fn: ast.AST) -> bool:
    """``jax.random.split`` / ``self._jax.random.split`` / ``random.split``."""
    if not (isinstance(fn, ast.Attribute) and fn.attr == "split"):
        return False
    base = fn.value
    return (isinstance(base, ast.Attribute) and base.attr == "random") \
        or (isinstance(base, ast.Name) and base.id == "random")


def _is_np_attr(fn: ast.AST, attrs: tuple) -> bool:
    return (isinstance(fn, ast.Attribute) and fn.attr in attrs
            and isinstance(fn.value, ast.Name)
            and fn.value.id in ("np", "numpy", "onp"))


@register
class HostSyncPass(Pass):
    """Device->host syncs inside engine dispatch/decode/verify methods.

    ``np.asarray`` / ``np.array`` on a device array, ``.item()``,
    ``jax.device_get`` and ``.block_until_ready()`` stall the engine loop
    on the device stream; they belong in the harvest phase (PR 6 phase
    timers attribute device wait there on purpose). ``jnp.asarray`` is
    host->device and fine. ``jnp.stack`` / ``jnp.concatenate`` and
    ``a, b = ...random.split(key)`` are eager device ops (one primitive an
    operand, two slices of the split): on a busy device each blocks the
    thread until the device reaches it. Build host values in numpy and
    do device work inside a jitted program.
    """

    id = "host-sync"
    title = "host sync in an engine hot path"
    hint = ("harvest device values in _harvest_one/_apply_verify (the "
            "designated sync points) or pragma "
            "`# graftlint: disable=host-sync` with a justification")

    def run(self, module: ModuleSource) -> list:
        if not HOT_PATH_RE.search(module.relpath):
            return []
        findings = []
        for fn, qualname, cls in iter_functions(module.tree):
            if cls is None or not HOT_METHOD_RE.match(fn.name):
                continue
            for node in ast.walk(fn):
                tag, what = self._tag(node)
                if tag is not None:
                    findings.append(self.emit(
                        module, node, qualname,
                        f"{tag} {what} inside {fn.name} (hot path)", tag,
                        extra_pragma_lines=(fn.lineno,)))
        return [f for f in findings if f is not None]

    @classmethod
    def _tag(cls, node: ast.AST) -> tuple:
        """(tag, what it does) for a node the pass reports, else (None,
        None)."""
        if isinstance(node, ast.Call):
            tag = cls._sync_tag(node)
            if tag is not None:
                return tag, "forces a device->host sync"
            fn = node.func
            if isinstance(fn, ast.Attribute) \
                    and fn.attr in ("stack", "concatenate") \
                    and isinstance(fn.value, ast.Name) \
                    and fn.value.id == "jnp":
                return (f"jnp.{fn.attr}",
                        "is an eager device op on the loop thread")
        elif isinstance(node, ast.Assign) \
                and isinstance(node.targets[0], ast.Tuple) \
                and isinstance(node.value, ast.Call) \
                and _is_random_split(node.value.func):
            return ("random.split", "unpacked eagerly slices a device "
                    "array on the loop thread")
        return None, None

    @staticmethod
    def _sync_tag(call: ast.Call) -> Optional[str]:
        fn = call.func
        if _is_np_attr(fn, ("asarray", "array")):
            return f"np.{fn.attr}"
        if isinstance(fn, ast.Attribute):
            if fn.attr == "block_until_ready":
                return "block_until_ready"
            if fn.attr == "device_get" and isinstance(fn.value, ast.Name) \
                    and fn.value.id == "jax":
                return "jax.device_get"
            if fn.attr == "item" and not call.args:
                return ".item()"
        if isinstance(fn, ast.Name) and fn.id in ("float", "int") \
                and call.args and isinstance(call.args[0], ast.Subscript):
            # float(logits[0])-style scalar pulls
            return f"{fn.id}(x[...])"
        return None


# ---------------------------------------------------------------------------


def _jit_targets(tree: ast.AST):
    """Yield (callable_node_or_name, jit_call_node, static_argnums) for
    every function handed to jax.jit / jit / pjit, plus decorated defs."""
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _is_jit(node.func) and node.args:
            out.append((node.args[0], node, _static_argnums(node)))
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _is_jit(dec):
                    out.append((node, dec, ()))
                elif isinstance(dec, ast.Call):
                    if _is_jit(dec.func):
                        out.append((node, dec, _static_argnums(dec)))
                    elif isinstance(dec.func, ast.Attribute) \
                            and dec.func.attr == "partial" or \
                            isinstance(dec.func, ast.Name) \
                            and dec.func.id == "partial":
                        if dec.args and _is_jit(dec.args[0]):
                            out.append((node, dec, _static_argnums(dec)))
    return out


def _is_jit(fn: ast.AST) -> bool:
    if isinstance(fn, ast.Name):
        return fn.id in ("jit", "pjit")
    if isinstance(fn, ast.Attribute):
        return fn.attr in ("jit", "pjit")
    return False


def _static_argnums(call: ast.Call) -> tuple:
    for kw in call.keywords:
        if kw.arg in ("static_argnums", "static_argnames"):
            if isinstance(kw.value, ast.Tuple):
                return tuple(e.value for e in kw.value.elts
                             if isinstance(e, ast.Constant))
            if isinstance(kw.value, ast.Constant):
                return (kw.value.value,)
    return ()


@register
class JitHygienePass(Pass):
    """Functions passed to jax.jit/pjit that read mutable state or branch
    in Python on traced values.

    Checks the jitted callable's own body (one level — called helpers are
    the callee's responsibility): reads of ``self.X`` where ``X`` is
    assigned outside ``__init__`` (mutated at runtime => silent re-trace
    or stale capture), reads of mutable module globals, and ``if``/
    ``while`` tests on non-static parameters (TracerBoolConversionError
    at best, shape-specialized silent recompiles at worst).
    """

    id = "jit-hygiene"
    title = "jit-boundary hygiene"
    hint = ("pass mutable state as an explicit argument (donate if "
            "large), mark config args static_argnums, and replace "
            "Python branches on traced values with lax.cond/jnp.where")

    def run(self, module: ModuleSource) -> list:
        findings = []
        mutable_globals = self._mutable_globals(module.tree)
        class_mutables = self._class_mutable_attrs(module.tree)
        # map: function name -> def node (module + class scope), for
        # resolving jax.jit(name) / jax.jit(self._name) references
        defs: dict[str, ast.AST] = {}
        owner: dict[str, Optional[ast.ClassDef]] = {}
        for fn, qualname, cls in iter_functions(module.tree):
            defs.setdefault(fn.name, fn)
            owner.setdefault(fn.name, cls)

        seen: set[int] = set()
        for target, jit_call, static in _jit_targets(module.tree):
            fn_node, cls = self._resolve(target, defs, owner)
            if fn_node is None or id(fn_node) in seen:
                continue
            seen.add(id(fn_node))
            symbol = getattr(fn_node, "name", "<lambda>")
            mut_attrs = class_mutables.get(cls, set()) if cls else set()
            findings.extend(self._check_fn(
                module, fn_node, symbol, mut_attrs, mutable_globals, static))
        return [f for f in findings if f is not None]

    # -- resolution ------------------------------------------------------
    @staticmethod
    def _resolve(target, defs, owner):
        """(function_node, owning_class_node) for a jit target, best
        effort: lambdas and defs analyzed directly; names / self._m
        resolved within the module."""
        if isinstance(target, (ast.Lambda, ast.FunctionDef,
                               ast.AsyncFunctionDef)):
            # owning class unknown for inline defs; harmless (self-attr
            # checks then key off the lambda's own reads of self)
            return target, None
        name = None
        if isinstance(target, ast.Name):
            name = target.id
        elif isinstance(target, ast.Attribute) \
                and isinstance(target.value, ast.Name) \
                and target.value.id == "self":
            name = target.attr
        if name is not None and name in defs:
            return defs[name], owner.get(name)
        return None, None

    # -- model building --------------------------------------------------
    @staticmethod
    def _mutable_globals(tree: ast.AST) -> set[str]:
        """Module-level names assigned a value (not imports/defs) that are
        not ALL_CAPS constants."""
        out: set[str] = set()
        for node in tree.body if isinstance(tree, ast.Module) else ():
            targets = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)) \
                    and node.value is not None:
                targets = [node.target]
            for t in targets:
                if isinstance(t, ast.Name) and not t.id.isupper() \
                        and not t.id.startswith("__"):
                    out.add(t.id)
        return out

    @staticmethod
    def _class_mutable_attrs(tree: ast.AST) -> dict:
        """Per class: self attributes assigned outside __init__ (runtime-
        mutable), including subscript/augmented stores."""
        out: dict = {}
        for node in ast.walk(tree):
            if not isinstance(node, ast.ClassDef):
                continue
            mutable: set[str] = set()
            for meth in node.body:
                if not isinstance(meth, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                if meth.name == "__init__":
                    continue
                for sub in ast.walk(meth):
                    attr = None
                    if isinstance(sub, ast.Assign):
                        for t in sub.targets:
                            attr = attr or _self_attr_target(t)
                    elif isinstance(sub, (ast.AugAssign, ast.AnnAssign)):
                        attr = _self_attr_target(sub.target)
                    if attr:
                        mutable.add(attr)
            out[node] = mutable
        return out

    # -- the actual checks ----------------------------------------------
    def _check_fn(self, module, fn, symbol, mut_attrs, mutable_globals,
                  static) -> list:
        findings = []
        params = self._params(fn)
        static_names = {params[i] for i in static
                        if isinstance(i, int) and i < len(params)}
        static_names.update(s for s in static if isinstance(s, str))
        local_names = set(params)
        for node in ast.walk(fn):
            if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Store):
                local_names.add(node.id)
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        for node in ast.walk(fn):
            # (a) mutable self attribute reads
            if isinstance(node, ast.Attribute) \
                    and isinstance(node.ctx, ast.Load) \
                    and isinstance(node.value, ast.Name) \
                    and node.value.id == "self" \
                    and node.attr in mut_attrs:
                findings.append(self.emit(
                    module, node, symbol,
                    f"jitted function reads self.{node.attr}, which is "
                    f"reassigned outside __init__ — the trace captures a "
                    f"stale value or re-traces mid-traffic",
                    f"self.{node.attr}",
                    extra_pragma_lines=(fn.lineno,)))
            # (b) mutable module-global reads
            elif isinstance(node, ast.Name) \
                    and isinstance(node.ctx, ast.Load) \
                    and node.id in mutable_globals \
                    and node.id not in local_names:
                findings.append(self.emit(
                    module, node, symbol,
                    f"jitted function reads mutable module global "
                    f"{node.id!r} — captured at trace time, silently stale "
                    f"after", f"global:{node.id}",
                    extra_pragma_lines=(fn.lineno,)))
            # (c) Python branches on traced parameters
            elif isinstance(node, (ast.If, ast.While, ast.IfExp)):
                bad = self._traced_test_param(node.test, set(params),
                                              static_names)
                if bad is not None:
                    findings.append(self.emit(
                        module, node, symbol,
                        f"Python `{'if' if not isinstance(node, ast.While) else 'while'}` "
                        f"on traced parameter {bad!r} inside a jitted "
                        f"function — TracerBoolConversionError or a compile "
                        f"per runtime value", f"branch:{bad}",
                        extra_pragma_lines=(fn.lineno,)))
        return findings

    @staticmethod
    def _params(fn) -> list[str]:
        a = fn.args
        names = [p.arg for p in a.posonlyargs + a.args]
        if names and names[0] == "self":
            names = names[1:]
        return names

    @staticmethod
    def _traced_test_param(test, params: set, static_names: set):
        """Name of a non-static parameter the test truth-depends on, or
        None. `is (not) None` identity checks are Python-level and fine."""
        if isinstance(test, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
            return None
        for node in ast.walk(test):
            if isinstance(node, ast.Call):
                # len(x), x.shape checks etc. are static under tracing
                return None
            if isinstance(node, ast.Attribute) and node.attr in (
                    "shape", "ndim", "dtype", "size"):
                return None
        for node in ast.walk(test):
            if isinstance(node, ast.Name) and node.id in params \
                    and node.id not in static_names:
                return node.id
        return None


def _self_attr_target(t) -> Optional[str]:
    if isinstance(t, ast.Attribute) and isinstance(t.value, ast.Name) \
            and t.value.id == "self":
        return t.attr
    if isinstance(t, ast.Subscript):
        return _self_attr_target(t.value)
    if isinstance(t, (ast.Tuple, ast.List)):
        for e in t.elts:
            got = _self_attr_target(e)
            if got:
                return got
    return None
