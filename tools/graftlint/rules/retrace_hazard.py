"""retrace-hazard: jit/AOT call sites fed Python-varying scalars/shapes.

A retrace mid-drain is dead air — trace, lowering and compile in front
of the batch that caused it (ops/aot.py) — so every device entry point
in this codebase is supposed to see only a small closed set of argument
shapes: batch sizes snapped to warmed
buckets (``ops/aot.register_shape_bucket`` + ``pipeline/policy.snap_batch``)
or padded to pow2 (``(n - 1).bit_length()``), and Python scalars
declared static (``static_argnums``/``static_argnames``).

The rule finds jitted callables — ``@jax.jit`` decorations (bare or via
``partial``), ``name = jax.jit(f)`` / ``name = aot_jit(...)`` bindings —
and flags their call sites when:

- a non-static argument is a Python-varying scalar (``len(...)``, or a
  local assigned from ``len(...)``): every distinct value under
  concretization keys a fresh trace;
- a non-static argument builds an array from a variable-length sequence
  (``jnp.asarray(xs)``, ``np.stack(xs)`` where ``xs`` is a parameter or
  a comprehension) and the enclosing function shows no evidence of
  shape discipline — no call to ``snap_batch``/``shape_buckets``/
  ``register_shape_bucket``, no pad/bucket helper, no
  ``.bit_length()`` pow2 rounding.

Round 13 adds the **donated-buffer check**: when a callable is jitted
with ``donate_argnums`` (directly or through ``aot_jit(jax.jit(...))``),
the arrays passed in donated positions are invalidated in place by XLA
— reading them after the call returns garbage SILENTLY (no exception;
the resident-sweep bug class).  The rule flags any later load of a name
that was passed in a donated position, unless the name was rebound first
(typically to the call's own result, the correct discipline).
"""

from __future__ import annotations

import ast

from ..core import Finding, Module, Project
from .common import call_name, dotted, module_functions, walk_excluding_nested

_JIT_FACTORIES = {"jit", "aot_jit"}
_ARRAY_BUILDERS = {"asarray", "array", "stack", "concatenate", "frombuffer", "fromiter"}
_SNAP_EVIDENCE = {"snap_batch", "shape_buckets", "register_shape_bucket", "bit_length"}
_SNAP_NAME_HINTS = ("pad", "bucket", "snap")


def _jit_call_statics(
    call: ast.Call,
) -> tuple[set[int], set[str], set[int]] | None:
    """If ``call`` constructs a jitted callable: its static argnums/names
    plus its DONATED argnums.  ``aot_jit(jax.jit(f, donate_argnums=...),
    name)`` resolves through the wrapper to the inner jit's donation."""
    cname = call_name(call)
    if cname in _JIT_FACTORIES:
        nums, names, donated = _statics_from(call)
        if call.args and isinstance(call.args[0], ast.Call):
            inner = _jit_call_statics(call.args[0])
            if inner is not None:
                nums |= inner[0]
                names |= inner[1]
                donated |= inner[2]
        return nums, names, donated
    if cname == "partial":
        # functools.partial(jax.jit, static_argnames=...)
        if call.args and isinstance(call.args[0], (ast.Name, ast.Attribute)):
            inner = dotted(call.args[0]) or ""
            if inner.split(".")[-1] in _JIT_FACTORIES:
                return _statics_from(call)
    return None


def _statics_from(call: ast.Call) -> tuple[set[int], set[str], set[int]]:
    nums: set[int] = set()
    names: set[str] = set()
    donated: set[int] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            for n in _const_ints(kw.value):
                nums.add(n)
        elif kw.arg == "static_argnames":
            for s in _const_strs(kw.value):
                names.add(s)
        elif kw.arg == "donate_argnums":
            for n in _const_ints(kw.value):
                donated.add(n)
    return nums, names, donated


def _const_ints(node: ast.AST) -> list[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts if isinstance(e, ast.Constant) and isinstance(e.value, int)]
    return []


def _const_strs(node: ast.AST) -> list[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts if isinstance(e, ast.Constant) and isinstance(e.value, str)]
    return []


class RetraceHazardRule:
    name = "retrace-hazard"
    description = "jitted call sites passing unsnapped Python-varying scalars/shapes"

    def check(self, project: Project) -> list[Finding]:
        findings: list[Finding] = []
        for module in project.modules:
            findings.extend(self._check_module(module))
        return findings

    def _check_module(self, module: Module) -> list[Finding]:
        # jitted callables visible by name in this module
        jitted: dict[str, tuple[set[int], set[str], set[int]]] = {}
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    statics = None
                    if isinstance(dec, ast.Call):
                        statics = _jit_call_statics(dec)
                    elif (dotted(dec) or "").split(".")[-1] in _JIT_FACTORIES:
                        statics = (set(), set(), set())
                    if statics is not None:
                        jitted[node.name] = statics
            elif isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                statics = _jit_call_statics(node.value)
                if statics is not None:
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            jitted[t.id] = statics

        if not jitted:
            return []

        findings: list[Finding] = []
        for fi in module_functions(module):
            nodes = walk_excluding_nested(fi.node)
            snapped = self._has_snap_evidence(nodes)
            len_locals = self._len_locals(nodes)
            params = {
                a.arg
                for a in fi.node.args.args
                + fi.node.args.posonlyargs
                + fi.node.args.kwonlyargs
            }
            for node in nodes:
                if not isinstance(node, ast.Call):
                    continue
                cname = call_name(node)
                if cname not in jitted:
                    continue
                nums, names, donated = jitted[cname]
                for pos, arg in enumerate(node.args):
                    if pos in nums:
                        continue
                    findings.extend(
                        self._check_arg(arg, cname, module, fi, snapped, len_locals, params)
                    )
                for kw in node.keywords:
                    if kw.arg in names:
                        continue
                    findings.extend(
                        self._check_arg(kw.value, cname, module, fi, snapped, len_locals, params)
                    )
                if donated:
                    findings.extend(
                        self._check_use_after_donate(
                            node, donated, cname, module, fi, nodes
                        )
                    )
        return findings

    # ------------------------------------------------- donated buffers

    def _check_use_after_donate(
        self, call: ast.Call, donated: set[int], cname: str, module, fi, nodes
    ) -> list[Finding]:
        """Flag loads of names passed in donated positions after the call
        — unless the name was rebound first (normally to the call's own
        result).  Use-after-donate reads an XLA-invalidated buffer and
        returns garbage with no exception."""
        donated_names = {
            arg.id
            for pos, arg in enumerate(call.args)
            if pos in donated and isinstance(arg, ast.Name)
        }
        if not donated_names:
            return []
        # a multi-line call puts its own arguments past call.lineno —
        # "after the call" means after its LAST line
        call_end = getattr(call, "end_lineno", None) or call.lineno
        # a rebinding shields every later use of that name: record the
        # first assignment line per name at/after the call line (the
        # `lo, hi = k(lo, hi, ...)` rebind shares the call's own line)
        rebound_at: dict[str, int] = {}
        for node in nodes:
            targets = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            elif isinstance(node, ast.For):
                targets = [node.target]
            for t in targets:
                for sub in ast.walk(t):
                    if isinstance(sub, ast.Name) and sub.id in donated_names:
                        if node.lineno >= call.lineno:
                            rebound_at[sub.id] = min(
                                rebound_at.get(sub.id, node.lineno), node.lineno
                            )
        findings = []
        flagged: set[str] = set()
        for node in nodes:
            if not (
                isinstance(node, ast.Name)
                and isinstance(node.ctx, ast.Load)
                and node.id in donated_names
                and node.id not in flagged
                and node.lineno > call_end
            ):
                continue
            shield = rebound_at.get(node.id)
            if shield is not None and shield <= node.lineno:
                continue
            flagged.add(node.id)
            findings.append(
                Finding(
                    rule=self.name,
                    path=module.rel,
                    line=node.lineno,
                    symbol=fi.qualname,
                    message=(
                        f"{node.id!r} was passed in a donated position "
                        f"(donate_argnums) of jitted {cname}() and is used "
                        "after the call: XLA invalidated that buffer in "
                        "place, so this read returns garbage silently — "
                        "rebind the name to the call's result instead"
                    ),
                )
            )
        return findings

    def _check_arg(self, arg, cname, module, fi, snapped, len_locals, params):
        # Python-varying scalar in a traced position
        if (isinstance(arg, ast.Call) and call_name(arg) == "len") or (
            isinstance(arg, ast.Name) and arg.id in len_locals
        ):
            return [
                Finding(
                    rule=self.name,
                    path=module.rel,
                    line=arg.lineno,
                    symbol=fi.qualname,
                    message=(
                        f"jitted {cname}() receives a Python-varying scalar "
                        "(len-derived) in a traced position: every distinct "
                        "value keys a fresh trace/compile — declare it via "
                        "static_argnums/static_argnames or bucket it"
                    ),
                )
            ]
        # array built from a variable-length sequence, no shape discipline
        if (
            not snapped
            and isinstance(arg, ast.Call)
            and call_name(arg) in _ARRAY_BUILDERS
            and arg.args
        ):
            operand = arg.args[0]
            varying = (
                isinstance(operand, ast.Name) and operand.id in params
            ) or isinstance(operand, (ast.ListComp, ast.GeneratorExp))
            if varying:
                return [
                    Finding(
                        rule=self.name,
                        path=module.rel,
                        line=arg.lineno,
                        symbol=fi.qualname,
                        message=(
                            f"jitted {cname}() receives an array built from a "
                            "variable-length sequence with no snap/pad in "
                            "scope: unwarmed batch shapes trace+compile "
                            "mid-drain — snap to ops/aot.register_shape_bucket "
                            "buckets or pad to pow2"
                        ),
                    )
                ]
        return []

    @staticmethod
    def _has_snap_evidence(nodes) -> bool:
        for node in nodes:
            if isinstance(node, ast.Call):
                cname = call_name(node)
                if cname in _SNAP_EVIDENCE:
                    return True
                if cname and any(h in cname.lower() for h in _SNAP_NAME_HINTS):
                    return True
        return False

    @staticmethod
    def _len_locals(nodes) -> set[str]:
        out: set[str] = set()
        for node in nodes:
            if isinstance(node, ast.Assign):
                has_len = any(
                    isinstance(sub, ast.Call) and call_name(sub) == "len"
                    for sub in ast.walk(node.value)
                )
                if has_len:
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            out.add(t.id)
        return out
