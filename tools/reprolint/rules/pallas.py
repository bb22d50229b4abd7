"""pallas-contract: structural checks on every ``pl.pallas_call``.

Three contracts the TPU kernels rely on:

1. **Signature arity** — the kernel function must take exactly
   ``num_scalar_prefetch + len(in_specs) + n_outputs +
   len(scratch_shapes)`` positional refs, in that order.  A missing
   scalar-prefetch ref shifts every operand one position and the
   kernel reads garbage (or crashes at trace time with a misleading
   shape error).
2. **Index-map purity** — BlockSpec index maps must be pure index
   arithmetic over ``(grid indices..., scalar-prefetch refs...)``:
   no attribute access, subscripting or calls, and no names captured
   from the enclosing scope.  Captured loop variables are evaluated at
   trace time with their *final* values; the sanctioned idiom binds
   them via lambda defaults (``lambda b, h, j, g=g: ...``).  Arity must
   equal grid rank + num_scalar_prefetch.
3. **Dispatch layering** — code under ``src/`` outside the kernels
   package reaches kernels only through ``repro.kernels.ops`` /
   ``repro.kernels.dispatch`` (or ``.ref``), never by importing a
   kernel implementation module directly: the dispatch layer owns the
   pallas/XLA routing, interpret-mode and compiler-params decisions.

Anything not statically resolvable (dynamic spec lists, kernels built
elsewhere) is skipped, not flagged.
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

from ..core import Finding, Module, RunContext, call_name, dotted_name, \
    keyword_arg

KERNEL_IMPL_MODULES = frozenset({
    "flash_attention", "ragged_prefill_attention",
    "batched_decode_attention", "decode_attention", "chunked_gla",
    "rmsnorm", "latent_decode_attention"})
_ALLOWED = frozenset({"ops", "dispatch", "ref"})


def _local_assignments(fn: Optional[ast.AST],
                       tree: ast.AST) -> Dict[str, ast.AST]:
    """name -> value expr for simple assignments in the enclosing
    function (falling back to module level)."""
    out: Dict[str, ast.AST] = {}
    body = getattr(fn, "body", None) or tree.body
    stack = list(body)
    while stack:
        stmt = stack.pop(0)
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name):
            out[stmt.targets[0].id] = stmt.value
        for attr in ("body", "orelse", "finalbody"):
            stack.extend(getattr(stmt, attr, []))
        for h in getattr(stmt, "handlers", []):
            stack.extend(h.body)
    return out


class PallasRule:
    name = "pallas-contract"
    description = ("pl.pallas_call structural contracts: kernel "
                   "signature arity vs grid spec (incl. scalar "
                   "prefetch), index-map purity/arity, and kernels "
                   "reached only via the dispatch layer")

    def check(self, mod: Module, ctx: RunContext) -> Iterable[Finding]:
        findings: List[Finding] = []
        self._check_layering(mod, findings)
        module_fns: Dict[str, ast.AST] = {}
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                module_fns.setdefault(node.name, node)
        # find pallas_call sites together with their enclosing function
        def scan(node: ast.AST, fn: Optional[ast.AST]) -> None:
            for child in ast.iter_child_nodes(node):
                child_fn = fn
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    child_fn = child
                if isinstance(child, ast.Call):
                    name = call_name(child)
                    if name is not None and (
                            name == "pallas_call"
                            or name.endswith(".pallas_call")):
                        self._check_site(mod, child, child_fn, module_fns,
                                         findings)
                scan(child, child_fn)

        scan(mod.tree, None)
        return findings

    # -- dispatch layering --------------------------------------------

    def _check_layering(self, mod: Module, findings: List[Finding]) -> None:
        parts = Path(mod.path).parts
        if "repro" not in parts or "kernels" in parts:
            return  # only library code outside the kernels package
        for node in ast.walk(mod.tree):
            bad: Optional[str] = None
            if isinstance(node, ast.Import):
                for alias in node.names:
                    segs = alias.name.split(".")
                    if len(segs) >= 3 and segs[0] == "repro" \
                            and segs[1] == "kernels" \
                            and segs[2] in KERNEL_IMPL_MODULES:
                        bad = alias.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                segs = node.module.split(".")
                if segs[:2] == ["repro", "kernels"]:
                    if len(segs) >= 3 and segs[2] in KERNEL_IMPL_MODULES:
                        bad = node.module
                    elif len(segs) == 2:
                        for alias in node.names:
                            if alias.name in KERNEL_IMPL_MODULES:
                                bad = f"repro.kernels.{alias.name}"
            if bad is not None:
                findings.append(Finding(
                    self.name, mod.path, node.lineno, "error",
                    f"direct import of kernel module '{bad}': library "
                    "code reaches kernels via repro.kernels.ops / "
                    "repro.kernels.dispatch only (the dispatch layer "
                    "owns pallas/XLA routing)"))

    # -- per-site structural checks -----------------------------------

    def _check_site(self, mod: Module, call: ast.Call,
                    fn: Optional[ast.AST], module_fns: Dict[str, ast.AST],
                    findings: List[Finding]) -> None:
        env = _local_assignments(fn, mod.tree)

        def resolve(node: Optional[ast.AST]) -> Optional[ast.AST]:
            seen = 0
            while isinstance(node, ast.Name) and node.id in env \
                    and seen < 5:
                node = env[node.id]
                seen += 1
            return node

        # spec source: grid_spec=... object, or kwargs on the call
        nsp = 0
        spec_src: ast.Call = call
        gs = resolve(keyword_arg(call, "grid_spec"))
        if gs is not None:
            if not isinstance(gs, ast.Call):
                return
            gs_name = call_name(gs) or ""
            if gs_name.endswith("PrefetchScalarGridSpec"):
                nsp_node = resolve(keyword_arg(gs, "num_scalar_prefetch"))
                if isinstance(nsp_node, ast.Constant) and isinstance(
                        nsp_node.value, int):
                    nsp = nsp_node.value
                elif nsp_node is not None:
                    return
            elif not gs_name.endswith("GridSpec"):
                return
            spec_src = gs

        grid = resolve(keyword_arg(spec_src, "grid"))
        grid_rank: Optional[int] = None
        if isinstance(grid, ast.Tuple):
            grid_rank = len(grid.elts)
        elif isinstance(grid, ast.Constant) and isinstance(grid.value, int):
            grid_rank = 1

        lambdas: List[ast.Lambda] = []

        def spec_count(node: Optional[ast.AST]) -> Optional[int]:
            node = resolve(node)
            if node is None:
                return None
            if isinstance(node, (ast.List, ast.Tuple)):
                for elt in node.elts:
                    self._collect_index_map(resolve(elt), lambdas)
                return len(node.elts)
            if isinstance(node, ast.Call):
                self._collect_index_map(node, lambdas)
                return 1
            if isinstance(node, ast.Constant) and node.value is None:
                return 1
            return None

        n_in = spec_count(keyword_arg(spec_src, "in_specs"))
        n_out = spec_count(keyword_arg(spec_src, "out_specs"))
        if n_out is None:
            out_shape = resolve(keyword_arg(call, "out_shape"))
            if isinstance(out_shape, (ast.List, ast.Tuple)):
                n_out = len(out_shape.elts)
            elif out_shape is not None:
                n_out = 1
        scratch = resolve(keyword_arg(spec_src, "scratch_shapes"))
        if scratch is None:
            n_scratch: Optional[int] = 0
        elif isinstance(scratch, (ast.List, ast.Tuple)):
            n_scratch = len(scratch.elts)
        else:
            n_scratch = None

        # kernel signature arity
        kernel = resolve(call.args[0]) if call.args else None
        extra_positional = 0
        if isinstance(kernel, ast.Call) and (
                call_name(kernel) or "").endswith("partial"):
            extra_positional = max(0, len(kernel.args) - 1)
            kernel = resolve(kernel.args[0]) if kernel.args else None
        kernel_def = None
        kernel_name = None
        if isinstance(kernel, ast.Name):
            kernel_name = kernel.id
            kernel_def = module_fns.get(kernel.id)
        if kernel_def is not None and None not in (n_in, n_out, n_scratch):
            args = kernel_def.args
            n_params = len(args.posonlyargs) + len(args.args) \
                - extra_positional
            n_defaults = len(args.defaults)
            expected = nsp + n_in + n_out + n_scratch
            if not (n_params - n_defaults <= expected <= n_params):
                findings.append(Finding(
                    self.name, mod.path, call.lineno, "error",
                    f"kernel '{kernel_name}' takes {n_params} positional "
                    f"refs but this pallas_call supplies {expected} "
                    f"(scalar_prefetch={nsp} + in_specs={n_in} + "
                    f"outputs={n_out} + scratch={n_scratch}); a "
                    "mismatched scalar-prefetch count shifts every "
                    "operand ref"))

        # index-map arity + purity
        for lam in lambdas:
            self._check_index_map(mod, lam, grid_rank, nsp, findings)

    def _collect_index_map(self, node: Optional[ast.AST],
                           lambdas: List[ast.Lambda]) -> None:
        """Pull the index_map lambda out of a BlockSpec(...) call."""
        if not isinstance(node, ast.Call):
            return
        name = call_name(node) or ""
        if not name.endswith("BlockSpec"):
            return
        cand = None
        if len(node.args) >= 2:
            cand = node.args[1]
        kw = keyword_arg(node, "index_map")
        if kw is not None:
            cand = kw
        if isinstance(cand, ast.Lambda):
            lambdas.append(cand)

    def _check_index_map(self, mod: Module, lam: ast.Lambda,
                         grid_rank: Optional[int], nsp: int,
                         findings: List[Finding]) -> None:
        args = lam.args
        params = [a.arg for a in args.posonlyargs + args.args]
        n_required = len(params) - len(args.defaults)
        if grid_rank is not None and n_required != grid_rank + nsp:
            findings.append(Finding(
                self.name, mod.path, lam.lineno, "error",
                f"index map takes {n_required} required args but the "
                f"grid supplies {grid_rank} indices + {nsp} scalar-"
                "prefetch refs; bind captured values via lambda "
                "defaults, not extra parameters"))
        allowed = set(params)
        for sub in ast.walk(lam.body):
            if isinstance(sub, (ast.Attribute, ast.Subscript, ast.Call)):
                findings.append(Finding(
                    self.name, mod.path, sub.lineno, "error",
                    "index map must be pure index arithmetic: no "
                    "attribute access, subscripting or calls (hoist "
                    "the value and bind it via a lambda default)"))
                break
            if isinstance(sub, ast.Name) and sub.id not in allowed:
                findings.append(Finding(
                    self.name, mod.path, sub.lineno, "error",
                    f"index map captures '{sub.id}' from the enclosing "
                    "scope; trace-time capture sees the final loop "
                    "value — bind it via a default "
                    f"(lambda ..., {sub.id}={sub.id}: ...)"))
                break
