"""Pallas kernel-contract checker for ``src/repro/kernels``.

Pallas failure modes are silent in exactly the way a lossless codec cannot
afford: a block shape that does not tile the grid quietly reads garbage
rows, an ``index_map`` with the wrong arity dies only at trace time on the
path that exercises it, and a dtype mismatch between a kernel store and
its declared ``out_shape`` truncates bytes.  Every wrapper here is checked
against a declared contract table.

Rules
-----
kernel-registry     every module-level function in ``kernels/`` that
                    issues a ``pl.pallas_call`` must be registered in
                    ``KERNEL_CONTRACT`` (the declared output dtypes).
kernel-arity        kernel function parameter count must equal
                    ``num_scalar_prefetch + len(in_specs) + len(out_specs)
                    + len(scratch_shapes)`` (refs are passed in that
                    order; a ``grid_spec=`` call's keywords count as the
                    ``pallas_call``'s own).
kernel-index-map    each ``BlockSpec`` index lambda takes exactly one
                    argument per grid dimension and returns one index per
                    block dimension.
kernel-block-shape  a spec indexed by a bare grid variable must tile its
                    array exactly: under ``grid=(E // D,)`` the block dim
                    must be ``D`` (for outputs, the declared shape must
                    equal grid x block).  Composite / constant index
                    expressions (revisit-and-accumulate patterns) are
                    skipped.
kernel-dtype        ``astype`` stores into output refs and declared
                    ``ShapeDtypeStruct`` dtypes must match the contract
                    table.
kernel-interpret    every ``pallas_call`` must thread ``interpret=`` from
                    a wrapper parameter — CPU CI runs interpret mode, so a
                    hardcoded value would silently pin one backend.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from .base import Project, SourceFile, Violation, dotted_name, node_fingerprint

FAMILY = "kernel_contract"
RULES = (
    "kernel-registry",
    "kernel-arity",
    "kernel-index-map",
    "kernel-block-shape",
    "kernel-dtype",
    "kernel-interpret",
)

SCOPE = ("src/repro/kernels/",)

# Declared output dtypes per public kernel wrapper (None = runtime-selected
# or input-following; unchecked).  A new pallas_call wrapper must be
# registered here — that IS the contract declaration.
KERNEL_CONTRACT: Dict[str, Tuple[Optional[str], ...]] = {
    "bytegroup_bf16_2d": ("uint8", "uint8"),
    "ungroup_bf16_2d": ("uint16",),
    "bytegroup_fp32_2d": ("uint8", "uint8", "uint8", "uint8"),
    "ungroup_fp32_2d": ("uint32",),
    "chunk_histogram_2d": ("int32",),
    "xor_elems_2d": (None,),
    "xor_delta_2d": ("uint32", "int32"),
    # The scalar-unit kernels move 32-bit words only (SMEM holds nothing
    # narrower); their wrappers reinterpret the words as uint32 / bytes.
    "bitpack_encode_chunks_multi": ("int32", "int32"),
    "huffdecode_chunks_multi": ("int32", "int32"),
    "plane_consumer": (None,),
}


@dataclass
class Spec:
    """A resolved BlockSpec: shape dim nodes + index lambda, after helper
    parameter substitution."""

    shape: Optional[List[ast.AST]]  # None if not a tuple literal
    index: Optional[ast.Lambda]
    lineno: int


@dataclass
class SpecList:
    specs: List[Spec] = field(default_factory=list)  # distinct spec exprs
    count: Optional[int] = None  # total entries, None if unresolvable


def _module_functions(sf: SourceFile) -> Dict[str, ast.FunctionDef]:
    return {
        n.name: n for n in sf.tree.body if isinstance(n, ast.FunctionDef)
    }


def _substitute(node: ast.AST, subst: Dict[str, ast.AST]) -> ast.AST:
    if isinstance(node, ast.Name) and node.id in subst:
        return subst[node.id]
    return node


def _resolve_blockspec(
    node: ast.AST, helpers: Dict[str, ast.FunctionDef]
) -> Optional[Spec]:
    """A ``pl.BlockSpec(shape, index)`` call or a call to a one-line helper
    that returns one (``_spec(rows)``) -> a :class:`Spec`."""
    if not isinstance(node, ast.Call):
        return None
    name = dotted_name(node.func)
    if name is not None and name.split(".")[-1] == "BlockSpec":
        shape_node = node.args[0] if node.args else None
        index_node = node.args[1] if len(node.args) > 1 else None
        shape = (
            list(shape_node.elts) if isinstance(shape_node, ast.Tuple) else None
        )
        index = index_node if isinstance(index_node, ast.Lambda) else None
        return Spec(shape, index, node.lineno)
    # helper function returning a single BlockSpec
    if isinstance(node.func, ast.Name) and node.func.id in helpers:
        fn = helpers[node.func.id]
        body = [s for s in fn.body if not isinstance(s, ast.Expr)]
        if len(body) == 1 and isinstance(body[0], ast.Return):
            inner = _resolve_blockspec(body[0].value, {})
            if inner is not None:
                params = [a.arg for a in fn.args.args]
                subst = {
                    p: arg for p, arg in zip(params, node.args)
                }
                if inner.shape is not None:
                    inner.shape = [_substitute(d, subst) for d in inner.shape]
                inner.lineno = node.lineno
                return inner
    return None


def _resolve_spec_list(
    node: Optional[ast.AST], helpers: Dict[str, ast.FunctionDef]
) -> SpecList:
    out = SpecList()
    if node is None:
        return out
    spec = _resolve_blockspec(node, helpers)
    if spec is not None:
        out.specs = [spec]
        out.count = 1
        return out
    if isinstance(node, ast.List):
        total = 0
        for elt in node.elts:
            s = _resolve_blockspec(elt, helpers)
            if s is None:
                return SpecList(out.specs, None)
            out.specs.append(s)
            total += 1
        out.count = total
        return out
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mult):
        base, mult = node.left, node.right
        if isinstance(base, ast.Constant):
            base, mult = mult, base
        inner = _resolve_spec_list(base, helpers)
        out.specs = inner.specs
        if (
            inner.count is not None
            and isinstance(mult, ast.Constant)
            and isinstance(mult.value, int)
        ):
            out.count = inner.count * mult.value
        return out
    return out


@dataclass
class OutShape:
    shape: Optional[List[ast.AST]]
    dtype: Optional[str]  # tail name of the dtype expr, e.g. "uint8"
    lineno: int


def _resolve_out_shapes(node: Optional[ast.AST]) -> Tuple[List[OutShape], Optional[int]]:
    if node is None:
        return [], None

    def one(n: ast.AST) -> Optional[OutShape]:
        if isinstance(n, ast.Call):
            name = dotted_name(n.func) or ""
            if name.split(".")[-1] == "ShapeDtypeStruct":
                shape_node = n.args[0] if n.args else None
                dtype_node = n.args[1] if len(n.args) > 1 else None
                shape = (
                    list(shape_node.elts)
                    if isinstance(shape_node, ast.Tuple)
                    else None
                )
                dname = dotted_name(dtype_node) if dtype_node is not None else None
                dtype = dname.split(".")[-1] if dname else None
                return OutShape(shape, dtype, n.lineno)
        return None

    s = one(node)
    if s is not None:
        return [s], 1
    if isinstance(node, ast.List):
        outs = []
        for elt in node.elts:
            s = one(elt)
            if s is None:
                return [], None
            outs.append(s)
        return outs, len(outs)
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mult):
        base, mult = node.left, node.right
        if isinstance(base, ast.Constant):
            base, mult = mult, base
        inner, n_inner = _resolve_out_shapes(base)
        if (
            n_inner is not None
            and isinstance(mult, ast.Constant)
            and isinstance(mult.value, int)
        ):
            return inner, n_inner * mult.value
        return inner, None
    return [], None


def _resolve_kernel_fns(
    arg: ast.AST, sf: SourceFile, wrapper: ast.FunctionDef
) -> List[ast.FunctionDef]:
    mod_fns = _module_functions(sf)
    if isinstance(arg, ast.Name):
        if arg.id in mod_fns:
            return [mod_fns[arg.id]]
        # local variable: kern = A if cond else B (or plain kern = A)
        cands: List[ast.FunctionDef] = []
        for node in ast.walk(wrapper):
            if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == arg.id
                for t in node.targets
            ):
                v = node.value
                exprs = (
                    [v.body, v.orelse] if isinstance(v, ast.IfExp) else [v]
                )
                for e in exprs:
                    if isinstance(e, ast.Name) and e.id in mod_fns:
                        cands.append(mod_fns[e.id])
        return cands
    return []


def _one_hop(name_node: ast.AST, wrapper: ast.FunctionDef) -> ast.AST:
    """Resolve a Name grid dim through a single local assignment."""
    if not isinstance(name_node, ast.Name):
        return name_node
    assigns = [
        n.value
        for n in ast.walk(wrapper)
        if isinstance(n, ast.Assign)
        and any(
            isinstance(t, ast.Name) and t.id == name_node.id
            for t in n.targets
        )
    ]
    if len(assigns) == 1:
        return assigns[0]
    return name_node


def _dim_equal(a: ast.AST, b: ast.AST) -> bool:
    return node_fingerprint(a) == node_fingerprint(b)


def check(project: Project) -> List[Violation]:
    out: List[Violation] = []
    for sf in project.under(*SCOPE):
        out.extend(_check_file(sf))
    return out


def _check_file(sf: SourceFile) -> List[Violation]:
    out: List[Violation] = []
    helpers = _module_functions(sf)

    for wrapper in sf.tree.body:
        if not isinstance(wrapper, ast.FunctionDef):
            continue
        calls = [
            n
            for n in ast.walk(wrapper)
            if isinstance(n, ast.Call)
            and (dotted_name(n.func) or "").split(".")[-1] == "pallas_call"
        ]
        if not calls:
            continue
        contract = KERNEL_CONTRACT.get(wrapper.name)
        if contract is None:
            out.append(
                Violation(
                    "kernel-registry",
                    sf.rel,
                    wrapper.lineno,
                    f"{wrapper.name}() issues a pallas_call but is not "
                    "registered in analysis.kernel_contract."
                    "KERNEL_CONTRACT — declare its output dtypes",
                )
            )
            contract = ()
        for call in calls:
            out.extend(_check_call(sf, wrapper, call, contract, helpers))
    return out


def _check_call(
    sf: SourceFile,
    wrapper: ast.FunctionDef,
    call: ast.Call,
    contract: Tuple[Optional[str], ...],
    helpers: Dict[str, ast.FunctionDef],
) -> List[Violation]:
    out: List[Violation] = []
    kw = {k.arg: k.value for k in call.keywords if k.arg is not None}
    grid_spec = kw.get("grid_spec")
    if isinstance(grid_spec, ast.Call):
        kw.update(
            {k.arg: k.value for k in grid_spec.keywords if k.arg is not None}
        )
    n_prefetch = kw.get("num_scalar_prefetch", ast.Constant(0))
    n_prefetch = (
        n_prefetch.value
        if isinstance(n_prefetch, ast.Constant) and isinstance(n_prefetch.value, int)
        else None
    )
    scratch = kw.get("scratch_shapes")
    n_scratch = (
        0 if scratch is None
        else len(scratch.elts) if isinstance(scratch, (ast.List, ast.Tuple))
        else None
    )
    grid = kw.get("grid")
    grid_dims: Optional[List[ast.AST]] = (
        list(grid.elts) if isinstance(grid, ast.Tuple) else None
    )
    in_specs = _resolve_spec_list(kw.get("in_specs"), helpers)
    out_specs = _resolve_spec_list(kw.get("out_specs"), helpers)
    out_shapes, n_shapes = _resolve_out_shapes(kw.get("out_shape"))

    # --- interpret threading ---------------------------------------------
    interp = kw.get("interpret")
    wrapper_params = {a.arg for a in (
        wrapper.args.posonlyargs + wrapper.args.args + wrapper.args.kwonlyargs
    )}
    if interp is None:
        out.append(
            Violation(
                "kernel-interpret",
                sf.rel,
                call.lineno,
                "pallas_call without interpret= — thread the wrapper's "
                "interpret parameter (CPU CI runs interpret mode)",
            )
        )
    elif isinstance(interp, ast.Constant) or not (
        isinstance(interp, ast.Name) and interp.id in wrapper_params
    ):
        out.append(
            Violation(
                "kernel-interpret",
                sf.rel,
                call.lineno,
                "interpret= must come from a wrapper parameter, not a "
                "hardcoded value — CPU CI and TPU runs share this code",
            )
        )

    # --- kernel arity -----------------------------------------------------
    n_out = out_specs.count if out_specs.count is not None else n_shapes
    if (
        in_specs.count is not None
        and n_out is not None
        and n_prefetch is not None
        and n_scratch is not None
        and call.args
    ):
        expected = n_prefetch + in_specs.count + n_out + n_scratch
        for fn in _resolve_kernel_fns(call.args[0], sf, wrapper):
            n_params = len(
                fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs
            )
            if n_params != expected:
                out.append(
                    Violation(
                        "kernel-arity",
                        sf.rel,
                        call.lineno,
                        f"kernel {fn.name}() takes {n_params} refs but "
                        f"this pallas_call passes {n_prefetch} scalar "
                        f"prefetch + {in_specs.count} inputs + {n_out} "
                        f"outputs + {n_scratch} scratch",
                    )
                )

    # --- declared output count / dtypes vs contract ------------------------
    if contract:
        if n_shapes is not None and n_shapes != len(contract):
            out.append(
                Violation(
                    "kernel-dtype",
                    sf.rel,
                    call.lineno,
                    f"{wrapper.name}() declares {n_shapes} outputs but "
                    f"KERNEL_CONTRACT registers {len(contract)}",
                )
            )
        elif n_shapes is not None:
            for i, (shape, want) in enumerate(zip(out_shapes, contract)):
                if want is not None and shape.dtype is not None and shape.dtype != want:
                    out.append(
                        Violation(
                            "kernel-dtype",
                            sf.rel,
                            shape.lineno,
                            f"{wrapper.name}() output {i} declared as "
                            f"{shape.dtype} but KERNEL_CONTRACT says {want}",
                        )
                    )

    # --- astype stores inside the kernel vs contract ------------------------
    if contract and in_specs.count is not None and call.args:
        for fn in _resolve_kernel_fns(call.args[0], sf, wrapper):
            params = [
                a.arg
                for a in fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs
            ]
            first_out = (n_prefetch or 0) + in_specs.count
            out_params = params[first_out : first_out + len(contract)]
            for node in ast.walk(fn):
                if not (
                    isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Subscript)
                    and isinstance(node.targets[0].value, ast.Name)
                    and node.targets[0].value.id in out_params
                ):
                    continue
                idx = out_params.index(node.targets[0].value.id)
                want = contract[idx] if idx < len(contract) else None
                v = node.value
                if (
                    want is not None
                    and isinstance(v, ast.Call)
                    and isinstance(v.func, ast.Attribute)
                    and v.func.attr == "astype"
                    and v.args
                ):
                    dname = dotted_name(v.args[0])
                    got = dname.split(".")[-1] if dname else None
                    if got is not None and got != want:
                        out.append(
                            Violation(
                                "kernel-dtype",
                                sf.rel,
                                node.lineno,
                                f"kernel {fn.name}() stores "
                                f"{got} into output {idx} but "
                                f"KERNEL_CONTRACT declares {want}",
                            )
                        )

    # --- index_map arity + block coverage ----------------------------------
    grid_rank = len(grid_dims) if grid_dims is not None else None
    all_specs = [(s, None) for s in in_specs.specs] + [
        (s, i) for i, s in enumerate(out_specs.specs)
    ]
    for spec, out_idx in all_specs:
        if spec.index is None:
            continue
        lam_params = [a.arg for a in spec.index.args.args]
        if grid_rank is not None and len(lam_params) != grid_rank:
            out.append(
                Violation(
                    "kernel-index-map",
                    sf.rel,
                    spec.lineno,
                    f"index_map takes {len(lam_params)} args but the grid "
                    f"has rank {grid_rank}",
                )
            )
            continue
        body = spec.index.body
        idx_elts = list(body.elts) if isinstance(body, ast.Tuple) else None
        if (
            idx_elts is not None
            and spec.shape is not None
            and len(idx_elts) != len(spec.shape)
        ):
            out.append(
                Violation(
                    "kernel-index-map",
                    sf.rel,
                    spec.lineno,
                    f"index_map returns {len(idx_elts)} indices but the "
                    f"block shape has rank {len(spec.shape)}",
                )
            )
            continue
        if idx_elts is None or spec.shape is None or grid_dims is None:
            continue
        for k, idx in enumerate(idx_elts):
            # only bare grid variables are statically checkable; composite
            # expressions (i * blocks + j) and constants (revisit blocks)
            # are skipped by design
            if not (isinstance(idx, ast.Name) and idx.id in lam_params):
                continue
            d = lam_params.index(idx.id)
            if d >= len(grid_dims):
                continue
            block_dim = spec.shape[k]
            grid_expr = grid_dims[d]
            resolved = _one_hop(grid_expr, wrapper)
            divisor = (
                resolved.right
                if isinstance(resolved, ast.BinOp)
                and isinstance(resolved.op, ast.FloorDiv)
                else None
            )
            if out_idx is not None:
                # outputs: declared shape must equal grid x block
                shape = (
                    out_shapes[out_idx].shape
                    if out_idx < len(out_shapes)
                    else None
                )
                if shape is None or k >= len(shape):
                    continue
                sdim = shape[k]
                prod_ok = (
                    _dim_equal(
                        sdim,
                        ast.BinOp(grid_expr, ast.Mult(), block_dim),
                    )
                    or _dim_equal(
                        sdim,
                        ast.BinOp(block_dim, ast.Mult(), grid_expr),
                    )
                )
                one_ok = (
                    isinstance(block_dim, ast.Constant)
                    and block_dim.value == 1
                    and _dim_equal(sdim, grid_expr)
                )
                div_ok = (
                    divisor is not None
                    and isinstance(resolved, ast.BinOp)
                    and _dim_equal(block_dim, divisor)
                    and _dim_equal(sdim, resolved.left)
                )
                if not (prod_ok or one_ok or div_ok):
                    out.append(
                        Violation(
                            "kernel-block-shape",
                            sf.rel,
                            spec.lineno,
                            f"output {out_idx} dim {k}: declared shape "
                            "must equal grid x block for a bare-index "
                            "spec — partial blocks would read/write "
                            "out of range",
                        )
                    )
            else:
                # inputs: catch the cross-constant copy-paste class
                if (
                    divisor is not None
                    and isinstance(divisor, ast.Name)
                    and isinstance(block_dim, ast.Name)
                    and block_dim.id != divisor.id
                ):
                    out.append(
                        Violation(
                            "kernel-block-shape",
                            sf.rel,
                            spec.lineno,
                            f"input block dim {k} is {block_dim.id} but "
                            f"the grid steps by {divisor.id} — the block "
                            "does not tile the grid",
                        )
                    )
    return out
