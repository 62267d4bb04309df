"""Pass 4 — Pallas call/BlockSpec consistency.

Scope: any analyzed file whose AST contains a `pallas_call` call
(today: ops/level_pallas.py, ops/aes_pallas.py, ops/keccak_pallas.py).

These are the executable subset of the Mosaic shape rules the r4/r5
chip sessions paid for in failed compiles — checked statically so a
mismatch fails `make analyze` instead of a chip run:

  PL001  BlockSpec whose index_map returns a tuple of different length
         than its block shape (rank mismatch: every block dim needs an
         index coordinate).
  PL002  BlockSpec index_map arity != len(grid) for pallas_calls whose
         grid is a static tuple (the index_map is called with one
         argument per grid axis).
  PL003  out_shape / out_specs element-count mismatch when both are
         literal tuples/lists in the same pallas_call.
  PL004  literal (constant-foldable) block-shape sublane dim — the
         second-to-last — that is neither 1 nor a multiple of 8:
         Mosaic only accepts such a tile when it equals the full array
         dim, which this analyzer cannot prove; suppress with the
         justification naming the array dim it equals.

Symbolic shapes (names the folder cannot resolve) are skipped — the
pass is deliberately zero-false-positive on arithmetic it cannot see.
"""

import ast

from .core import Finding, call_name

PASS_NAME = "pallasck"

RULES = {
    "PL001": "BlockSpec rank mismatch (shape vs index_map return)",
    "PL002": "BlockSpec index_map arity != grid rank",
    "PL003": "out_shape / out_specs count mismatch",
    "PL004": "literal sublane block dim neither 1 nor a multiple of 8",
}


def in_scope(rel: str, tree: ast.Module = None) -> bool:
    if tree is None:
        return False
    return any(isinstance(n, ast.Call)
               and call_name(n).endswith("pallas_call")
               for n in ast.walk(tree))


def _is_blockspec(node) -> bool:
    return (isinstance(node, ast.Call)
            and call_name(node).endswith("BlockSpec"))


def _lambda_return_len(node):
    if isinstance(node, ast.Lambda) and isinstance(node.body, ast.Tuple):
        return len(node.body.elts)
    return None


def _lambda_arity(node):
    if isinstance(node, ast.Lambda):
        a = node.args
        return len(a.posonlyargs) + len(a.args)
    return None


def _kwarg(call: ast.Call, name: str):
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _seq_len(node):
    if isinstance(node, (ast.Tuple, ast.List)):
        return len(node.elts)
    return None


def _local_consts(fn, info) -> dict:
    """Names assigned exactly once in `fn` to a foldable int."""
    counts: dict = {}
    for node in ast.walk(fn):
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.For)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for t in targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Name):
                        counts[n.id] = counts.get(n.id, 0) + 1
    env: dict = {}
    changed = True
    while changed:
        changed = False
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and counts.get(node.targets[0].id) == 1 \
                    and node.targets[0].id not in env:
                val = info.fold(node.value, env)
                if val is not None:
                    env[node.targets[0].id] = val
                    changed = True
    return env


def _check_blockspec(spec, info, env, grid_len, findings):
    if not spec.args:
        return
    shape = spec.args[0]
    index_map = spec.args[1] if len(spec.args) > 1 else None
    shape_len = _seq_len(shape)
    ret_len = _lambda_return_len(index_map)
    if shape_len is not None and ret_len is not None \
            and shape_len != ret_len:
        findings.append(Finding(
            "PL001", info.rel, spec.lineno,
            f"BlockSpec block shape has {shape_len} dims but its "
            f"index_map returns {ret_len} coordinates"))
    arity = _lambda_arity(index_map)
    if grid_len is not None and arity is not None and arity != grid_len:
        findings.append(Finding(
            "PL002", info.rel, spec.lineno,
            f"index_map takes {arity} grid indices but the grid has "
            f"{grid_len} axes"))
    if shape_len is not None and shape_len >= 2:
        sub = info.fold(shape.elts[-2], env)
        if sub is not None and sub != 1 and sub % 8 != 0:
            findings.append(Finding(
                "PL004", info.rel, spec.lineno,
                f"sublane block dim {sub} is neither 1 nor a multiple "
                "of 8 — Mosaic accepts it only when it equals the "
                "full array dim (suppress with that justification)"))


def check(info) -> list:
    findings: list = []
    # Map every BlockSpec to its enclosing function (for local-constant
    # folding) and, where visible, its pallas_call's static grid rank.
    fn_of: dict = {}

    def map_fns(node, fn):
        for child in ast.iter_child_nodes(node):
            child_fn = child if isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef)) else fn
            fn_of[child] = child_fn
            map_fns(child, child_fn)

    map_fns(info.tree, None)
    env_cache: dict = {}

    def env_for(node):
        fn = fn_of.get(node)
        if fn is None:
            return {}
        if id(fn) not in env_cache:
            env_cache[id(fn)] = _local_consts(fn, info)
        return env_cache[id(fn)]

    grid_of_spec: dict = {}
    for node in ast.walk(info.tree):
        if not (isinstance(node, ast.Call)
                and call_name(node).endswith("pallas_call")):
            continue
        grid = _kwarg(node, "grid")
        grid_len = _seq_len(grid)
        out_shape = _kwarg(node, "out_shape")
        out_specs = _kwarg(node, "out_specs")
        n_shape = _seq_len(out_shape)
        n_specs = _seq_len(out_specs)
        if n_shape is not None and n_specs is not None \
                and n_shape != n_specs:
            findings.append(Finding(
                "PL003", info.rel, node.lineno,
                f"out_shape has {n_shape} entries but out_specs has "
                f"{n_specs}"))
        if grid_len is not None:
            for kw in ("in_specs", "out_specs"):
                seq = _kwarg(node, kw)
                elts = (seq.elts if isinstance(seq, (ast.Tuple, ast.List))
                        else [seq] if _is_blockspec(seq) else [])
                for spec in elts:
                    if _is_blockspec(spec):
                        grid_of_spec[id(spec)] = grid_len

    for node in ast.walk(info.tree):
        if _is_blockspec(node):
            _check_blockspec(node, info, env_for(node),
                             grid_of_spec.get(id(node)), findings)
    seen = set()
    out = []
    for f in findings:
        if f.key() in seen:
            continue
        seen.add(f.key())
        out.append(f)
    return out
