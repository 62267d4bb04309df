#!/usr/bin/env python3
"""Minimal dependency-free lint gate (pyflakes is not in this image).

Checks, over mastic_tpu/, tests/, tools/ and the repo-root scripts:

1. every file parses (syntax);
2. unused imports (name imported but never referenced);
3. public functions/methods in the scalar protocol layer carry full
   type annotations (the local stand-in for the reference's strict
   mypy gate, /root/reference/.github/workflows/test.yml:36-44 —
   mypy.ini is shipped for environments that have mypy);
4. no `print(` in library code (drivers return data; observability is
   the metrics dict);
5. every annotation in the ANNOTATED layer resolves at runtime
   (typing.get_type_hints over each public function, class and
   method — undefined or misspelled type names fail here even
   without mypy; mypy itself remains uninstallable in this image);
6. intra-repo calls to module-level functions match the callee's
   signature — positional arity, keyword names, required args (the
   executable subset of mypy's call checking; conservative: bare
   names only, decorated defs / reassigned names / star-spreads
   skipped);
7. every MASTIC_* env lever referenced in mastic_tpu/ or bench.py is
   documented in USAGE.md, and every kernel/backend lever (read in
   mastic_tpu/ops/ or mastic_tpu/backend/) is exercised by
   tools/chip_session.sh — either by env name or by its bench.py
   flag form (--foo-bar for MASTIC_FOO_BAR).  Prevents the r5 class
   of "kernel exists but no session script exercises it";
8. the ANNOTATED list below stays in sync with mypy.ini's strict
   module set (the modules under `strict = True` with no relaxing
   override).  mypy cannot run in this image, so the two lists had
   started to drift silently; this check makes the drift a lint
   failure in both directions;
9. every metric name the telemetry registry declares
   (mastic_tpu/obs/registry.py DECLARED) appears in USAGE.md's
   "Observability" metric table — an operator reading /metrics must
   be able to look every series up, so a new metric cannot ship
   undocumented (the metric twin of check 7's lever rule);
10. USAGE.md's "Static analysis" rule table lists EXACTLY the rule
   IDs in tools.analysis._RULE_TABLE — both directions: a shipped
   rule missing from the table is undocumented, a table row whose
   rule no longer exists is stale (the analyzer twin of check 9;
   the table had only stayed in sync by luck before);
11. the refusal/shed reason-code contract: every reason literal the
   code counts into `ServiceCounters.shed_reasons` (via bump_shed /
   count_front_shed / FrontDoor.shed / shed_external) or into
   `mastic_tls_refusals_total` (the TLS_* constants in
   net/transport.py) appears in USAGE.md's reason tables, and every
   table row names a reason the code still counts — an operator
   grepping a reason off /statusz must always land on its row
   (`tls-handshake-failed` and `incomplete-body` had already drifted
   undocumented before this check existed).

Exit status 0 iff clean.  Run via `make lint` / `make ci`.
"""

import ast
import configparser
import pathlib
import re
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent

# Scalar-layer modules held to the annotation standard (the batched
# JAX layer's shapes/dtypes are documented in docstrings instead).
# Check 8 keeps this list equal to mypy.ini's strict set.
ANNOTATED = [
    "mastic_tpu/common.py", "mastic_tpu/dst.py", "mastic_tpu/field.py",
    "mastic_tpu/xof.py", "mastic_tpu/aes.py", "mastic_tpu/keccak.py",
    "mastic_tpu/vidpf.py", "mastic_tpu/mastic.py", "mastic_tpu/vdaf.py",
    "mastic_tpu/oracle.py", "mastic_tpu/flp/flp.py",
    "mastic_tpu/flp/circuits.py", "mastic_tpu/testvec_codec.py",
    "mastic_tpu/wire.py", "mastic_tpu/compile_cache.py",
]

PRINT_OK = ("tools/", "bench.py", "gen_test_vec.py", "tests/",
            "__graft_entry__.py", "demo")


class ImportTracker(ast.NodeVisitor):
    def __init__(self):
        self.imported: dict = {}
        self.used: set = set()

    def visit_Import(self, node):
        for alias in node.names:
            name = (alias.asname or alias.name).split(".")[0]
            self.imported.setdefault(name, node.lineno)

    def visit_ImportFrom(self, node):
        for alias in node.names:
            if alias.name == "*":
                continue
            name = alias.asname or alias.name
            self.imported.setdefault(name, node.lineno)

    def visit_Name(self, node):
        if isinstance(node.ctx, ast.Load):
            self.used.add(node.id)

    def visit_Attribute(self, node):
        self.generic_visit(node)


def check_file(path: pathlib.Path) -> list:
    rel = str(path.relative_to(REPO))
    problems = []
    try:
        tree = ast.parse(path.read_text(), filename=rel)
    except SyntaxError as err:
        return [f"{rel}:{err.lineno}: syntax error: {err.msg}"]

    tracker = ImportTracker()
    tracker.visit(tree)
    # Names used only inside docstring type references don't count;
    # __all__ re-exports do.
    exported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for target in node.targets:
                if isinstance(target, ast.Name) and target.id == "__all__":
                    if isinstance(node.value, (ast.List, ast.Tuple)):
                        for elt in node.value.elts:
                            if isinstance(elt, ast.Constant):
                                exported.add(elt.value)
    if not rel.endswith("__init__.py"):
        for (name, lineno) in sorted(tracker.imported.items(),
                                     key=lambda kv: kv[1]):
            if name not in tracker.used and name not in exported:
                problems.append(f"{rel}:{lineno}: unused import "
                                f"'{name}'")

    if rel in ANNOTATED:
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            if node.name.startswith("_"):
                continue
            args = node.args
            all_args = args.posonlyargs + args.args + args.kwonlyargs
            missing = [a.arg for a in all_args
                       if a.annotation is None
                       and a.arg not in ("self", "cls")]
            if missing:
                problems.append(
                    f"{rel}:{node.lineno}: public function "
                    f"'{node.name}' missing annotations: {missing}")
            if node.returns is None and node.name != "__init__":
                problems.append(
                    f"{rel}:{node.lineno}: public function "
                    f"'{node.name}' missing return annotation")

    if not any(rel.startswith(ok) or ok in rel for ok in PRINT_OK):
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "print"
                    and not _prints_to_stderr(node)):
                problems.append(f"{rel}:{node.lineno}: print() to "
                                "stdout in library code")
    return problems


def _prints_to_stderr(node: ast.Call) -> bool:
    """Diagnostics on stderr are fine; stdout pollution is the smell."""
    for kw in node.keywords:
        if kw.arg == "file" and isinstance(kw.value, ast.Attribute) \
                and kw.value.attr == "stderr":
            return True
    return False


def check_annotations_resolve() -> list:
    """Check 5: every annotation in the ANNOTATED layer resolves at
    runtime.  get_type_hints evaluates the annotation expressions
    against the module globals, so a typo'd or un-imported type name
    raises here — the executable subset of mypy's name resolution."""
    import importlib
    import inspect
    import typing

    problems = []
    sys.path.insert(0, str(REPO))
    for rel in ANNOTATED:
        mod_name = rel[:-3].replace("/", ".")
        try:
            mod = importlib.import_module(mod_name)
        except Exception as exc:
            problems.append(f"{rel}: module does not import: "
                            f"{type(exc).__name__}: {exc}")
            continue
        def unwrap(member):
            """classmethod/staticmethod descriptors and properties
            hide their function from inspect.isfunction — unwrap, or
            their annotations would silently escape the check."""
            if isinstance(member, (classmethod, staticmethod)):
                return member.__func__
            if isinstance(member, property):
                return member.fget
            return member

        targets = []
        for (name, obj) in vars(mod).items():
            if getattr(obj, "__module__", None) != mod_name:
                continue
            if inspect.isfunction(obj):
                targets.append((name, obj))
            elif inspect.isclass(obj):
                targets.append((name, obj))
                for (mname, member) in vars(obj).items():
                    member = unwrap(member)
                    if inspect.isfunction(member):
                        targets.append((f"{name}.{mname}", member))
        for (tname, target) in targets:
            try:
                typing.get_type_hints(target)
            except Exception as exc:
                problems.append(
                    f"{rel}: annotation on '{tname}' does not "
                    f"resolve: {type(exc).__name__}: {exc}")
    return problems


def _module_name(path: pathlib.Path) -> str:
    rel = path.relative_to(REPO)
    return str(rel)[:-3].replace("/", ".")


def _collect_defs(tree: ast.Module) -> dict:
    """Module-level plain functions only (no methods — `self` and
    inheritance are out of scope; no decorated defs — decorators may
    change the signature)."""
    defs = {}
    for node in tree.body:
        if isinstance(node, ast.FunctionDef) and not node.decorator_list:
            defs[node.name] = node.args
    return defs


def _signature_problem(name: str, a: ast.arguments,
                       call: ast.Call) -> str:
    """Arity/keyword mismatch text, or '' if the call fits.  Calls
    spreading *args/**kwargs are the caller's business — skipped."""
    if any(isinstance(x, ast.Starred) for x in call.args) \
            or any(k.arg is None for k in call.keywords):
        return ""
    pos_params = [p.arg for p in a.posonlyargs + a.args]
    kw_names = set(pos_params[len(a.posonlyargs):]) \
        | {p.arg for p in a.kwonlyargs}
    if a.vararg is None and len(call.args) > len(pos_params):
        return (f"takes {len(pos_params)} positional arg(s), "
                f"call passes {len(call.args)}")
    for k in call.keywords:
        if k.arg not in kw_names and a.kwarg is None:
            return f"got unexpected keyword '{k.arg}'"
    supplied = set(pos_params[:len(call.args)]) \
        | {k.arg for k in call.keywords}
    n_defaults = len(a.defaults)
    required = pos_params[:len(pos_params) - n_defaults]
    missing = [p for p in required if p not in supplied]
    if missing:
        return f"missing required arg(s) {missing}"
    return ""


def check_call_signatures(files: list) -> list:
    """Check 6: intra-repo calls to module-level functions match the
    callee's signature (positional arity, keyword names, required
    args) — the executable subset of mypy's call checking.  Only
    calls through a bare name that is a same-module def or a
    `from <repo module> import name`; names locally reassigned and
    star-spread calls are skipped."""
    trees = {}
    for path in files:
        try:
            trees[path] = ast.parse(path.read_text())
        except SyntaxError:
            continue  # check 1 reports it
    defs_by_module = {_module_name(p): _collect_defs(t)
                      for (p, t) in trees.items()}

    problems = []
    for (path, tree) in trees.items():
        mod = _module_name(path)
        pkg_parts = mod.split(".")[:-1]
        # name -> (defining module, name there)
        env = {n: (mod, n) for n in defs_by_module.get(mod, {})}
        reassigned = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                if node.level:
                    base = pkg_parts[:len(pkg_parts) - node.level + 1]
                    target = ".".join(base + ([node.module]
                                              if node.module else []))
                else:
                    target = node.module or ""
                if target in defs_by_module:
                    for alias in node.names:
                        if alias.name in defs_by_module[target]:
                            env[alias.asname or alias.name] = \
                                (target, alias.name)
            elif isinstance(node, (ast.Assign, ast.AugAssign,
                                   ast.AnnAssign, ast.For)):
                targets = (node.targets
                           if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            reassigned.add(n.id)
            elif isinstance(node, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                for arg in (node.args.posonlyargs + node.args.args
                            + node.args.kwonlyargs):
                    reassigned.add(arg.arg)
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)):
                continue
            name = node.func.id
            if name in reassigned or name not in env:
                continue
            (dmod, dname) = env[name]
            msg = _signature_problem(
                name, defs_by_module[dmod][dname], node)
            if msg:
                problems.append(
                    f"{path.relative_to(REPO)}:{node.lineno}: call to "
                    f"{dmod}.{dname} {msg}")
    return problems


_LEVER_RE = re.compile(r"MASTIC_[A-Z][A-Z0-9_]*")


def check_env_levers() -> list:
    """Check 7: lever coverage.  A MASTIC_* env var referenced
    anywhere in mastic_tpu/ or bench.py must be documented in
    USAGE.md; one referenced in the kernel/backend layer (ops/ or
    backend/ — the compute-path levers a chip session must measure)
    must additionally appear in tools/chip_session.sh, either
    verbatim or as the bench.py flag it maps to."""
    lever_files = sorted((REPO / "mastic_tpu").rglob("*.py"))
    lever_files.append(REPO / "bench.py")
    levers: dict = {}          # name -> (first file, is_kernel_lever)
    for path in lever_files:
        rel = str(path.relative_to(REPO))
        kernel = rel.startswith(("mastic_tpu/ops/",
                                 "mastic_tpu/backend/"))
        for name in _LEVER_RE.findall(path.read_text()):
            (seen_rel, seen_kernel) = levers.get(name, (rel, False))
            levers[name] = (seen_rel, seen_kernel or kernel)

    usage = (REPO / "USAGE.md").read_text()
    session = (REPO / "tools" / "chip_session.sh").read_text()
    problems = []
    for (name, (rel, kernel)) in sorted(levers.items()):
        if name not in usage:
            problems.append(
                f"{rel}: env lever {name} is not documented in "
                f"USAGE.md")
        flag = "--" + name[len("MASTIC_"):].lower().replace("_", "-")
        if kernel and name not in session and flag not in session:
            problems.append(
                f"{rel}: kernel lever {name} is not exercised by "
                f"tools/chip_session.sh (neither {name} nor its "
                f"bench flag {flag} appears in the matrix)")
    return problems


def _strict_mypy_modules(ini_path: pathlib.Path = None) -> set:
    """Module names mypy.ini holds to the full strict standard: under
    the global `strict = True` with no per-module override relaxing
    them (ignore_errors or disallow_untyped_defs).  __init__ re-export
    shims are skipped — they hold no function signatures."""
    cfg = configparser.ConfigParser()
    cfg.read(ini_path or REPO / "mypy.ini")
    relaxed_patterns = []
    for section in cfg.sections():
        if not section.startswith("mypy-"):
            continue
        sub = cfg[section]
        if sub.getboolean("ignore_errors", fallback=False) \
                or not sub.getboolean("disallow_untyped_defs",
                                      fallback=True):
            relaxed_patterns.append(section[len("mypy-"):])

    def relaxed(module: str) -> bool:
        for pat in relaxed_patterns:
            if pat.endswith(".*"):
                if module == pat[:-2] or module.startswith(pat[:-1]):
                    return True
            elif module == pat:
                return True
        return False

    strict = set()
    for path in sorted((REPO / "mastic_tpu").rglob("*.py")):
        if path.name == "__init__.py":
            continue
        module = str(path.relative_to(REPO))[:-3].replace("/", ".")
        if not relaxed(module):
            strict.add(module)
    return strict


def check_metric_docs() -> list:
    """Check 9: every declared registry series is documented.  The
    registry module is import-cheap (stdlib only), so importing it to
    read DECLARED is the same pattern check 5 uses."""
    sys.path.insert(0, str(REPO))
    from mastic_tpu.obs.registry import declared_metric_names

    usage = (REPO / "USAGE.md").read_text()
    problems = []
    for name in declared_metric_names():
        if name not in usage:
            problems.append(
                f"mastic_tpu/obs/registry.py: metric {name} is "
                f"declared but not documented in USAGE.md's "
                f"Observability metric table")
    return problems


_RULE_ROW_RE = re.compile(r"^\|\s*`([A-Z]{2}\d{3})`")


def check_rule_table_docs() -> list:
    """Check 10: the USAGE.md analyzer rule table == the analyzer's
    _RULE_TABLE.  The table rows are the lines starting `| \\`XX000\\``
    inside the "Static analysis" section (same import-the-source-of-
    truth pattern as check 9 — tools.analysis is stdlib-only)."""
    sys.path.insert(0, str(REPO))
    from tools.analysis import _RULE_TABLE

    usage = (REPO / "USAGE.md").read_text()
    in_section = False
    documented = set()
    for line in usage.splitlines():
        if line.startswith("## "):
            in_section = line.startswith("## Static analysis")
            continue
        if in_section:
            m = _RULE_ROW_RE.match(line)
            if m:
                documented.add(m.group(1))
    problems = []
    for rule in sorted(set(_RULE_TABLE) - documented):
        problems.append(
            f"tools/analysis: rule {rule} is shipped but missing "
            f"from USAGE.md's Static-analysis rule table")
    for rule in sorted(documented - set(_RULE_TABLE)):
        problems.append(
            f"USAGE.md: rule-table row {rule} names a rule the "
            f"analyzer no longer ships — remove the stale row")
    return problems


# Sinks whose string-literal (or ALL_CAPS-constant) arguments are
# shed reasons; the TLS refusal vocabulary is the TLS_* constant set
# in net/transport.py (the reasons reach _count_refusal through
# exception attributes, so the constants ARE the source of truth).
_SHED_SINKS = {"bump_shed", "count_front_shed", "shed",
               "shed_external"}
_REASON_ROW_RE = re.compile(r"^\|\s*`([a-z0-9]+(?:-[a-z0-9]+)+)`")
_REASON_SECTIONS = ("## Collector service", "## Network front",
                    "## Durability",
                    "## Transport security")


def _counted_reasons() -> dict:
    """reason literal -> file that counts it, from the code."""
    files = sorted((REPO / "mastic_tpu").rglob("*.py"))
    trees = {}
    consts: dict = {}      # ALL_CAPS name -> hyphenated str value
    for path in files:
        rel = str(path.relative_to(REPO))
        try:
            trees[rel] = ast.parse(path.read_text())
        except SyntaxError:
            continue  # check 1 reports it
        for node in trees[rel].body:
            if isinstance(node, ast.Assign) \
                    and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and node.targets[0].id.isupper() \
                    and isinstance(node.value, ast.Constant) \
                    and isinstance(node.value.value, str) \
                    and "-" in node.value.value:
                consts[node.targets[0].id] = node.value.value

    reasons: dict = {}
    for (rel, tree) in trees.items():
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _SHED_SINKS):
                continue
            for arg in node.args:
                if isinstance(arg, ast.Constant) \
                        and isinstance(arg.value, str) \
                        and "-" in arg.value:
                    reasons.setdefault(arg.value, rel)
                elif isinstance(arg, ast.Name) \
                        and arg.id in consts:
                    reasons.setdefault(consts[arg.id], rel)
    tls_rel = "mastic_tpu/net/transport.py"
    for (name, value) in consts.items():
        if name.startswith("TLS_") and value.startswith("tls-"):
            reasons.setdefault(value, tls_rel)
    return reasons


def check_reason_docs() -> list:
    """Check 11: the reason-code contract.  The kebab-case rows of
    the reason tables in USAGE.md's service/network/transport
    sections must equal the reason literals the code counts — both
    directions (same shape as check 10)."""
    counted = _counted_reasons()
    usage = (REPO / "USAGE.md").read_text()
    in_section = False
    documented = set()
    for line in usage.splitlines():
        if line.startswith("## "):
            in_section = line.startswith(_REASON_SECTIONS)
            continue
        if in_section:
            m = _REASON_ROW_RE.match(line)
            if m:
                documented.add(m.group(1))
    problems = []
    for reason in sorted(set(counted) - documented):
        problems.append(
            f"{counted[reason]}: shed/refusal reason "
            f"'{reason}' is counted but has no row in USAGE.md's "
            f"reason tables")
    for reason in sorted(documented - set(counted)):
        problems.append(
            f"USAGE.md: reason-table row '{reason}' names a reason "
            f"the code no longer counts — remove the stale row")
    return problems


def check_mypy_sync() -> list:
    """Check 8: ANNOTATED == mypy.ini's strict module set, so the
    runtime annotation gate (checks 3/5) covers exactly the modules
    real CI would hold to strict mypy."""
    annotated = {rel[:-3].replace("/", ".") for rel in ANNOTATED}
    strict = _strict_mypy_modules()
    problems = []
    for module in sorted(strict - annotated):
        problems.append(
            f"mypy.ini: {module} is mypy-strict but missing from "
            f"tools/lint.py ANNOTATED (add it, or relax it in "
            f"mypy.ini with a reason)")
    for module in sorted(annotated - strict):
        problems.append(
            f"tools/lint.py: {module} is in ANNOTATED but relaxed in "
            f"mypy.ini (drop the override, or remove it from "
            f"ANNOTATED)")
    return problems


def main() -> int:
    roots = [REPO / "mastic_tpu", REPO / "tests", REPO / "tools"]
    files = [REPO / "bench.py", REPO / "__graft_entry__.py"]
    fixtures = REPO / "tests" / "fixtures"
    for root in roots:
        files += sorted(p for p in root.rglob("*.py")
                        if fixtures not in p.parents)
    problems = []
    for path in files:
        problems += check_file(path)
    problems += check_annotations_resolve()
    problems += check_call_signatures(files)
    problems += check_env_levers()
    problems += check_mypy_sync()
    problems += check_metric_docs()
    problems += check_rule_table_docs()
    problems += check_reason_docs()
    for problem in problems:
        print(problem)
    print(f"lint: {len(files)} files, {len(problems)} problem(s)")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
