"""Pass: FLAGS_* namespace hygiene.

Every `FLAGS_*` string literal used in code — `get_flag("FLAGS_x")`,
`set_flags({"FLAGS_x": ...})`, `os.environ.get("FLAGS_x")` — must
resolve to a registered default in the `_flags` dict of
`paddle_tpu/framework/core.py`. A typo'd flag read silently returns
the fallback default forever (`get_flag` has no unknown-key error);
a typo'd flag WRITE vanishes into the dict and steers nothing. Both
are exactly the bugs a 2.9M-LoC framework's flag checker exists to
catch.

The inverse check runs when the whole scope was scanned: a registered
flag that no code outside the registry ever reads is DEAD (warning) —
delete it or alias it to the live spelling. Flags kept only for
paddle-API compatibility (accepted + queryable, steering
XLA-internal machinery) are declared in `COMPAT_ACCEPTED`; references
from tests/ and benchmarks/ also count as live (some knobs exist for
harnesses).

Exact-match only: a literal must BE a flag name (`"FLAGS_benchmark"`),
not merely mention one ("FLAGS_check_nan_inf is enabled"); docstrings
are prose and are skipped entirely.
"""
from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, List, Set, Tuple

from ..core import FileContext, LintPass

REGISTRY_RELPATH = "paddle_tpu/framework/core.py"
_FLAG_RE = re.compile(r"^FLAGS_[A-Za-z0-9_]+$")
_FLAG_SCAN_RE = re.compile(r"FLAGS_[A-Za-z0-9_]+")

# registered but intentionally unconsumed: the paddle-API-compat block
# in framework/core.py (accepted + queryable; the machinery they steer
# is XLA-internal on TPU)
COMPAT_ACCEPTED = {
    "FLAGS_conv_workspace_size_limit",
    "FLAGS_cudnn_batchnorm_spatial_persistent",
    "FLAGS_enable_cublas_tensor_op_math",
    "FLAGS_use_system_allocator",
    "FLAGS_use_pinned_memory",
    "FLAGS_init_allocated_mem",
    "FLAGS_initial_cpu_memory_in_mb",
    "FLAGS_memory_fraction_of_eager_deletion",
    "FLAGS_fast_eager_deletion_mode",
    "FLAGS_use_mkldnn",
    "FLAGS_enable_pir_api",
    "FLAGS_new_executor_serial_run",
    "FLAGS_low_precision_op_list",
    "FLAGS_print_model_stats",
    "FLAGS_sync_nccl_allreduce",
    "FLAGS_fuse_parameter_memory_size",
    "FLAGS_rpc_deadline",
    "FLAGS_apply_pass_to_program",
    "FLAGS_gpu_memory_limit_mb",
    "FLAGS_embedding_deterministic",
}

# non-package trees whose FLAGS_ references keep a flag alive (harness
# knobs); scanned textually in finish()
_EXTERNAL_REF_DIRS = ("tests", "benchmarks", "tools")


def _docstring_ids(tree) -> Set[int]:
    """ids of Constant nodes sitting in docstring position."""
    out: Set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.FunctionDef,
                             ast.AsyncFunctionDef, ast.ClassDef)):
            body = node.body
            if body and isinstance(body[0], ast.Expr) and \
                    isinstance(body[0].value, ast.Constant) and \
                    isinstance(body[0].value.value, str):
                out.add(id(body[0].value))
    return out


def parse_registry(core_path: Path) -> Dict[str, int]:
    """FLAGS_* keys of the `_flags = {...}` dict literal -> line no."""
    tree = ast.parse(core_path.read_text(), filename=str(core_path))
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign):
            targets = [node.target]
        else:
            continue
        if len(targets) == 1 and isinstance(targets[0], ast.Name) and \
                targets[0].id == "_flags" and \
                isinstance(node.value, ast.Dict):
            return {k.value: k.lineno for k in node.value.keys
                    if isinstance(k, ast.Constant) and
                    isinstance(k.value, str) and _FLAG_RE.match(k.value)}
    raise RuntimeError(
        f"flags-hygiene: no `_flags = {{...}}` dict literal found in "
        f"{core_path} — the registry moved; update "
        f"tools/graft_lint/passes/flags_hygiene.py")


class FlagsHygienePass(LintPass):
    name = "flags-hygiene"
    description = ("FLAGS_* literals must resolve to a registered "
                   "default in framework/core.py; registered flags "
                   "nobody reads are dead")
    severity = "error"
    scope = ("paddle_tpu/",)

    def begin(self, repo):
        self._repo = repo
        self._registered: Dict[str, int] = parse_registry(
            repo / REGISTRY_RELPATH)
        self._registry_key_lines: Set[int] = set(
            self._registered.values())
        self._used: Dict[str, List[Tuple[str, int]]] = {}

    def check_file(self, ctx: FileContext):
        out: List = []
        in_registry_file = ctx.relpath == REGISTRY_RELPATH
        doc_ids = _docstring_ids(ctx.tree)
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Constant) and
                    isinstance(node.value, str) and
                    _FLAG_RE.match(node.value)):
                continue
            if id(node) in doc_ids:
                continue
            flag = node.value
            if in_registry_file and node.lineno in self._registry_key_lines:
                continue    # the registry entry itself, not a use
            self._used.setdefault(flag, []).append(
                (ctx.relpath, node.lineno))
            if flag not in self._registered:
                out.append(self.finding(
                    ctx, node.lineno,
                    f"{flag!r} is not registered in framework/core.py "
                    f"`_flags` — a typo'd read silently returns its "
                    f"fallback default forever and a typo'd write "
                    f"steers nothing; register it with a default (or "
                    f"fix the spelling)"))
        return out

    def finish(self):
        if not self.scanned_full_scope:
            return []
        from ..core import Finding
        live = set(self._used) | COMPAT_ACCEPTED | self._external_refs()
        out = []
        for flag, line in sorted(self._registered.items()):
            if flag not in live:
                out.append(Finding(
                    REGISTRY_RELPATH, line, self.name,
                    f"registered flag {flag!r} is never read by any "
                    f"code — delete it, or add it to COMPAT_ACCEPTED "
                    f"in flags_hygiene.py if it exists for paddle API "
                    f"compatibility", severity="warning"))
        return out

    def _external_refs(self) -> Set[str]:
        """Flags referenced from harness trees (tests/, benchmarks/,
        tools/) — textual scan, comments included: a flag a
        test sets is live even if the package reads it via env only."""
        refs: Set[str] = set()
        roots = [self._repo / d for d in _EXTERNAL_REF_DIRS]
        files: List[Path] = []
        for r in roots:
            if r.is_dir():
                files.extend(r.rglob("*.py"))
        for f in files:
            if "__pycache__" in f.parts or not f.is_file():
                continue
            try:
                refs.update(_FLAG_SCAN_RE.findall(f.read_text()))
            except OSError:
                continue
        return refs
