"""Pallas kernel contract verifier — the static gate before the
accelerator push (DESIGN.md §9).

Two layers over ``src/repro/kernels``:

Layer 1 — AST rules (this module, PR-7 style: stdlib ``ast``, file:line
findings, no imports of the checked code):

* ``kernel-missing-oracle``    — every public ``*_kernel`` wrapper must
  pair with a ``ref.py`` oracle: ``<stem>_ref`` by name, or an explicit
  ``# analysis: oracle=<name>`` pragma on the def (``mlstm_chunked``'s
  oracle is ``gla_ref``).  A kernel without an oracle has no exact
  semantics to test against — the repo's whole validation chain
  (interpret-mode equality, differential fuzz) hangs off the pairing.
* ``kernel-memory-space``      — every ``pl.BlockSpec(...)`` must
  declare ``memory_space=``: scalars ride SMEM, dense rows VMEM, and an
  undeclared spec silently takes whatever default the Pallas version
  ships, which is exactly the kind of contract that breaks under a
  toolchain bump.
* ``kernel-mxu-element-type``  — ``jnp.dot`` / ``lax.dot_general`` /
  ``pl.dot`` must set ``preferred_element_type``: the MXU accumulates
  bf16 inputs in bf16 unless told otherwise, and the prefix-sum trick
  in the buffer-manager kernels is exact only under f32 accumulation.
* ``kernel-float-mantissa-cast`` — an integer-keyed input must not be
  cast to a float dtype whose mantissa is narrower than the key's used
  bits (the ``fifo_grant`` 2^24 rule generalized: its FIFO keys use
  ~30 bits, f32 carries 24).  A ``key``-named parameter cast via
  ``.astype(float dtype)`` is flagged unless the enclosing function
  dispatches on ``jnp.issubdtype(key.dtype, jnp.integer)`` — the
  sanctioned pattern: integers ride an i32 path, floats the f32 one.

Layer 2 — abstract interpretation (:mod:`repro.analysis.absint`): each
kernel wrapper in :data:`CONTRACTS` runs against small example operands
with ``pl.pallas_call`` swapped for a recorder, and the captured
grid/BlockSpec geometry is checked for coverage, index bounds, write
races and the VMEM budget.  See ``absint``'s docstring for the rules.

``verify_kernels()`` is the combined entry point wired into
``python -m repro.analysis --check``.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Set

from .findings import Finding
from .lint import repo_src_root

__all__ = [
    "CONTRACTS",
    "KernelContract",
    "check_contracts",
    "kernel_lint_source",
    "ref_oracle_names",
    "verify_kernels",
]

#: float dtypes by mantissa width (bits of exact integer headroom)
FLOAT_MANTISSA = {
    "float64": 53, "float32": 24, "float16": 11, "bfloat16": 8,
}
#: parameter names treated as integer-capable sort/priority keys
_KEY_PARAM = ("key", "keys")
#: dotted-call tails that hit the MXU and must pin their accumulator type
_MXU_TAILS = ("dot", "dot_general", "matmul")
#: files in kernels/ that are not kernel-wrapper modules
_NON_KERNEL_FILES = {"__init__.py", "ref.py", "ops.py"}

_ORACLE_PRAGMA = "# analysis: oracle="


def _dotted(func: ast.expr) -> Optional[str]:
    parts: List[str] = []
    node = func
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _root_name(node: ast.expr) -> Optional[str]:
    while isinstance(node, (ast.Attribute, ast.Subscript, ast.Call)):
        node = node.func if isinstance(node, ast.Call) else node.value
    return node.id if isinstance(node, ast.Name) else None


def _is_key_param(name: Optional[str]) -> bool:
    return name is not None and (
        name in _KEY_PARAM or name.endswith("_key") or name.endswith("_keys")
    )


def _float_target(node: ast.expr) -> Optional[str]:
    """Float dtype name if this astype target is one, else None."""
    if isinstance(node, ast.Attribute) and node.attr in FLOAT_MANTISSA:
        return node.attr
    if isinstance(node, ast.Name) and node.id in FLOAT_MANTISSA:
        return node.id
    if isinstance(node, ast.Constant) and node.value in FLOAT_MANTISSA:
        return str(node.value)
    return None


def ref_oracle_names(ref_source: str) -> Set[str]:
    """Module-level def names of ``kernels/ref.py``."""
    try:
        tree = ast.parse(ref_source)
    except SyntaxError:
        return set()
    return {n.name for n in tree.body if isinstance(n, ast.FunctionDef)}


def _oracle_pragma(src_lines: Sequence[str], node: ast.AST) -> Optional[str]:
    """``# analysis: oracle=<name>`` on the def line or the line above."""
    for ln in (node.lineno - 1, node.lineno - 2):
        if 0 <= ln < len(src_lines):
            text = src_lines[ln]
            if _ORACLE_PRAGMA in text:
                tail = text.split(_ORACLE_PRAGMA, 1)[1]
                name = tail.split()[0].strip() if tail.split() else ""
                return name or None
    return None


class _FunctionScan(ast.NodeVisitor):
    """Per-function rule sites: MXU element type, BlockSpec memory
    space, float-mantissa key casts."""

    def __init__(self, rel: str, findings: List[Finding],
                 params: Set[str], dispatched: Set[str]):
        self.rel = rel
        self.findings = findings
        self.params = params
        self.dispatched = dispatched   # params with an issubdtype dispatch

    def _emit(self, rule: str, node: ast.AST, message: str) -> None:
        self.findings.append(Finding(
            rule=rule, path=self.rel, line=node.lineno,
            col=node.col_offset, message=message,
        ))

    def visit_Call(self, node: ast.Call) -> None:
        dotted = _dotted(node.func) or ""
        tail = dotted.rsplit(".", 1)[-1]
        kwargs = {kw.arg for kw in node.keywords}

        if tail == "BlockSpec" and "memory_space" not in kwargs:
            self._emit(
                "kernel-memory-space", node,
                "BlockSpec without memory_space= — declare it (scalars "
                "ride pltpu.SMEM, dense rows pltpu.VMEM); an undeclared "
                "spec takes the Pallas version's default",
            )
        if tail in _MXU_TAILS and dotted != tail \
                and "preferred_element_type" not in kwargs:
            self._emit(
                "kernel-mxu-element-type", node,
                f"`{dotted}` without preferred_element_type= — the MXU "
                "accumulates narrow inputs narrowly unless pinned; the "
                "prefix-sum kernels are exact only under f32 accumulation",
            )
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr == "astype" and node.args:
            target = _float_target(node.args[0])
            root = _root_name(node.func.value)
            if target is not None and _is_key_param(root) \
                    and root in self.params and root not in self.dispatched:
                bits = FLOAT_MANTISSA[target]
                self._emit(
                    "kernel-float-mantissa-cast", node,
                    f"`{root}.astype({target})` casts a priority key to a "
                    f"{bits}-bit-mantissa float unconditionally — integer "
                    f"keys wider than 2^{bits} collapse (the fifo_grant "
                    "2^24 rule).  Dispatch on jnp.issubdtype"
                    f"({root}.dtype, jnp.integer) and keep integers on an "
                    "i32 path",
                )
        self.generic_visit(node)


def _issubdtype_params(fn: ast.FunctionDef) -> Set[str]:
    """Parameters whose dtype the function dispatches on via
    ``jnp.issubdtype(<param>.dtype, ...)`` (the sanctioned guard)."""
    out: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Call) \
                and (_dotted(node.func) or "").endswith("issubdtype"):
            for arg in node.args:
                root = _root_name(arg)
                if root is not None:
                    out.add(root)
    return out


def kernel_lint_source(source: str, rel: str,
                       ref_names: Optional[Set[str]] = None) -> List[Finding]:
    """Layer-1 kernel rules over one kernel module's source.

    ``ref_names`` is the set of oracle defs in ``kernels/ref.py``
    (injectable for tests); ``None`` skips the oracle rule.
    """
    findings: List[Finding] = []
    try:
        tree = ast.parse(source, filename=rel)
    except SyntaxError as exc:
        findings.append(Finding(
            rule="syntax-error", path=rel, line=exc.lineno or 1,
            message=str(exc.msg),
        ))
        return findings
    src_lines = source.splitlines()

    for node in tree.body:
        if not isinstance(node, ast.FunctionDef):
            continue
        # oracle pairing for public wrappers
        if ref_names is not None and node.name.endswith("_kernel") \
                and not node.name.startswith("_"):
            stem = node.name[: -len("_kernel")]
            declared = _oracle_pragma(src_lines, node)
            if declared is not None:
                if declared not in ref_names:
                    findings.append(Finding(
                        rule="kernel-missing-oracle", path=rel,
                        line=node.lineno,
                        message=f"{node.name} declares oracle "
                                f"{declared!r} but kernels/ref.py does "
                                "not define it",
                    ))
            elif f"{stem}_ref" not in ref_names:
                findings.append(Finding(
                    rule="kernel-missing-oracle", path=rel,
                    line=node.lineno,
                    message=f"{node.name} has no ref.py oracle: define "
                            f"`{stem}_ref` (or declare the pairing with "
                            "`# analysis: oracle=<name>`) — interpret-"
                            "mode equality against the oracle is the "
                            "kernel's only executable spec",
                ))

    class _Walk(ast.NodeVisitor):
        def visit_FunctionDef(self, fn: ast.FunctionDef) -> None:
            params = {
                a.arg for a in (list(fn.args.posonlyargs) + list(fn.args.args)
                                + list(fn.args.kwonlyargs))
            }
            scan = _FunctionScan(rel, findings, params,
                                 _issubdtype_params(fn))
            for stmt in fn.body:
                scan.visit(stmt)
            self.generic_visit(fn)

    _Walk().visit(tree)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings


def lint_kernel_tree(root=None) -> List[Finding]:
    """Layer 1 over every kernel module under ``<root>/kernels``."""
    root = Path(root) if root is not None else repo_src_root()
    kdir = root / "kernels"
    if not kdir.is_dir():
        return []
    ref_path = kdir / "ref.py"
    ref_names = (
        ref_oracle_names(ref_path.read_text(encoding="utf-8"))
        if ref_path.exists() else set()
    )
    findings: List[Finding] = []
    for path in sorted(kdir.glob("*.py")):
        if path.name in _NON_KERNEL_FILES:
            continue
        try:
            rel = str(path.relative_to(root.parent))
        except ValueError:
            rel = str(path)
        findings += kernel_lint_source(
            path.read_text(encoding="utf-8"), rel, ref_names)
    return findings


# -------------------------------------------------------------- contracts --

class KernelContract(NamedTuple):
    """One pallas_call site + the example point it is verified at.

    ``build`` imports the wrapper lazily (the contract table must be
    importable without jax) and returns ``(fn, args, kwargs)``; the
    wrapper is then executed with ``pl.pallas_call`` patched to a
    recorder, so its real padding/grid/BlockSpec logic runs but the
    kernel body never does.  Example shapes are small — the checked
    properties (divisibility, bounds, injectivity, footprint) are
    shape-relative and transfer to any size the wrapper computes its
    geometry from.
    """

    name: str
    build: Callable


def _c_batched_evict():
    import jax.numpy as jnp
    from repro.kernels.pbm_timeline import batched_evict_kernel

    P = 4096
    return (batched_evict_kernel,
            (jnp.zeros(P, jnp.float32), jnp.ones(P, jnp.float32),
             jnp.ones(P, bool), jnp.float32(8.0)),
            {})


def _c_batched_evict_i32():
    """The integer-key path: array-OPT exact next-use distances must
    survive beyond 2^24 (the rule that caught the unconditional f32
    cast this PR fixed)."""
    import jax.numpy as jnp
    from repro.kernels.pbm_timeline import batched_evict_kernel

    P = 4096
    return (batched_evict_kernel,
            (jnp.zeros(P, jnp.int32), jnp.ones(P, jnp.float32),
             jnp.ones(P, bool), jnp.float32(8.0)),
            {})


def _c_fifo_grant():
    """One grid step (``jax.vmap`` adds the lane axis) holding the whole
    pool row in VMEM, dense as (P_pad / 128, 128): the recorder sees a
    single full-row block per operand."""
    import jax.numpy as jnp
    from repro.kernels.pbm_timeline import fifo_grant_kernel

    P = 4096
    return (fifo_grant_kernel,
            (jnp.zeros(P, jnp.int32), jnp.ones(P, jnp.float32),
             jnp.float32(64.0), jnp.int32(8)),
            {})


def _c_fifo_grant_tail():
    """The same geometry at a ragged pool: P = 3 x 512 + 129 pads to
    2,048 pages, (16, 128), so the coverage and bounds rules must hold
    with padding in play, not just at the divisible example point."""
    import jax.numpy as jnp
    from repro.kernels.pbm_timeline import fifo_grant_kernel

    P = 3 * 512 + 129
    return (fifo_grant_kernel,
            (jnp.zeros(P, jnp.int32), jnp.ones(P, jnp.float32),
             jnp.float32(64.0), jnp.int32(8)),
            {})


def _c_wake_solve():
    """The grant's geometry: one grid step (``jax.vmap`` adds the lane
    axis) holding the whole pool row in VMEM, dense as (P_pad / 128,
    128), with ``pops``, ``credit0`` and ``inc`` in SMEM."""
    import jax.numpy as jnp
    from repro.kernels.pbm_timeline import wake_solve_kernel

    P = 4096
    return (wake_solve_kernel,
            (jnp.zeros(P, jnp.int32), jnp.ones(P, jnp.float32),
             jnp.float32(4.0), jnp.float32(2.0), jnp.int32(6)),
            {"h_cap": 16})


def _c_wake_solve_tail():
    """The same geometry at a ragged pool: P = 3 x 512 + 129 pads to
    2,048 pages, (16, 128), so the footprint and write-coverage checks
    must pass with padding in play."""
    import jax.numpy as jnp
    from repro.kernels.pbm_timeline import wake_solve_kernel

    P = 3 * 512 + 129
    return (wake_solve_kernel,
            (jnp.zeros(P, jnp.int32), jnp.ones(P, jnp.float32),
             jnp.float32(4.0), jnp.float32(2.0), jnp.int32(6)),
            {"h_cap": 16})


def _c_paged_attention():
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels.paged_attention import paged_attention_kernel

    b, h, hk, dh = 2, 4, 2, 128
    page_size, pages_per_seq, n_pages = 16, 3, 10
    # the page table deliberately includes the pool's LAST page id so the
    # bounds probe exercises the table-driven index_map at the pool edge
    pt = np.arange(b * pages_per_seq, dtype=np.int32).reshape(b, -1)
    pt[0, 0] = n_pages - 1
    return (paged_attention_kernel,
            (jnp.zeros((b, h, dh), jnp.float32),
             jnp.zeros((n_pages, page_size, hk, dh), jnp.float32),
             jnp.zeros((n_pages, page_size, hk, dh), jnp.float32),
             jnp.asarray(pt),
             jnp.full((b,), page_size * pages_per_seq, jnp.int32)),
            {})


def _c_flash_attention():
    import jax.numpy as jnp
    from repro.kernels.flash_attention import flash_attention_kernel

    b, t, h, dh = 1, 256, 2, 128
    z = jnp.zeros((b, t, h, dh), jnp.float32)
    return (flash_attention_kernel, (z, z, z),
            {"causal": True, "block_q": 128, "block_kv": 128})


def _c_mamba2_scan():
    import jax.numpy as jnp
    from repro.kernels.mamba2_scan import mamba2_scan_kernel

    B, T, H, P, N = 2, 256, 2, 64, 64
    return (mamba2_scan_kernel,
            (jnp.zeros((B, T, H, P), jnp.float32),
             jnp.ones((B, T, H), jnp.float32),
             jnp.zeros((B, T, N), jnp.float32),
             jnp.zeros((B, T, N), jnp.float32)),
            {"chunk": 128})


def _c_mlstm():
    import jax.numpy as jnp
    from repro.kernels.mlstm import mlstm_chunked_kernel

    B, T, H, K, P = 2, 256, 2, 64, 64
    qk = jnp.zeros((B, T, H, K), jnp.float32)
    v = jnp.zeros((B, T, H, P), jnp.float32)
    g = jnp.ones((B, T, H), jnp.float32)
    return (mlstm_chunked_kernel, (qk, qk, v, g, g), {"chunk": 128})


#: every pl.pallas_call site in src/repro/kernels, by wrapper
CONTRACTS = (
    KernelContract("batched_evict", _c_batched_evict),
    KernelContract("batched_evict[i32]", _c_batched_evict_i32),
    KernelContract("fifo_grant", _c_fifo_grant),
    KernelContract("fifo_grant[tail]", _c_fifo_grant_tail),
    KernelContract("wake_solve", _c_wake_solve),
    KernelContract("wake_solve[tail]", _c_wake_solve_tail),
    KernelContract("paged_attention", _c_paged_attention),
    KernelContract("flash_attention", _c_flash_attention),
    KernelContract("mamba2_scan", _c_mamba2_scan),
    KernelContract("mlstm_chunked", _c_mlstm),
)


def check_contracts(contracts: Sequence[KernelContract] = CONTRACTS,
                    vmem_budget: Optional[int] = None) -> List[Finding]:
    """Layer 2: run each contract's wrapper under the recorder and check
    every captured pallas_call."""
    from .absint import DEFAULT_VMEM_BUDGET, capture_calls, check_call

    budget = DEFAULT_VMEM_BUDGET if vmem_budget is None else vmem_budget
    findings: List[Finding] = []
    for contract in contracts:
        fn, args, kwargs = contract.build()
        calls: List = []
        try:
            with capture_calls(calls):
                fn(*args, **kwargs)
        except Exception as exc:  # noqa: BLE001 — wrapper crash IS a finding
            findings.append(Finding(
                rule="kernel-contract-error", path="repro/kernels", line=0,
                message=f"{contract.name}: wrapper raised "
                        f"{type(exc).__name__} under capture: {exc}",
            ))
            continue
        if not calls:
            findings.append(Finding(
                rule="kernel-contract-error", path="repro/kernels", line=0,
                message=f"{contract.name}: wrapper made no pallas_call "
                        "(contract is stale — update CONTRACTS)",
            ))
        for call in calls:
            findings += check_call(call, vmem_budget=budget)
    return findings


def verify_kernels(root=None,
                   vmem_budget: Optional[int] = None,
                   contracts: bool = True) -> List[Finding]:
    """Both layers.  ``root`` scopes the AST layer (PR-7 ``--root``
    convention); the contract layer always targets the *installed*
    kernels, so it is skipped when a custom root is given."""
    findings = lint_kernel_tree(root)
    if contracts and root is None:
        findings += check_contracts(vmem_budget=vmem_budget)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings
