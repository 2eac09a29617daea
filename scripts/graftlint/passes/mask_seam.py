"""mask-seam: the ``id < 0`` tombstone/padding mask must never be skipped.

Every scan formulation encodes three row states in one id array
(``neighbors/mutate``): ``>= 0`` live, ``-1`` never-filled padding,
``<= -2`` tombstoned (encoded ``-(id + 2)``).  Library code that tests
``ids == -1`` sees padding but *misses tombstones* — a delete-aware
path silently resurrects deleted rows.  The only comparisons that
respect the seam are sign tests (``< 0`` / ``>= 0``); the only place an
exact ``-1`` is legitimate is AFTER ``grouped.finalize_topk`` clamps
encoded ids to the public sentinel (suppress with a reason there).

The second seam is numeric: Pallas kernels that multiply masks or
one-hots into distance values must never see an inf — IEEE says
``0 * inf = NaN``, so sentinel distances inside ``ops/*_pallas.py``
must be the finite ``3.0e38`` (``_ACC_WORST``) wherever they can meet
a product.  An ``inf`` flowing into ``*`` / ``@`` / ``dot`` poisons
whole rows.

The third seam is the fused kernels' VMEM scratch: the per-query
accumulator (``acc_*`` refs) and any staging block (``stg_*`` /
``*ring*``) hold exhausted ranks at exactly the finite sentinel — an
``inf`` (or any huge float that is not ``_ACC_WORST``) written there
breaks the merge's liveness test (``< _ACC_WORST/2``) that the
epilogue shares.  And because the VMEM model (``ops/vmem_budget``) and
the kernel must agree on the footprint, the fused kernels'
``scratch_shapes`` must be sized by the shared budget helpers, never
by inline shape lists.

The fourth seam is the round-20 admission bit: filtered search streams
packed per-(query, candidate) admission words into the fused kernels,
which unpack them to 0/1 blocks (``adm`` / ``adm_ref`` / ``adm_words``).
The ONLY safe way to apply that bit is to fold it into the existing
validity mask (``invalid | (adm == 0)`` / ``ok & (adm > 0)``) so the
rejected candidate takes the finite ``_ACC_WORST`` sentinel exactly
like padding.  Multiplying admission bits into distances reintroduces
the ``0 * inf`` hazard AND silently turns a rejected candidate into a
zero-distance best hit; selecting with an ``inf`` branch poisons the
merge; comparing against a non-zero constant (``adm == 1``) breaks the
moment the unpack widens its nonzero encoding.

Rules:

- ``mask-seam``: ``== -1`` / ``!= -1`` comparisons against id-ish
  expressions (names containing ``ids`` / ``indices``, the scan id
  buffers ``outi`` / ``alli`` / ``best_i``, ``neighbors``) anywhere
  under ``raft_tpu/``.
- ``mask-seam``: a multiplication / matmul / ``dot`` in
  ``raft_tpu/ops/*_pallas.py`` with an ``inf`` literal anywhere in its
  operands.
- ``admission-seam``: in ``raft_tpu/ops/*_pallas.py``, an
  admission-bit expression used as a product operand, an admission
  conditional select whose branches carry an ``inf`` literal, or an
  admission bit compared against a non-zero constant.
- ``staging-ring``: a write to a staging-ring / accumulator scratch
  ref in ``raft_tpu/ops/*_pallas.py`` whose value contains an ``inf``
  literal or a non-sentinel huge-float fill.
- ``scratch-budget``: a ``scratch_shapes=`` keyword in the fused scan
  / hop kernel modules that does not route through
  ``ops.vmem_budget`` (``fused_scan_scratch`` / ``hop_scratch``; the
  legacy non-fused ``_scratch_shapes`` helper is also accepted).
"""

from __future__ import annotations

import ast
from typing import List, Optional

from scripts.graftlint.core import (
    Diagnostic,
    Project,
    contains,
    register,
    terminal_name,
)

_ID_EXACT = {"outi", "alli", "best_i", "neighbors", "ti", "gi"}
_DOT_CALLS = {"dot", "dot_general", "matmul", "einsum"}

#: modules whose kernels feed the windowed one-hot merge: their scratch
#: MUST be sized by the shared VMEM-budget helpers
_FUSED_MODULES = {
    "raft_tpu/ops/pq_group_scan_pallas.py",
    "raft_tpu/ops/pq_code_scan_pallas.py",
    "raft_tpu/ops/cagra_hop_pallas.py",
}
_SCRATCH_HELPERS = {"fused_scan_scratch", "hop_scratch",
                    "_scratch_shapes"}
_ACC_SENTINEL = 3.0e38


def _ringish(name: str) -> bool:
    n = name.lower()
    return (n.startswith("stg") or n.startswith("acc")
            or "ring" in n or "staging" in n)


def _ring_target(node: ast.AST) -> bool:
    """True for a subscripted staging-ring / accumulator scratch ref
    (``stg_v[...]``, ``acc_i[:]``, ``stg[0][:]``)."""
    if not isinstance(node, ast.Subscript):
        return False
    while isinstance(node, ast.Subscript):
        node = node.value
    return isinstance(node, ast.Name) and _ringish(node.id)


def _is_rogue_sentinel(node: ast.AST) -> bool:
    """A huge float literal that is not the shared ``_ACC_WORST``."""
    return (isinstance(node, ast.Constant)
            and isinstance(node.value, float)
            and node.value == node.value
            and abs(node.value) != float("inf")
            and abs(node.value) >= 1e30
            and abs(node.value) != _ACC_SENTINEL)


def _idish(name: str) -> bool:
    n = name.lower()
    return ("indices" in n or n in _ID_EXACT or n == "ids"
            or n.endswith("_ids") or n.startswith("ids_"))


def _idish_expr(node: ast.AST) -> Optional[str]:
    """The id-ish identifier an expression reads, if any (follows
    attribute/subscript bases: ``index.list_indices[0] == -1``)."""
    while isinstance(node, ast.Subscript):
        node = node.value
    if isinstance(node, ast.Attribute) and _idish(node.attr):
        return node.attr
    if isinstance(node, ast.Name) and _idish(node.id):
        return node.id
    return None


def _admish(name: str) -> bool:
    n = name.lower()
    return (n == "adm" or n == "admission" or n.startswith("adm_")
            or n.endswith("_adm") or "admission" in n)


def _admish_expr(node: ast.AST) -> bool:
    """True when an expression reads an admission-bit buffer (follows
    subscript/attribute bases: ``adm_ref[0]``, ``st.adm[:, None]``)."""
    while isinstance(node, ast.Subscript):
        node = node.value
    if isinstance(node, ast.Attribute):
        return _admish(node.attr)
    return isinstance(node, ast.Name) and _admish(node.id)


_SELECT_CALLS = {"where", "select", "select_n"}


def _is_minus_one(node: ast.AST) -> bool:
    return (isinstance(node, ast.UnaryOp)
            and isinstance(node.op, ast.USub)
            and isinstance(node.operand, ast.Constant)
            and node.operand.value == 1)


def _is_inf(node: ast.AST) -> bool:
    if isinstance(node, ast.Attribute) and node.attr == "inf":
        return True
    if isinstance(node, ast.Name) and node.id == "inf":
        return True
    if isinstance(node, ast.Constant) and isinstance(node.value, float):
        return node.value != node.value or abs(node.value) == float("inf")
    if (isinstance(node, ast.Call) and terminal_name(node.func) == "float"
            and node.args and isinstance(node.args[0], ast.Constant)
            and str(node.args[0].value).lower() in ("inf", "-inf",
                                                    "infinity")):
        return True
    return False


@register
class MaskSeamPass:
    name = "mask-seam"
    docs = {
        "mask-seam":
            "id arrays are masked with sign tests (tombstones are <= -2,"
            " not -1); Pallas one-hot merges need finite sentinels, "
            "never inf in a product",
        "admission-seam":
            "filtered-search admission bits fold into the validity "
            "mask and take the finite _ACC_WORST sentinel — never "
            "multiplied into distances, selected against inf, or "
            "compared to non-zero constants",
        "staging-ring":
            "windowed-merge staging rings hold the finite _ACC_WORST "
            "sentinel: no inf literals or rogue huge-float fills may "
            "reach a ring/accumulator scratch write",
        "scratch-budget":
            "fused scan/hop kernels size VMEM scratch through "
            "ops.vmem_budget helpers so the merge-window selector and "
            "the kernel agree on the footprint",
    }

    def run(self, project: Project) -> List[Diagnostic]:
        out: List[Diagnostic] = []
        for mod in project.walk("raft_tpu/"):
            pallas = (mod.rel.startswith("raft_tpu/ops/")
                      and mod.rel.endswith("_pallas.py"))
            fused_mod = mod.rel in _FUSED_MODULES
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.Compare):
                    self._check_compare(mod, node, out)
                if pallas and isinstance(node, (ast.Assign, ast.AugAssign)):
                    self._check_ring_write(mod, node, out)
                if fused_mod and isinstance(node, ast.Call):
                    self._check_scratch(mod, node, out)
                if pallas:
                    self._check_admission(mod, node, out)
                    if (isinstance(node, ast.BinOp)
                            and isinstance(node.op, (ast.Mult,
                                                     ast.MatMult))
                            and (contains(node.left, _is_inf)
                                 or contains(node.right, _is_inf))):
                        out.append(Diagnostic(
                            mod.rel, node.lineno, "mask-seam",
                            "inf literal flows into a product — IEEE "
                            "0*inf=NaN poisons the one-hot merge; use "
                            "the finite 3.0e38 sentinel (_ACC_WORST)"))
                    elif (isinstance(node, ast.Call)
                          and terminal_name(node.func) in _DOT_CALLS
                          and any(contains(a, _is_inf)
                                  for a in node.args)):
                        out.append(Diagnostic(
                            mod.rel, node.lineno, "mask-seam",
                            "inf literal flows into a dot/matmul — IEEE "
                            "0*inf=NaN poisons the one-hot merge; use "
                            "the finite 3.0e38 sentinel (_ACC_WORST)"))
        return out

    def _check_compare(self, mod, node: ast.Compare,
                       out: List[Diagnostic]) -> None:
        sides = [node.left] + list(node.comparators)
        ops_ok = all(isinstance(op, (ast.Eq, ast.NotEq)) for op in node.ops)
        if not ops_ok:
            return
        has_minus_one = any(_is_minus_one(s) for s in sides)
        if not has_minus_one:
            return
        for s in sides:
            name = _idish_expr(s)
            if name is not None:
                out.append(Diagnostic(
                    mod.rel, node.lineno, "mask-seam",
                    f"'{name} == -1' misses tombstones (encoded <= -2) "
                    f"— mask with a sign test (< 0 / >= 0) or clamp "
                    f"through grouped.finalize_topk first"))
                return

    def _check_admission(self, mod, node: ast.AST,
                         out: List[Diagnostic]) -> None:
        # admission bit multiplied (or matmul'd/dotted) into a value:
        # a rejected candidate becomes distance 0 — the BEST hit — and
        # any inf partner NaN-poisons the row.  The bit is a mask, not
        # a scale factor.
        if (isinstance(node, ast.BinOp)
                and isinstance(node.op, (ast.Mult, ast.MatMult))
                and (contains(node.left, _admish_expr)
                     or contains(node.right, _admish_expr))):
            out.append(Diagnostic(
                mod.rel, node.lineno, "admission-seam",
                "admission bit used as a product operand — a rejected "
                "candidate would score 0 (the best distance!) instead "
                "of worst; fold it into the validity mask (invalid | "
                "(adm == 0)) so it takes the finite _ACC_WORST "
                "sentinel"))
            return
        if (isinstance(node, ast.Call)
                and terminal_name(node.func) in _DOT_CALLS
                and any(contains(a, _admish_expr) for a in node.args)):
            out.append(Diagnostic(
                mod.rel, node.lineno, "admission-seam",
                "admission bits flow into a dot/matmul — fold them "
                "into the validity mask and the finite _ACC_WORST "
                "sentinel, never into an accumulator product"))
            return
        # where/select on an admission condition with an inf branch:
        # the folded value must be the finite sentinel
        if (isinstance(node, ast.Call)
                and terminal_name(node.func) in _SELECT_CALLS
                and node.args
                and contains(node.args[0], _admish_expr)
                and any(contains(a, _is_inf) for a in node.args[1:])):
            out.append(Diagnostic(
                mod.rel, node.lineno, "admission-seam",
                "admission select folds rejected candidates to inf — "
                "the windowed one-hot merge multiplies masked rows "
                "(0*inf=NaN); fold to the finite 3.0e38 sentinel "
                "(_ACC_WORST) instead"))
            return
        # adm == 1 (or any non-zero constant): the unpack contract is
        # only 0 vs non-zero — test the zero side
        if isinstance(node, ast.Compare):
            sides = [node.left] + list(node.comparators)
            if not any(_admish_expr(s) for s in sides):
                return
            for s in sides:
                if (isinstance(s, ast.Constant)
                        and isinstance(s.value, (int, float))
                        and not isinstance(s.value, bool)
                        and s.value != 0):
                    out.append(Diagnostic(
                        mod.rel, node.lineno, "admission-seam",
                        "admission bit compared against a non-zero "
                        "constant — the unpack contract is 0 vs "
                        "non-zero; test '== 0' / '> 0' so a widened "
                        "encoding stays correct"))
                    return

    def _check_ring_write(self, mod, node, out: List[Diagnostic]) -> None:
        targets = (node.targets if isinstance(node, ast.Assign)
                   else [node.target])
        if not any(_ring_target(t) for t in targets):
            return
        if contains(node.value, _is_inf):
            out.append(Diagnostic(
                mod.rel, node.lineno, "staging-ring",
                "inf written into a staging-ring/accumulator scratch — "
                "the next windowed flush multiplies ring rows into the "
                "one-hot merge (0*inf=NaN); fill with the finite "
                "_ACC_WORST sentinel"))
        elif contains(node.value, _is_rogue_sentinel):
            out.append(Diagnostic(
                mod.rel, node.lineno, "staging-ring",
                "non-sentinel huge-float fill at a staging-ring write — "
                "uncovered ring slots must hold exactly _ACC_WORST "
                "(3.0e38) so merge liveness tests (< _ACC_WORST/2) and "
                "the epilogue agree"))

    def _check_scratch(self, mod, node: ast.Call,
                       out: List[Diagnostic]) -> None:
        for kw in node.keywords:
            if kw.arg != "scratch_shapes":
                continue
            routed = contains(
                kw.value,
                lambda n: (isinstance(n, ast.Call)
                           and terminal_name(n.func) in _SCRATCH_HELPERS))
            if not routed:
                out.append(Diagnostic(
                    mod.rel, kw.value.lineno, "scratch-budget",
                    "inline scratch_shapes in a fused kernel module — "
                    "size scratch through ops.vmem_budget "
                    "(fused_scan_scratch / hop_scratch) so the "
                    "merge-window selector and the kernel lowering "
                    "agree on the VMEM footprint"))
