"""VMEM-gate pass: every Pallas kernel behind a fit gate, gates re-checked.

TPU Pallas kernels pull their whole working set into VMEM (~16 MiB/core);
a shape that overflows it fails at compile time in the middle of a serving
run.  The repo's convention is that ``pl.pallas_call`` is never reached
except through a dispatcher that first consults a *fit gate* — a pure
byte-formula function named ``*_tq`` (returns a tile size or None) or
``*_fits_vmem`` (returns bool) in ``kernels/ops.py`` — and falls back to
the XLA reference path otherwise.

Two rules:

``vmem-ungated-pallas-call``
    A ``pl.pallas_call`` whose enclosing function is not *dominated* by a
    gate: neither the function itself nor any transitive caller (≤ 4 call
    edges, simple-name call graph) calls a recognized gate.  Kernel-body
    functions (taken as first argument by ``pallas_call``) inherit their
    dispatcher's gate through the caller walk.

``vmem-gate-overflow`` (runtime check, needs jax importable)
    Each gate's byte formula is re-evaluated against every shipped
    ``configs/*`` architecture shape — all (p, bsz, dtype) combinations the
    solvers can produce, and all (page_size, kv_pages, groups, head_dim)
    the serving engine ships.  The check asserts *consistency*, not fit:
    when a gate approves (returns a tile / True) the formula's bytes must
    be ≤ budget, and when it declines the minimum-tile bytes must exceed
    budget — a gate that approves an overflowing shape, or that can never
    decline, is a bug in the formula.  mixtral-scale d_ff legitimately
    makes ``fused_iteration_tq`` return None; that is a *decision*, not a
    finding.
"""

from __future__ import annotations

import ast

from repro.analysis.framework import Finding, Project, call_name, dotted_name, rule

__all__ = ["check_vmem_gates", "check_gate_formulas"]

# VMEM budget the gates enforce: kernels/ops._VMEM_BUDGET, restated (the
# 96 MiB scoped limit asked of Mosaic less 16 MiB of headroom).
_BUDGET = 80 * 1024 * 1024


def _is_gate_name(name: str) -> bool:
    return name.endswith("_tq") or name.endswith("_fits_vmem")


def _pallas_calls(ctx):
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            nm = dotted_name(node.func)
            if nm.endswith("pallas_call") or call_name(node) == "pallas_call":
                yield node


@rule(
    "vmem-ungated-pallas-call",
    "pl.pallas_call not dominated by a *_tq / *_fits_vmem fit gate",
)
def check_vmem_gates(project: Project):
    findings = []
    for ctx in project.files:
        if "kernels" not in ctx.rel.split("/"):
            continue
        for node in _pallas_calls(ctx):
            fn = ctx.enclosing_function(node)
            while isinstance(fn, ast.Lambda):
                fn = ctx.enclosing_function(fn)
            if fn is None:
                findings.append(
                    Finding(
                        rule="vmem-ungated-pallas-call",
                        path=ctx.rel,
                        line=node.lineno,
                        message="pl.pallas_call at module level cannot be gated",
                        suggestion="wrap in a dispatcher that checks a fit gate",
                    )
                )
                continue
            if _dominated_by_gate(project, fn.name):
                continue
            findings.append(
                Finding(
                    rule="vmem-ungated-pallas-call",
                    path=ctx.rel,
                    line=node.lineno,
                    message=(
                        f"`{fn.name}` reaches pl.pallas_call but neither it "
                        "nor any caller (≤4 edges) consults a *_tq/"
                        "*_fits_vmem gate; an oversized shape will fail at "
                        "compile time instead of falling back"
                    ),
                    suggestion=(
                        "route the call through a dispatcher in kernels/ops.py "
                        "that checks a fit gate and falls back to the XLA "
                        "reference path"
                    ),
                )
            )
    return findings


def _dominated_by_gate(project: Project, fn_name: str) -> bool:
    """``fn_name`` or any transitive caller calls a recognized gate."""
    infos = [f for f in project.functions if f.name == fn_name]
    for info in infos:
        if any(_is_gate_name(c) for c in info.calls):
            return True
    for caller in project.transitive_callers(fn_name, depth=4):
        if any(_is_gate_name(c) for c in caller.calls):
            return True
    return False


# --------------------------- formula re-evaluation ---------------------------

def _iter_solver_shapes():
    """(p, bsz, dtype) combinations the quantization solvers can produce:
    every weight-matrix row count across shipped archs × the default and
    max block sizes × both matmul dtypes."""
    from repro.configs import base as cfgs

    ps = set()
    for arch in cfgs.list_configs():
        c = cfgs.get_config(arch)
        ps.update(
            x
            for x in (
                c.d_model,
                getattr(c, "d_ff", 0),
                getattr(c, "moe_ff", 0) or 0,
                getattr(c, "d_inner", 0) or 0,
            )
            if x
        )
    # Pallas pads p to a multiple of 8 lanes; gates see the padded value.
    ps = {(-(-p // 8)) * 8 for p in ps}
    for p in sorted(ps):
        for bsz in (128, 256):  # solver/outlier and quantease defaults
            for dtype in ("float32", "bfloat16"):
                yield p, bsz, dtype


def _iter_attention_shapes():
    """(page_size, kv_pages, groups, head_dim, kv_bytes, quantized) combos
    the paged serving engine ships."""
    from repro.configs import base as cfgs

    for arch in cfgs.list_configs():
        c = cfgs.get_config(arch)
        g = max(1, c.n_heads // max(1, c.n_kv_heads))
        for psz in (16, 32):
            for kvp in (16, 64, 256):
                for kv_bytes, quantized in ((2, False), (2, True), (4, False)):
                    yield psz, kvp, g, c.hd, kv_bytes, quantized


def check_gate_formulas() -> list:
    """Re-evaluate every fit gate against all shipped config shapes.

    Returns findings (empty when all gates are self-consistent).  Needs a
    working jax/repro import; the CLI runs it unless --no-runtime.
    """
    from repro.kernels import ops

    findings = []

    def flag(gate, msg):
        findings.append(
            Finding(
                rule="vmem-gate-overflow",
                path="src/repro/kernels/ops.py",
                line=1,
                message=f"{gate}: {msg}",
                suggestion="fix the gate's byte formula in kernels/ops.py",
            )
        )

    # Pipelined blocks count twice (double buffering), scratch and
    # in-kernel temporaries once.
    def fused_bytes(p_pad, bsz, dtype, tq):
        cd = 2 if dtype == "bfloat16" else 4
        blocks = p_pad * tq * 4 + bsz * p_pad * cd + bsz * bsz * 4 + 7 * bsz * tq * 4
        temps = p_pad * tq * 4 + (p_pad * tq * 2 if cd == 2 else 0)
        return 2 * blocks + temps

    def outlier_bytes(p_pad, bsz, dtype, tq):
        cd = 2 if dtype == "bfloat16" else 4
        blocks = (
            2 * p_pad * tq * 4 + 2 * bsz * p_pad * cd + bsz * bsz * 4
            + 8 * bsz * tq * 4
        )
        temps = 2 * p_pad * tq * 4 + (p_pad * tq * 2 if cd == 2 else 0)
        return 2 * blocks + temps

    for p, bsz, dtype in _iter_solver_shapes():
        for gate_name, bytes_fn in (
            ("fused_iteration_tq", fused_bytes),
            ("outlier_iteration_tq", outlier_bytes),
        ):
            gate = getattr(ops, gate_name, None)
            if gate is None:
                flag(gate_name, "gate missing from kernels/ops.py")
                continue
            tq = gate(p, bsz, matmul_dtype=dtype)
            shape = f"p={p} bsz={bsz} dtype={dtype}"
            if tq is not None:
                if bytes_fn(p, bsz, dtype, tq) > _BUDGET:
                    flag(
                        gate_name,
                        f"approved tq={tq} at {shape} but the working set "
                        f"is {bytes_fn(p, bsz, dtype, tq)} B > {_BUDGET} B",
                    )
                if tq < 128 or tq & (tq - 1):
                    flag(gate_name, f"returned non-power-of-two tile {tq} at {shape}")
            else:
                if bytes_fn(p, bsz, dtype, 128) <= _BUDGET:
                    flag(
                        gate_name,
                        f"declined {shape} although the minimum tile (128) "
                        "fits the budget — fallback taken needlessly",
                    )

    sweep_gate = getattr(ops, "block_sweep_tq", None)
    if sweep_gate is None:
        flag("block_sweep_tq", "gate missing from kernels/ops.py")
    else:
        # The sweep tiles q, so evaluate every shipped q (row count) too —
        # and the gate must approve every realistic block size (the sweep
        # working set is tiny; a decline means the formula broke).
        for q, bsz, _ in _iter_solver_shapes():
            tq = sweep_gate(q, bsz)
            # 6 (bsz × tq) fp32 tiles + the (bsz × bsz) Σ̃ block.
            if tq is not None:
                got = 6 * bsz * tq * 4 + bsz * bsz * 4
                if got > _BUDGET:
                    flag(
                        "block_sweep_tq",
                        f"approved tq={tq} at q={q} bsz={bsz} but working "
                        f"set is {got} B > {_BUDGET} B",
                    )
            elif 6 * bsz * 128 * 4 + bsz * bsz * 4 <= _BUDGET:
                flag(
                    "block_sweep_tq",
                    f"declined q={q} bsz={bsz} although the minimum tile fits",
                )

    dm_gate = getattr(ops, "dequant_matmul_fits_vmem", None)
    if dm_gate is None:
        flag("dequant_matmul_fits_vmem", "gate missing from kernels/ops.py")
    else:
        for p, _, _ in _iter_solver_shapes():
            for m in (1, 8, 128, 1024):
                for q in (1024, 4096, 16384):
                    ok = dm_gate(m, q, p)
                    tm, tq, tk = min(128, m), min(128, q), min(512, p)
                    tile = tm * tk * 4 + tq * tk + 2 * tq * tk * 4 + tm * tq * 4
                    if ok and tile > _BUDGET:
                        flag(
                            "dequant_matmul_fits_vmem",
                            f"approved m={m} q={q} p={p} but tile working "
                            f"set is {tile} B > {_BUDGET} B",
                        )
                    if not ok and tile <= _BUDGET:
                        flag(
                            "dequant_matmul_fits_vmem",
                            f"declined m={m} q={q} p={p} although {tile} B fits",
                        )

    pa_gate = getattr(ops, "paged_attention_fits_vmem", None)
    if pa_gate is None:
        flag("paged_attention_fits_vmem", "gate missing from kernels/ops.py")
    else:
        for psz, kvp, g, hd, kv_bytes, quantized in _iter_attention_shapes():
            ok = pa_gate(psz, kvp, g, hd, kv_bytes=kv_bytes, quantized=quantized)
            pages = 2 * 2 * psz * kvp * hd * kv_bytes
            if quantized:
                pages += 2 * 2 * psz * kvp * 4
            fixed = kvp * g * hd * 4 * 3 + kvp * g * 4 * 2
            total = pages + fixed
            if ok and total > _BUDGET:
                flag(
                    "paged_attention_fits_vmem",
                    f"approved page_size={psz} kv_pages={kvp} g={g} hd={hd} "
                    f"kv_bytes={kv_bytes} quantized={quantized} but working "
                    f"set is {total} B > {_BUDGET} B",
                )
            if not ok and total <= _BUDGET:
                flag(
                    "paged_attention_fits_vmem",
                    f"declined page_size={psz} kv_pages={kvp} g={g} hd={hd} "
                    f"although {total} B fits the budget",
                )
    return findings


@rule(
    "vmem-gate-overflow",
    "fit-gate byte formula inconsistent with shipped configs/* shapes "
    "(runtime check; skipped under --no-runtime)",
)
def _check_formulas_rule(project: Project):
    if not project.runtime_checks:
        return []
    # Only meaningful when analyzing this repo (the gates must be importable).
    if not any(c.rel.endswith("kernels/ops.py") for c in project.files):
        return []
    try:
        return check_gate_formulas()
    except ImportError:
        return []
