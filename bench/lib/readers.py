"""Per-layer quantities shared by several metric files.  Each takes the
run (``harness.RunResult``) and returns a number, or None where the run
holds nothing to read it from.  Shares are in percent."""

from __future__ import annotations


# Kernel ops are named after the Pallas wrapper that launches them
# ("vmap_jit_quantease_fused_iteration_pallas__" for the fused CD iteration).
CD_KERNELS = ("quantease",)


def idle_share(run):
    tr = run.trace
    if tr is None or not tr.ops:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s())


# --- quantize -------------------------------------------------------------------

def cd_share(run):
    tr = run.trace
    if tr is None or not tr.kernel_events(CD_KERNELS):
        return None
    return 100.0 * tr.kernel_s(CD_KERNELS) / tr.busy_s()


def cd_roofline(run):
    tr = run.trace
    events = tr.kernel_events(CD_KERNELS) if tr is not None else []
    n = run.info["n_blocks"]
    if not events or len(events) != n * run.info["cd_kernel_calls_per_block"] * len(tr.ops):
        return None  # the CD sweeps did not all run on the fused kernel
    return 100.0 * n * run.info["cd_least_s_per_block"] / tr.kernel_s(CD_KERNELS)


def capture_device_s(run):
    tr = run.trace
    if tr is None or not tr.ops:
        return None
    return (tr.busy_s() - tr.kernel_s(CD_KERNELS)) / run.info["n_blocks"]


def quant_mfu(run):
    return 100.0 * run.info["flops_per_block"] / run.info["block_s"] / run.peaks["bf16_flops"]
