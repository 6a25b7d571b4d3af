"""Least time of the window's CD sweeps (flops.cd_iteration at the bf16 peak) over their kernels' time (trace)."""

from lib import readers

read = readers.cd_roofline
