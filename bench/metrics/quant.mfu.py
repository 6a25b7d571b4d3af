"""FLOP one block needs (flops.quantize_block_flops) over quant_block_s over the bf16 peak."""

from lib import readers

read = readers.quant_mfu
