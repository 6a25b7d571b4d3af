"""Share of device busy time in the quantease_cd kernels (trace)."""

from lib import readers

read = readers.cd_share
