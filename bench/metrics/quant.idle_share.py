"""Share of the traced window in which no device operation ran."""

from lib import readers

read = readers.idle_share
