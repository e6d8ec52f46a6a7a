"""Driver layer (runtime/driver): the share of the traced rounds' window in
which no operation ran on the device, in % — host work between rounds
(the ``done`` read, dispatch) shows here.  Four-chip rounds cells, where it
moves ``round_s``."""
from per_round import idle_pct as read  # noqa: F401
