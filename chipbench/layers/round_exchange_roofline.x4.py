"""Exchange layer: the least bytes a device must send per round
(``exchange.exchange_min_bytes``) at the chip's ICI bandwidth, as a share
of the exchange's device time, in %.  Four-chip rounds cells, where it
moves ``round_s``."""
from exchange import exchange_roofline_pct as read  # noqa: F401
