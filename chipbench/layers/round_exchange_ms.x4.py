"""Exchange layer (the round's collectives over ICI, under ``ne_exchange``):
device self ms per traced round, averaged over the devices.  Four-chip
rounds cells, where it moves ``round_s``."""
from exchange import exchange_ms as read  # noqa: F401
