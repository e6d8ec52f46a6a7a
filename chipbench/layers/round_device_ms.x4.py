"""Round program layer (dist/partitioner_sm.spmd_round_step): device-busy
milliseconds per traced round, averaged over the devices.  Four-chip rounds
cells, where it moves ``round_s``."""
from per_round import device_ms as read  # noqa: F401
