"""Round program layer, two-hop phase: device ms per traced round in ops under
the ``ne_two_hop`` scope (the candidate scan, its ``while`` op's own time
included, the quota split, the keep mask; not its nested ``ne_sync``),
averaged over the devices.  Rounds cells, where it moves ``round_s``."""
from program_trace import two_hop_ms as read  # noqa: F401
