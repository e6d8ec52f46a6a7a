"""Round program layer, one-hop phase: device ms per traced round in ops under
the ``ne_one_hop`` scope (the per-edge claim gathers, the (P,) histogram,
the ``edge_part`` update), averaged over the devices.  Jobs cells, where it
moves ``partition_s``."""
from program_trace import one_hop_ms as read  # noqa: F401
