"""Round program layer, one-hop phase: device ms per traced round in ops under
the ``ne_one_hop`` scope (the per-edge claim gathers, the (P,) histogram,
the ``edge_part`` update) over each chip's quarter of the edges, averaged
over the devices.  Four-chip rounds cells, where it moves ``round_s``."""
from program_trace import one_hop_ms as read  # noqa: F401
