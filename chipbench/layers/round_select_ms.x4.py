"""Round program layer, selection phase: device ms per traced round in ops
under the ``ne_select`` scope (claims, the per-partition ``top_k``),
replicated on each chip, averaged over the devices.  Four-chip rounds
cells, where it moves ``round_s``."""
from program_trace import select_ms as read  # noqa: F401
