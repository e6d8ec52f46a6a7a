"""Round program layer, selection phase: device ms per traced round in ops
under the ``ne_select`` scope (claims, the per-partition ``top_k``, the
packed map's unpacking), averaged over the devices.  Jobs cells, where it
moves ``partition_s``."""
from program_trace import select_ms as read  # noqa: F401
