"""Round program layer, sync phase: device ms per traced round in ops under the
``ne_sync`` scope (both ``_apply_alloc`` calls: the local (P,) and D_rest
deltas, the replica-map delta and the collectives), averaged over the
devices.  Rounds cells, where it moves ``round_s``."""
from program_trace import sync_ms as read  # noqa: F401
