"""Exchange layer: MB per traced round the program says its sync sends, the
``sync_payload_bytes`` argument of its ``round`` spans.  Four-chip rounds
cells, where it moves ``round_s``."""
from exchange import exchange_mb as read  # noqa: F401
