"""Driver layer (runtime/driver): host ms per traced round on the round's
critical path, from the program's spans: ``round`` less its ``round_wait``
child, plus ``done_read``.  Jobs cells, where it moves ``partition_s``."""
from program_trace import host_ms as read  # noqa: F401
