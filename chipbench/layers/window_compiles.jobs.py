"""Driver layer (runtime/driver): JAX compile events inside the traced job,
counted from the program's ``repro.compile`` marks; warm-up compiles every
program the window runs, so it should read 0.  Jobs cells, where it moves
``partition_s``."""
from program_trace import compiles as read  # noqa: F401
