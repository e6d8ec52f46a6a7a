"""Kernels layer (the round's XLA sorts, scatters and gathers): the least
bytes a round must move per device (chipbench/roofline.py) at the device's
peak HBM bandwidth, as a share of the device-busy time per round, in %.
Four-chip rounds cells, where it moves ``round_s``."""
from per_round import hbm_roofline_pct as read  # noqa: F401
