"""The numbers that decide ``correct``, each with its limit.

Every number counts entries that differ from the plain reference
(``reference.py``) run on the same edges and seed.  The reference
reproduces the program's rounds edge for edge, so each limit is 0: an
exact comparison (PERF.md gives the readings of sound runs, of the
control and of the planted faults that these were set from).
"""
from __future__ import annotations

import numpy as np

LIMITS = {"edges_off": 0, "replicas_off": 0, "counts_off": 0,
          "degree_off": 0, "remaining_off": 0, "rounds_off": 0,
          "stats_off": 0, "artifact_off": 0}


def off(a, b) -> int:
    """Entries that differ (all of them where the shapes differ)."""
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return int(max(a.size, b.size))
    return int(np.count_nonzero(a != b))


def job_numbers(got: dict, want, want_stats: dict, art=None,
                edges=None) -> dict:
    """A finished job: ``got`` holds ``edge_part``, ``vparts``,
    ``edges_per_part``, ``rounds`` and the reported ``rf``/``eb``/``vb``;
    ``art`` is the artifact as read back, where there is one."""
    nums = {"edges_off": off(got["edge_part"], want.edge_part),
            "replicas_off": off(got["vparts"], want.vparts),
            "counts_off": off(got["edges_per_part"], want.edges_per_part),
            "rounds_off": abs(got["rounds"] - want.rounds),
            "stats_off": sum(int(got[k] != want_stats[k])
                             for k in ("rf", "eb", "vb"))}
    if art is not None:
        nums["artifact_off"] = (off(art.edge_part, want.edge_part)
                                + off(art.vparts, want.vparts)
                                + off(art.edges, edges)
                                + off(art.edges_per_part,
                                      want.edges_per_part)
                                + abs(art.rounds - want.rounds))
    return nums


def state_numbers(got: dict, want) -> dict:
    """The round state after the window's rounds."""
    return {"edges_off": off(got["edge_part"], want.edge_part),
            "replicas_off": off(got["vparts"], want.vparts),
            "degree_off": off(got["degree_rest"], want.degree_rest),
            "counts_off": off(got["edges_per_part"], want.edges_per_part),
            "remaining_off": abs(got["remaining"] - want.remaining),
            "rounds_off": abs(got["rounds"] - want.rounds)}


def worst(answers: list) -> dict:
    return {k: max(a[k] for a in answers) for k in answers[0]}


def correct(nums: dict) -> bool:
    return all(v <= LIMITS[k] for k, v in nums.items())
