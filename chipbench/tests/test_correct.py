"""``correct`` comes out true for the program as it is, and false for the
control and for each fault a cell can have, planted in the program
underneath a run that skips only the harness's look for a chip."""
import jax
import jax.numpy as jnp
import pytest

import compare
import control
import minibench
import run


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("bench")
    g = minibench.small_config("graph500_s16_p16", scale=10)
    g["ne"] = dict(g["ne"], max_rounds=200)
    g4 = dict(minibench.small_config("graph500_s20_p16", scale=10),
              name="g4", num_devices=4)
    g4["ne"] = g["ne"]
    return minibench.make_root(tmp, [("g.jobs", g, "jobs", 1),
                                     ("g.rounds", g, "rounds", 1),
                                     ("g4.rounds", g4, "rounds", 4)])


@pytest.fixture(autouse=True)
def fresh_programs():
    # a planted fault must be traced into the round program again
    jax.clear_caches()
    yield
    jax.clear_caches()


def cell(root, workload, seed=2**31 + 9):
    return run.run_cell(root, workload, seed, 0.5, False, require_tpu=False)


@pytest.mark.parametrize("workload", ["g.jobs", "g.rounds", "g4.rounds"])
def test_sound_run_is_correct(root, workload):
    out = cell(root, workload)
    assert out["correct"] and out["failed"] == 0
    assert all(c["value"] == 0 for c in out["checks"].values())
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("workload,rounds", [("g.jobs", None),
                                             ("g.rounds", 6),
                                             ("g4.rounds", 6)])
def test_control_is_not_correct(root, workload, rounds):
    for seed in (1, 2, 2**31 + 3):
        nums = control.control_numbers(root, workload, seed, rounds)
        assert nums["edges_off"] > 0 and not compare.correct(nums)


def unchanged_step(monkeypatch):
    from repro.runtime.driver import PartitionDriver

    monkeypatch.setattr(PartitionDriver, "step", lambda self: self.rounds)


def half_batch(monkeypatch):
    from repro.runtime import driver

    step = driver.spmd_round_step

    def halved(cfg, limit, n, mesh, u, v, mask, state):
        keep = jnp.arange(mask.shape[1]) < mask.shape[1] // 2
        return step(cfg, limit, n, mesh, u, v, mask & keep[None, :], state)

    monkeypatch.setattr(driver, "spmd_round_step", halved)


def no_exchange(monkeypatch):
    from repro.dist import partitioner_sm

    monkeypatch.setattr(partitioner_sm.jax.lax, "psum", lambda x, axis: x)
    monkeypatch.setattr(partitioner_sm.jax.lax, "all_gather",
                        lambda x, axis: x[None])


def altered_answer(monkeypatch):
    from repro.runtime import driver

    step = driver.spmd_round_step
    finalize = driver.PartitionDriver.finalize

    def altered_step(*args):
        st = step(*args)
        ep = st.edge_part
        return st._replace(edge_part=ep.at[0, 0].set((ep[0, 0] + 1) % 16))

    def altered_finalize(self):
        res = finalize(self)
        if self._result is res and not getattr(self, "_altered", False):
            res.edge_part[0] = (res.edge_part[0] + 1) % 16
            self._altered = True
        return res

    monkeypatch.setattr(driver, "spmd_round_step", altered_step)
    monkeypatch.setattr(driver.PartitionDriver, "finalize", altered_finalize)


FAULTS = {"unchanged_step": unchanged_step, "half_batch": half_batch,
          "no_exchange": no_exchange, "altered_answer": altered_answer}


@pytest.mark.parametrize("workload,fault", [
    ("g.jobs", "unchanged_step"), ("g.jobs", "half_batch"),
    ("g.jobs", "altered_answer"),
    ("g.rounds", "unchanged_step"), ("g.rounds", "half_batch"),
    ("g.rounds", "altered_answer"), ("g4.rounds", "no_exchange"),
    ("g4.rounds", "half_batch"), ("g4.rounds", "altered_answer")])
def test_fault_is_not_correct(root, monkeypatch, workload, fault):
    FAULTS[fault](monkeypatch)
    out = cell(root, workload)
    assert not out["correct"] and out["failed"] > 0
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
