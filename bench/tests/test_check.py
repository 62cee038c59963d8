"""The output check: a rehearsal of whole runs on the CPU at a tiny fleet
(the harness's look for a chip skipped), the control failing it, and the
timed path broken underneath failing it.

The program runs its CPU paths here; the Pallas learner in interpret mode
in the rehearsal of the fixed fleet."""

import json
import time

import jax
import numpy as np
import pytest

from bench import check, control, harness, traffic
from bench.reference.session import Deployment

TINY = {"magpie8_1024.steady": ({"sessions": 16, "chunk": 16}, 4),
        "paper2d_64.churn": ({"sessions": 32, "chunk": 32}, 22)}


def run_cell(cell, seed, monkeypatch=None):
    overrides, seconds = TINY[cell]
    return harness.run(["--workload", cell, "--seed", str(seed),
                        "--seconds", str(seconds), "--trace", "0"],
                       t_start=time.perf_counter(), require_tpu=False,
                       overrides=overrides)


def failed(result) -> list:
    return [k for k, c in result["checks"].items()
            if not harness.holds(c)]


def test_rehearsal_fixed_fleet_interpret(monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    result = run_cell("magpie8_1024.steady", 4000000001)
    assert result["correct"], result["checks"]
    assert result["device"]["platform"] == "cpu"
    assert list(result)[-1] == "checks"


def test_rehearsal_churn():
    result = run_cell("paper2d_64.churn", 4000000002)
    assert result["correct"], result["checks"]
    assert "final_gap" in result["checks"]
    assert result["metrics"]["join_p90_s"]["value"] > 0


def _cell_outputs(cell, seed):
    """Program-shaped stand-ins for what a window of the cell gives for
    its 16 sampled sessions, drawn from ``seed`` (no program run): the
    steps, anchor and learner boundaries of a steady window of six rounds,
    or of a 32-step life under churn."""
    spec = json.load(open(harness.ROOT + "/BENCHMARK.json"))
    parts = harness.cell_files(spec, cell)
    config = parts["config"]
    dep = Deployment(config)
    fixed = parts["mix"]["turnover_share"] == 0
    steps, first = (70, 10) if fixed else (32, 3)
    anchor = steps - first
    # the boundaries of both spans: each round of advance(10), or each step
    snaps = [first, steps] if fixed else [1, 2, 3, 30, 31, 32]
    roster = traffic.Roster(config["workloads"], config["objectives"],
                            np.random.default_rng(seed))
    outputs = [{"join": {"workload": a.workload, "weights": a.weights,
                         "seed": a.seed},
                "idx": np.zeros((steps, dep.m)), "anchor": (anchor, None),
                "learner_steps": first,
                "snapshots": {t: None for t in snaps},
                **({} if fixed else {"final": None})}
               for a in roster.draw(harness.CHECK_SAMPLE)]
    return dep, outputs, parts["limits"]


@pytest.mark.parametrize("seed", [11, 12, 13])
@pytest.mark.parametrize("cell", list(TINY))
def test_control_fails(cell, seed):
    dep, outputs, limits = _cell_outputs(cell, seed)
    ctl = control.control_outputs(dep, outputs)
    nums = check.compare(dep, ctl)
    over = [k for k, v in nums.items()
            if not harness.holds({"value": v, "limit": limits[k]})]
    assert over, nums


def _unchanged_state(monkeypatch):
    import repro.core.episode as episode
    monkeypatch.setattr(episode, "_learn_scan",
                        lambda state, *a, **k: (state, None))


def _half_batch(monkeypatch):
    import repro.core.ddpg as ddpg
    gather = ddpg.gather_minibatches

    def half(data, idx):
        return gather(data, idx[:, :idx.shape[1] // 2])
    monkeypatch.setattr(ddpg, "gather_minibatches", half)


def _altered_config(monkeypatch):
    """The served configuration recorded one level off on knob 0, where
    the chunk program computes it."""
    import jax.numpy as jnp

    import repro.core.episode as episode
    maps_of = episode.jax_coord_maps

    def altered(space):
        maps = maps_of(space)
        top = space.specs[0].cardinality - 1

        def first(a):
            out = dict(maps[0](a))
            idx = out["idx"]
            out["idx"] = jnp.where(idx < top, idx + 1, idx - 1)
            return out
        return [first] + list(maps[1:])
    monkeypatch.setattr(episode, "jax_coord_maps", altered)


def _altered_metrics(monkeypatch):
    """The throughput the environment reports altered by 1%."""
    import functools

    import repro.envs.lustre_model as model
    build = model.build_lustre_fns

    @functools.lru_cache(maxsize=None)
    def altered(*args):
        init, step, perf = build(*args)

        def step2(params, state, action, eval_run):
            st, vec, cost = step(params, state, action, eval_run)
            return st, vec.at[10].multiply(1.01), cost
        return init, step2, perf
    monkeypatch.setattr(model, "build_lustre_fns", altered)


FAULTS = {"unchanged_state": _unchanged_state, "half_batch": _half_batch,
          "altered_config": _altered_config,
          "altered_metrics": _altered_metrics}


@pytest.mark.parametrize("fault", list(FAULTS))
@pytest.mark.parametrize("cell", list(TINY))
def test_broken_path_is_not_correct(cell, fault, monkeypatch):
    import repro.core.episode as episode
    monkeypatch.setattr(episode, "_EPISODE_CACHE", {})
    FAULTS[fault](monkeypatch)
    result = run_cell(cell, 4000000100)
    assert not result["correct"]
    assert failed(result), result["checks"]
