"""The check that decides ``correct``, driven through a whole run at a size
a CPU holds: the cell's configuration cut to two layers and narrow widths,
two workers of three inner steps on 64-token sequences. The look for a chip
is skipped (``harness.run`` is called directly). The numbers compared are
the cell's own; their limits are set for this size, from its readings on
seeds 1, 2, 3 and 5 (a loss over 256 tokens a worker swings more than one
over 32,768): sound runs read at most 1.8e-4 (first step's loss), 3.7e-4
(any step's loss), 3.1e-4 (eval loss) and 0.012 (worst leaf); the control
reads 5.5e-4 or more on the first loss of the smollm cells and 0.09 or more
on the worst leaf of the Muon cells; half of the batch reads 0.0149 or more
on the losses and 0.057 or more on the worst leaf.

* a sound program passes;
* the control (the reference with fp8-rounded matrix operands in the
  program's place) fails;
* the timed path, broken underneath, fails: a round that returns its state
  unchanged, and inner steps that leave out half of each worker's batch and
  take the mean over the rest.
"""
from __future__ import annotations

import json
import os
import time

import pytest

from bench import check, harness
from bench.reference.common import follow_round0

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL_LIMITS = {"first_loss_gap": 3e-4, "loss_gap": 0.005, "eval_gap": 0.003,
                "grad_gap": 0.03, "change_gap": 0.03}
with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


def load(cell_name: str):
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        cell = {w["name"]: w for w in json.load(f)["workloads"]}[cell_name]
    with open(os.path.join(BENCH, "configs", f"{cell['config']}.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", f"{cell['traffic']}.json")) as f:
        traffic = json.load(f)
    if config["family"] == "dense":
        config["model"].update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                               head_dim=16, d_ff=128, vocab=256)
    else:
        config["model"].update(n_layers=2, d_model=64, ssm_state=16, ssm_head_dim=16,
                               ssm_chunk=16, vocab=256)
    traffic.update(workers=min(traffic["workers"], 2), sync_interval=3,
                   batch_per_worker=2, seq_len=64)
    compared = check.load_limits(BENCH, cell_name)
    return cell, config, traffic, {k: v for k, v in SMALL_LIMITS.items() if k in compared}


def run(cell_name: str, seed: int = 5) -> dict:
    cell, config, traffic, limits = load(cell_name)
    return harness.run(cell, config, traffic, seed, 0.5, False, [], limits,
                       time.perf_counter())


@pytest.mark.parametrize("cell_name", CELLS)
def test_sound_program_is_correct(cell_name):
    res = run(cell_name)
    assert res["correct"], res["check"]
    assert res["rounds"] >= 1 and res["tokens"] > 0


@pytest.mark.parametrize("cell_name", CELLS)
def test_control_is_not_correct(cell_name):
    _, config, traffic, limits = load(cell_name)
    family = harness.load_module("reference", config["family"])
    rt = harness.reference_traffic(traffic)
    ref = follow_round0(family, config["model"], rt, 5)
    control = follow_round0(family, config["model"], rt, 5, numerics="fp8")
    correct, table = check.judge(check.readings(control, ref), limits)
    assert not correct, table


@pytest.mark.parametrize("cell_name", CELLS)
def test_state_left_unchanged_is_not_correct(cell_name, monkeypatch):
    import repro.engine.engine as engine_mod

    real = engine_mod.diloco_round

    def unchanged(model, dcfg, opt, state, batches, **kw):
        _, info = real(model, dcfg, opt, state, batches, **kw)
        return state, info

    monkeypatch.setattr(engine_mod, "diloco_round", unchanged)
    res = run(cell_name)
    assert not res["correct"], res["check"]


@pytest.mark.parametrize("cell_name", CELLS)
def test_half_batch_is_not_correct(cell_name, monkeypatch):
    import jax

    import repro.core.diloco as diloco

    real = diloco.inner_step

    def half(model, opt, state, batch, **kw):
        rows = jax.tree.leaves(batch)[0].shape[1] // 2
        return real(model, opt, state, jax.tree.map(lambda b: b[:, :rows], batch), **kw)

    monkeypatch.setattr(diloco, "inner_step", half)
    res = run(cell_name)
    assert not res["correct"], res["check"]
