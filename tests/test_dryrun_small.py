"""Integration: the dry-run machinery on a small forced-device-count world.

Runs in a subprocess because XLA pins the device count at first
initialization — the main pytest process must keep its single CPU device.
"""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.configs import get_config, reduce_config
from repro.core.diloco import DiLoCoConfig
from repro.launch.mesh import make_debug_mesh
from repro.launch.steps import build_plans
from repro.roofline.hlo import collective_bytes_corrected

mesh = make_debug_mesh(data=2, model=2, pod=2)
out = {}
for arch, shape in [("smollm-135m", "train_4k"), ("mamba2-370m", "decode_32k"),
                    ("deepseek-moe-16b", "prefill_32k")]:
    cfg = reduce_config(get_config(arch))
    # shrink the shapes too: patch INPUT_SHAPES locally via small seq
    from repro.configs import base as cb
    cb.INPUT_SHAPES["train_4k"] = cb.InputShape("train_4k", 64, 8, "train")
    cb.INPUT_SHAPES["decode_32k"] = cb.InputShape("decode_32k", 64, 4, "decode")
    cb.INPUT_SHAPES["prefill_32k"] = cb.InputShape("prefill_32k", 64, 4, "prefill")
    plans = build_plans(cfg, shape, mesh, **(
        {"dcfg": DiLoCoConfig(n_workers=2, sync_interval=4)} if shape == "train_4k" else {}))
    for plan in plans:
        with jax.set_mesh(mesh):
            c = jax.jit(plan.fn, in_shardings=plan.in_shardings,
                        donate_argnums=plan.donate).lower(*plan.args).compile()
        coll = collective_bytes_corrected(c.as_text())
        rec = {"ok": True, "collective_total": coll["total"]}
        if plan.name in ("round_step", "superstep"):
            from repro.launch.dryrun import round_step_donation_report
            rec["donation"] = round_step_donation_report(
                plan.args[0], c.as_text(), c.memory_analysis(),
                mesh.devices.size)
        out[f"{arch}/{shape}/{plan.name}"] = rec

# no-pod regression config: on a single-pod mesh whose 'model' axis is wider
# than 'data', GSPMD used to propagate a 'model'-sharded layout onto the
# (unconstrained) output state even though the committed outer-state layout
# drops 'model' on TP-unfriendly archs — a layout mismatch that silently
# broke donation of the round/superstep outer state (the 16x16 production
# mesh hit exactly this). The plan fns now pin their outputs with
# with_sharding_constraint, so this config must alias like any other.
nopod = make_debug_mesh(data=2, model=4)
cfg = reduce_config(get_config("smollm-135m"))
plans = build_plans(cfg, "train_4k", nopod,
                   dcfg=DiLoCoConfig(n_workers=1, sync_interval=4))
for plan in plans:
    with jax.set_mesh(nopod):
        c = jax.jit(plan.fn, in_shardings=plan.in_shardings,
                    donate_argnums=plan.donate).lower(*plan.args).compile()
    rec = {"ok": True}
    if plan.name in ("round_step", "superstep"):
        from repro.launch.dryrun import round_step_donation_report
        rec["donation"] = round_step_donation_report(
            plan.args[0], c.as_text(), c.memory_analysis(),
            nopod.devices.size)
    out[f"nopod/{plan.name}"] = rec
print(json.dumps(out))
"""


@pytest.mark.slow
def test_dryrun_on_8_device_world():
    env = dict(os.environ, PYTHONPATH="src")
    res = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(os.path.dirname(__file__)))
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert len(out) == 10  # 6 combo plans + 4 no-pod train plans
    # the DiLoCo sync step must exist and every plan lowered
    assert all(v["ok"] for v in out.values())
    # the train step moves bytes over the wire (FSDP gathers)
    assert out["smollm-135m/train_4k/train_step"]["collective_total"] > 0
    # the engine's fused round + scan-over-R superstep plans lower on the
    # same mesh and communicate
    for plan in ("round_step", "superstep"):
        rec = out[f"smollm-135m/train_4k/{plan}"]
        assert rec["collective_total"] > 0
        # donated under GSPMD (ROADMAP open item): the outer-transform
        # state buffers are among the aliased outputs, and the per-chip
        # aliased bytes cover at least the outer params+opt shard
        donation = rec["donation"]
        assert donation["outer_opt_bytes_global"] > 0
        assert donation["outer_state_aliased"], donation
    # the no-pod (K=1, model > data) mesh is the configuration where GSPMD
    # output-sharding propagation used to break outer-state donation — it
    # must stay fully aliased now that the plan fns pin their outputs
    for plan in ("round_step", "superstep"):
        donation = out[f"nopod/{plan}"]["donation"]
        assert donation["outer_state_aliased"], donation
