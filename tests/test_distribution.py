"""Distribution-layer integration tests.

These need >1 XLA device, so they run in a subprocess with
xla_force_host_platform_device_count=8 (the main test process keeps the
single-device view per the dry-run isolation rule)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np, jax.numpy as jnp
from repro.configs import get_config, reduce_config, ShapeConfig
from repro.launch.steps import (input_specs, make_serve_step, make_train_step,
                                opt_struct, param_struct, serve_cache_struct)
from repro.parallel import (batch_shardings, cache_shardings, param_shardings,
                            set_active_mesh)
from repro.launch import roofline as rf
from repro.launch.mesh import make_debug_mesh
from repro.models import identity_dispatch, init_params, train_loss

mesh = make_debug_mesh(data=2, model=4)
set_active_mesh(mesh)

# ---- 1. sharded train-step lower+compile for a dense arch
cfg = reduce_config(get_config("glm4-9b"))
pstruct = param_struct(cfg)
pshard = param_shardings(pstruct, mesh)
step, opt = make_train_step(cfg, chunk=64)
ostruct = opt_struct(cfg, opt, pstruct)
oshard = param_shardings(ostruct, mesh)
specs = input_specs(cfg, ShapeConfig("t", 128, 8, "train"))
bshard = batch_shardings(specs["batch"], mesh)
with mesh:
    compiled = jax.jit(step, in_shardings=(pshard, oshard, bshard)).lower(
        pstruct, ostruct, specs["batch"]).compile()
colls = rf.collective_stats(compiled.as_text())
assert colls["all-reduce"]["count"] > 0, "expected gradient all-reduces"
print("MARK train_lowering_ok")

# ---- 2. sharded decode lower+compile with cache shardings
sstep = make_serve_step(cfg, chunk=64)
cstruct = serve_cache_struct(cfg, 8, 256)
cshard = cache_shardings(cstruct, mesh)
dspec = input_specs(cfg, ShapeConfig("d", 256, 8, "decode"))
tsh = batch_shardings({"tokens": dspec["tokens"],
                       "positions": dspec["positions"]}, mesh)
with mesh:
    jax.jit(sstep, in_shardings=(pshard, cshard, tsh["tokens"],
                                 tsh["positions"])).lower(
        pstruct, cstruct, dspec["tokens"], dspec["positions"]).compile()
print("MARK decode_lowering_ok")

# ---- 3. shard_map MoE == local MoE numerically (real execution)
cfgm = reduce_config(get_config("qwen3-moe-30b-a3b"), dtype="float32")
disp = identity_dispatch(cfgm.moe.num_experts, 4)
set_active_mesh(None)
params = init_params(cfgm, jax.random.PRNGKey(0), moe_dispatch=disp)
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0,
                                      cfgm.vocab_size),
         "targets": jax.random.randint(jax.random.PRNGKey(2), (8, 64), 0,
                                       cfgm.vocab_size)}
l_local, _ = jax.jit(lambda p, b: train_loss(cfgm, p, b, moe_dispatch=disp,
                                             chunk=32))(params, batch)
set_active_mesh(mesh)
with mesh:
    l_dist, _ = jax.jit(lambda p, b: train_loss(cfgm, p, b, moe_dispatch=disp,
                                                chunk=32))(params, batch)
assert abs(float(l_local) - float(l_dist)) < 5e-3, (l_local, l_dist)
print("MARK moe_parity_ok")

# ---- 4. elastic remesh: values survive a mesh change
from repro.runtime import elastic_remesh
set_active_mesh(None)
mesh_b = make_debug_mesh(data=4, model=2)
state = {"blocks": {"wq": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
moved = elastic_remesh(state, mesh_b)
np.testing.assert_array_equal(np.asarray(moved["blocks"]["wq"]),
                              np.asarray(state["blocks"]["wq"]))
print("MARK elastic_ok")

# ---- 5. multi-pod mesh axes exist and shard the pod dimension
mesh3 = make_debug_mesh(data=2, model=2, pods=2)
set_active_mesh(mesh3)
pshard3 = param_shardings(param_struct(cfg), mesh3)
specs3 = input_specs(cfg, ShapeConfig("t", 128, 8, "train"))
bsh3 = batch_shardings(specs3["batch"], mesh3)
assert "pod" in str(bsh3["tokens"].spec)
step3, opt3 = make_train_step(cfg, chunk=64)
osh3 = param_shardings(opt_struct(cfg, opt3, param_struct(cfg)), mesh3)
with mesh3:
    jax.jit(step3, in_shardings=(pshard3, osh3, bsh3)).lower(
        param_struct(cfg), opt_struct(cfg, opt3, param_struct(cfg)),
        specs3["batch"]).compile()
print("MARK multipod_ok")
"""


@pytest.mark.slow
def test_distribution_stack():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True,
        text=True, timeout=1200,
    )
    out = proc.stdout
    assert proc.returncode == 0, f"stdout:\n{out}\nstderr:\n{proc.stderr[-3000:]}"
    for mark in ("train_lowering_ok", "decode_lowering_ok", "moe_parity_ok",
                 "elastic_ok", "multipod_ok"):
        assert f"MARK {mark}" in out, f"missing {mark}\n{out}"
