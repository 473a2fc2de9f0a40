"""Distribution tests: param sharding rules, mesh helpers, hierarchical
collectives and the dry-run (the latter two in subprocesses with fake
multi-device CPU topologies, since the main test process holds 1 device)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import (MeshAxes, activation_spec,
                                 param_spec_for_path)
from repro.launch.mesh import make_local_mesh, mesh_axes_for

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}


# ---------------------------------------------------------- sharding rules --
def test_param_rules_attention():
    ax = MeshAxes()
    assert param_spec_for_path("blocks/attn/attn/wq", 3, ax) == \
        P(None, ("data",), "model")
    assert param_spec_for_path("blocks/attn/attn/wo", 3, ax) == \
        P(None, "model", ("data",))
    assert param_spec_for_path("embed", 2, ax) == P("model", ("data",))
    assert param_spec_for_path("lm_head", 2, ax) == P(("data",), "model")


def test_param_rules_moe_and_ssm():
    ax = MeshAxes()
    # stacked experts: (L, E, D, F) -> EP over model on E, FSDP on D
    assert param_spec_for_path("blocks/attn/moe/w_up", 4, ax) == \
        P(None, "model", ("data",), None)
    assert param_spec_for_path("blocks/attn/moe/w_down", 4, ax) == \
        P(None, "model", None, ("data",))
    assert param_spec_for_path("blocks/mamba/ssm/in_proj", 3, ax) == \
        P(None, ("data",), "model")
    assert param_spec_for_path("blocks/rwkv/rwkv/w_k", 3, ax) == \
        P(None, ("data",), "model")
    # norms replicated
    assert param_spec_for_path("blocks/attn/norm1", 2, ax) == P(None, None)


def test_activation_specs():
    ax = MeshAxes(batch=("data",))
    assert activation_spec("hidden", ax) == P(("data",), None, None)
    assert activation_spec("logits", ax) == P(("data",), None, "model")
    assert activation_spec("kv_cache", ax) == \
        P(None, ("data",), None, "model", None)


def test_mesh_axes_batch1_drops_dp():
    mesh = make_local_mesh()
    ax = mesh_axes_for(mesh, batch_size=1)
    assert ax.batch == () or all(mesh.shape[a] == 1 for a in ax.batch)


def test_build_param_shardings_tree():
    from repro.configs import get_config, reduced
    from repro.dist.sharding import build_param_shardings
    from repro.models import build_model
    cfg = reduced(get_config("tinyllama-1.1b"))
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    mesh = make_local_mesh()
    sh = build_param_shardings(shapes, mesh)
    # every leaf got a NamedSharding of matching rank
    for (path, leaf), (_, s) in zip(
            jax.tree_util.tree_flatten_with_path(shapes)[0],
            jax.tree_util.tree_flatten_with_path(sh)[0]):
        assert len(s.spec) <= leaf.ndim


# ------------------------------------------------- subprocess integration --
def _run(code: str, timeout=540):
    return subprocess.run([sys.executable, "-c", code], env=ENV,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_hierarchical_collectives_8dev():
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.dist.collectives import hierarchical_grad_reduce
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
g = jnp.arange(32.0).reshape(8, 4)
spec = P(("pod", "data"), None)

def f(x):
    return hierarchical_grad_reduce({"g": x}, mesh)["g"]

out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(spec,),
                            out_specs=spec, check_vma=False))(g)
# mean over pod x data of the 4 shards
want = np.asarray(g).reshape(4, 2, 4).mean(0).repeat(4, 0) * 0
shards = np.asarray(g).reshape(4, 2, 4)
mean = shards.mean(axis=0)
want = np.tile(mean, (4, 1))
np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6)

# compressed variant close to exact
def fc(x):
    return hierarchical_grad_reduce({"g": x}, mesh, compress_pod=True)["g"]
outc = jax.jit(jax.shard_map(fc, mesh=mesh, in_specs=(spec,),
                             out_specs=spec, check_vma=False))(g)
np.testing.assert_allclose(np.asarray(outc), want, rtol=0.05, atol=0.05)
print("OK")
"""
    r = _run(code)
    assert "OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_dryrun_single_cell_subprocess():
    """The required dry-run mechanics on a tiny arch cell: lower+compile on
    the 16x16 production mesh (512 fake CPU devices) with probes."""
    code = """
from repro.launch.dryrun import lower_cell
compiled, report = lower_cell("seamless-m4t-medium", "decode_32k",
                              probe=False, verbose=False)
assert report.n_chips == 256
assert compiled.memory_analysis() is not None
print("OK", report.dominant)
"""
    r = _run(code)
    assert "OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_dryrun_multipod_subprocess():
    code = """
from repro.launch.dryrun import lower_cell
compiled, report = lower_cell("smollm-360m", "decode_32k",
                              multi_pod=True, probe=False, verbose=False)
assert report.n_chips == 512
print("OK")
"""
    r = _run(code)
    assert "OK" in r.stdout, r.stdout + r.stderr


# ------------------------------------------------------- roofline parsing --
def test_collective_bytes_parser():
    from repro.roofline.analyze import collective_bytes_from_hlo
    hlo = '''
  %ag = bf16[256,1024]{1,0} all-gather(bf16[16,1024]{1,0} %x), dim=0
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %y), to_apply=%add
  %rs.1 = f32[64]{0} reduce-scatter(f32[1024]{0} %z), dimensions={0}
  %a2a = (f32[8,4]{1,0}, f32[8,4]{1,0}) all-to-all(f32[8,4]{1,0} %p, f32[8,4]{1,0} %q)
  %cp-start = bf16[32]{0} collective-permute-start(bf16[32]{0} %w)
  %cp-done = bf16[32]{0} collective-permute-done(bf16[32]{0} %cp-start)
'''
    out = collective_bytes_from_hlo(hlo)
    assert out["all-gather"] == 256 * 1024 * 2
    assert out["all-reduce"] == 1024 * 4
    assert out["reduce-scatter"] == 64 * 4
    assert out["all-to-all"] == 2 * 8 * 4 * 4
    assert out["collective-permute"] == 32 * 2


def test_roofline_report_math():
    from repro.roofline.analyze import RooflineReport
    r = RooflineReport(arch="a", shape="s", mesh="m", n_chips=256,
                       flops_per_device=197e12, bytes_per_device=819e9,
                       collective_bytes={"all-reduce": 50_000_000_000},
                       memory_per_device=8 * 2 ** 30,
                       model_flops=197e12 * 256)
    assert abs(r.t_compute - 1.0) < 1e-9
    assert abs(r.t_memory - 1.0) < 1e-9
    assert abs(r.t_collective - 1.0) < 1e-9
    assert r.step_time == 1.0
    assert abs(r.useful_flops_ratio - 1.0) < 1e-9
    assert abs(r.roofline_fraction - 1.0) < 1e-9


@pytest.mark.slow
def test_moe_ep_path_matches_dense():
    """The shard_map expert-parallel MoE must match the single-device
    dense path exactly when capacity is generous (no drops)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config, reduced
from repro.dist.sharding import MeshAxes, set_mesh_axes
from repro.models import moe as moe_lib

cfg = reduced(get_config("qwen3-moe-30b-a3b"))
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
    cfg.moe, n_experts=8, top_k=2, capacity_factor=4.0))
params = moe_lib.moe_init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                      jnp.float32) * 0.1

# dense reference (no mesh)
y_ref, aux_ref = moe_lib.moe_apply(params, x, cfg)

# EP path on a (data=2, model=4) mesh
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
ax = MeshAxes(mesh=mesh, batch=("data",))
with set_mesh_axes(ax), mesh:
    y_ep, aux_ep = jax.jit(lambda p, x_: moe_lib.moe_apply(p, x_, cfg))(params, x)

np.testing.assert_allclose(np.asarray(y_ref, np.float32),
                           np.asarray(y_ep, np.float32), rtol=2e-2, atol=2e-3)
np.testing.assert_allclose(float(aux_ref), float(aux_ep), rtol=1e-4)
print("OK")
"""
    r = _run(code)
    assert "OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_moe_serve_layout_matches_dense():
    """The serving-layout MoE (experts over data + F-TP over model) must
    also match the dense reference."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config, reduced
from repro.dist.sharding import MeshAxes, set_mesh_axes
from repro.models import moe as moe_lib

cfg = reduced(get_config("qwen3-moe-30b-a3b"))
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
    cfg.moe, n_experts=8, top_k=2, capacity_factor=4.0),
    moe_serve_layout=True)
params = moe_lib.moe_init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                      jnp.float32) * 0.1

cfg_dense = dataclasses.replace(cfg, moe_serve_layout=False)
y_ref, aux_ref = moe_lib.moe_apply(params, x, cfg_dense)

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
ax = MeshAxes(mesh=mesh, batch=("data",))
with set_mesh_axes(ax), mesh:
    y_srv, aux_srv = jax.jit(lambda p, x_: moe_lib.moe_apply(p, x_, cfg))(params, x)

np.testing.assert_allclose(np.asarray(y_ref, np.float32),
                           np.asarray(y_srv, np.float32), rtol=2e-2, atol=2e-3)
np.testing.assert_allclose(float(aux_ref), float(aux_srv), rtol=1e-4)
print("OK")
"""
    r = _run(code)
    assert "OK" in r.stdout, r.stdout + r.stderr
