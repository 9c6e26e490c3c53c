"""The expert-parallel MoE paths (``moe_apply_ep``, ``moe_apply_ep_replicated``)
against the single-shard layer, and ``Parallel.resolve_moe``'s choice of path
from the mesh and the shapes.

The EP cases run in one subprocess on four virtual CPU devices, a (1, 4)
("data", "model") mesh, so that the parent's JAX keeps its own device count.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.models import Parallel
from repro.models.config import LayerSpec, ModelConfig

REPO = Path(__file__).resolve().parents[1]

# (E, k, shared experts) x seq_len: 8 divides over the 4-way axis ("ep"),
# 1 does not ("ep_rep").
EP_CASES = [(e, k, sh, s) for e, k, sh in [(8, 2, 0), (8, 2, 2), (16, 4, 0)]
            for s in (8, 1)]

_SUBPROC = r"""
import json, sys
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType
from repro.models import Parallel
from repro.models.config import LayerSpec, ModelConfig
from repro.models.model import _apply_moe
from repro.models.moe import moe_apply_local, moe_init

mesh = jax.make_mesh((1, 4), ("data", "model"),
                     axis_types=(AxisType.Auto, AxisType.Auto))
par = Parallel(mesh=mesh)
f32 = jnp.float32
out = {}
for e, k, sh, s in json.loads(sys.argv[1]):
    cfg = ModelConfig(
        name="t", n_layers=1, d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
        vocab_size=128, pattern=(LayerSpec("attn", "moe"),), n_experts=e,
        top_k=k, n_shared_experts=sh, d_ff_expert=16,
        capacity_factor=e / k, compute_dtype="float32")   # dropless
    # unit-variance weights over fan-in, so that outputs are O(1)
    params = moe_init(jax.random.PRNGKey(0), cfg, f32)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(e + k + sh), len(leaves))
    params = jax.tree.unflatten(tree, [
        jax.random.normal(kk, a.shape, f32) * a.shape[-2] ** -0.5
        for kk, a in zip(keys, leaves)])
    x = jax.random.normal(jax.random.PRNGKey(s), (2, s, 32), f32)
    ref, _ = jax.jit(lambda p, xx: moe_apply_local(cfg, p, xx, f32))(params, x)
    with mesh:
        got, _ = jax.jit(lambda p, xx: _apply_moe(cfg, p, xx, par, f32))(
            params, x)
    out[f"{e}-{k}-{sh}-{s}"] = {
        "mode": par.resolve_moe(cfg, s),
        "max_abs_err": float(jnp.max(jnp.abs(got - ref))),
        "max_abs_ref": float(jnp.max(jnp.abs(ref))),
    }
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def ep_results():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src"))
    res = subprocess.run(
        [sys.executable, "-c", _SUBPROC, json.dumps(EP_CASES)],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("e,k,shared,seq_len", EP_CASES)
def test_ep_moe_matches_local(ep_results, e, k, shared, seq_len):
    r = ep_results[f"{e}-{k}-{shared}-{seq_len}"]
    assert r["mode"] == ("ep" if seq_len == 8 else "ep_rep")
    assert r["max_abs_ref"] > 0
    assert r["max_abs_err"] <= 1e-5, r


class _ShapeMesh:
    """Stand-in mesh: ``resolve_moe`` reads only the axis sizes."""

    def __init__(self, model: int):
        self.shape = {"data": 1, "model": model}


@pytest.mark.parametrize("model_axis,n_experts,seq_len,mode", [
    (None, 64, 8, "local"),      # no mesh: single shard
    (4, 64, 8, "ep"),            # experts and sequence divide over the axis
    (4, 64, 1, "ep_rep"),        # decode: tokens replicated over the axis
    (16, 40, 8, "local"),        # granite's 40 on 16: TP over d_ff
])
def test_resolve_moe_from_shapes(model_axis, n_experts, seq_len, mode):
    cfg = ModelConfig(
        name="t", n_layers=1, d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
        vocab_size=128, pattern=(LayerSpec("attn", "moe"),),
        n_experts=n_experts, top_k=2)
    mesh = None if model_axis is None else _ShapeMesh(model_axis)
    assert Parallel(mesh=mesh).resolve_moe(cfg, seq_len) == mode
