"""Launch-layer tests: sharding rules, input specs, small-mesh end-to-end
(multi-device runs happen in a subprocess so XLA device count can be set)."""
import json
import subprocess
import sys

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, get_config, smoke_config
from repro.distributed.sharding import ShardingRules
from repro.launch.roofline import model_flops
from repro.launch.steps import input_specs

# --------------------------------------------------------------------- #
# input_specs: every (arch x shape) cell has well-defined structs
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("arch", ["deepseek-moe-16b", "llava-next-mistral-7b",
                                  "musicgen-medium", "xlstm-350m"])
@pytest.mark.parametrize("shape", ["train_4k", "decode_32k"])
def test_input_specs_structures(arch, shape):
    cfg = get_config(arch)
    spec = SHAPES[shape]
    s = input_specs(cfg, spec)
    assert "params" in s
    if spec.kind == "train":
        assert "opt_state" in s and "batch" in s
        assert s["batch"]["labels"].shape == (spec.global_batch, spec.seq_len)
    else:
        assert "cache" in s and "pos" in s
        if cfg.frontend == "audio":
            assert s["tokens"] is None and "embeds" in s
        else:
            assert s["tokens"].shape == (spec.global_batch, 1)
    # nothing was allocated
    flat = [x for x in jax.tree.leaves(s) if x is not None]
    assert all(isinstance(x, jax.ShapeDtypeStruct) for x in flat)


def test_model_flops_magnitudes():
    cfg = get_config("mistral-large-123b")
    f_train = model_flops(cfg, SHAPES["train_4k"])
    # 6 * 123e9 * (256*4096) ~ 7.7e17 plus attention
    assert 7e17 < f_train < 1.2e18
    f_dec = model_flops(cfg, SHAPES["decode_32k"])
    assert 2 * 123e9 * 128 * 0.9 < f_dec < 2 * 123e9 * 128 * 3


# --------------------------------------------------------------------- #
# Sharding rules on a tiny mesh (1 device: specs still well-formed)
# --------------------------------------------------------------------- #


def test_sharding_rules_divisibility_guards():
    import numpy as np

    class FakeMesh:
        shape = {"data": 4, "model": 16}
        axis_names = ("data", "model")

    cfg = get_config("granite-moe-3b-a800m")          # 40 experts: not / 16
    rules = ShardingRules(cfg, FakeMesh())
    spec = rules.param_spec(
        (jax.tree_util.DictKey("units"), jax.tree_util.DictKey("b0"),
         jax.tree_util.DictKey("ffn"), jax.tree_util.DictKey("w_gate")),
        jax.ShapeDtypeStruct((31, 40, 1536, 512), jax.numpy.float32),
    )
    # EP impossible (40 % 16 != 0) -> TP on d_ff instead
    assert spec == P(None, None, None, "model")

    cfg2 = get_config("deepseek-moe-16b")             # 64 experts: / 16
    rules2 = ShardingRules(cfg2, FakeMesh())
    spec2 = rules2.param_spec(
        (jax.tree_util.DictKey("units"), jax.tree_util.DictKey("b0"),
         jax.tree_util.DictKey("ffn"), jax.tree_util.DictKey("w_gate")),
        jax.ShapeDtypeStruct((27, 64, 2048, 1408), jax.numpy.float32),
    )
    assert spec2 == P(None, "model", None, None)

    # batch=1 cache: batch unshardable -> context parallelism on seq
    cspec = rules2.cache_spec(
        (jax.tree_util.DictKey("units"), jax.tree_util.DictKey("b0"),
         jax.tree_util.DictKey("k")),
        jax.ShapeDtypeStruct((27, 1, 1024, 16, 128), jax.numpy.bfloat16),
    )
    assert cspec == P(None, None, "data", "model", None)


# --------------------------------------------------------------------- #
# Multi-device end-to-end (subprocess with forced host device count)
# --------------------------------------------------------------------- #

_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.configs import smoke_config
from repro.models import Parallel, init_params, loss_fn, random_batch
from repro.distributed.sharding import ShardingRules

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto, AxisType.Auto))
cfg = smoke_config("deepseek-moe-16b")   # 8 experts / 4 = 2 per device
par = Parallel(mesh=mesh)
rules = ShardingRules(cfg, mesh)
params = init_params(cfg, jax.random.PRNGKey(0))
batch = random_batch(cfg, 4, 32, seed=1)

# single-shard reference
ref, _ = loss_fn(cfg, params, batch)

p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                    rules.param_specs(params),
                    is_leaf=lambda s: isinstance(s, P))
b_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), rules.batch_spec(batch),
                    is_leaf=lambda s: isinstance(s, P))
params_d = jax.device_put(params, p_sh)
batch_d = jax.device_put({k: jnp.asarray(v) for k, v in batch.items()}, b_sh)
with mesh:
    dist, _ = jax.jit(lambda p, b: loss_fn(cfg, p, b, par=par))(params_d, batch_d)
print(json.dumps({"ref": float(ref), "dist": float(dist)}))
"""


@pytest.mark.slow
def test_distributed_loss_matches_single_shard():
    """EP shard_map path on 8 host devices == local math (same routing)."""
    res = subprocess.run(
        [sys.executable, "-c", _SUBPROC], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root"},
        timeout=600, cwd="/root/repo",
    )
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert abs(out["ref"] - out["dist"]) / abs(out["ref"]) < 2e-2, out


@pytest.mark.slow
def test_dryrun_cli_end_to_end(tmp_path):
    """The actual deliverable path: dryrun CLI lowers+compiles a cell on the
    512-device production mesh and emits a roofline JSON artifact."""
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "smollm-135m",
         "--shape", "decode_32k", "--mesh", "pod", "--out", str(tmp_path),
         "--force"],
        capture_output=True, text=True, timeout=900, cwd="/root/repo",
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root"},
    )
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(
        (tmp_path / "smollm-135m__decode_32k__pod_16x16.json").read_text()
    )
    assert out["status"] == "ok"
    assert out["n_devices"] == 256
    r = out["roofline"]
    assert r["memory_s"] > 0 and r["dominant"] in (
        "compute", "memory", "collective")
    assert out["memory_analysis"]["argument_size_in_bytes"] > 0


# --------------------------------------------------------------------- #
# Serving: placement, peaks, compile cache
# --------------------------------------------------------------------- #


def test_placement_permutes_expert_stacks_in_place():
    """plan_and_apply_placement == apply_placement unit by unit, and the
    placed model computes the same logits (routing is permuted with the
    experts)."""
    import numpy as np

    from repro.launch.serve import (calibrate_router_stats,
                                    plan_and_apply_placement)
    from repro.models import forward, init_params, random_batch
    from repro.models.moe import apply_placement

    cfg = smoke_config("llama-moe-3.5b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": random_batch(cfg, 2, 16, seed=3)["tokens"]}
    counts = calibrate_router_stats(cfg, params, batch)
    logits0, _ = forward(cfg, params, batch)
    ffn0 = jax.tree.map(np.asarray, params["units"]["b0"]["ffn"])

    placed, plans, _ = plan_and_apply_placement(cfg, params, counts)
    ffn = placed["units"]["b0"]["ffn"]
    for u, plan in enumerate(plans):
        want = apply_placement(jax.tree.map(lambda a: a[u], ffn0),
                               plan.expert_perm)
        for k, w in want.items():
            np.testing.assert_array_equal(np.asarray(ffn[k][u]),
                                          np.asarray(w))
    logits1, _ = forward(cfg, placed, batch)
    np.testing.assert_allclose(np.asarray(logits1), np.asarray(logits0),
                               atol=1e-5, rtol=1e-5)


def test_device_peaks_keyed_by_kind():
    from repro.launch.roofline import device_peaks
    v5e = device_peaks("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(ValueError, match="no published peaks"):
        device_peaks("cpu")


@pytest.mark.parametrize("env", ["/some/shared/cache", None])
def test_compile_cache_dir(monkeypatch, env):
    """JAX_COMPILATION_CACHE_DIR wins and is left to jax; otherwise the
    cache sits at a fixed path inside the checkout."""
    from repro.launch.cache import DEFAULT_DIR, REPO_ROOT, setup_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        if env is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            assert setup_compile_cache() == str(DEFAULT_DIR)
            assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
            assert DEFAULT_DIR.parent == REPO_ROOT
            assert (REPO_ROOT / "pyproject.toml").exists()
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
            assert setup_compile_cache() == env
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
