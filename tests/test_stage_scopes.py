"""The model's stage scopes (``repro.models.layers.STAGES``).

Every stage name reaches the ``op_name`` metadata of the compiled prefill
and decode programs, and every op of the decode scan's body lies in a
model stage or in the scan itself (``layers``), so that a profiler trace
can be split by stage.  Ops the compiler adds (copies, tuples) carry no
metadata and are not the program's; on the chip their share is measured
by ``decode_scan_share``.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from bench import trace_stages
from repro.launch import steps
from repro.models import LayerSpec, ModelConfig, Parallel
from repro.models.layers import STAGES

#: A dense first layer, then MoE layers with a shared expert: every stage.
CFG = ModelConfig(
    name="tiny-stages", n_layers=3, d_model=32, n_heads=4, n_kv_heads=2,
    d_ff=16, vocab_size=128, pattern=(LayerSpec("attn", "moe"),),
    n_experts=4, top_k=2, n_shared_experts=1, d_ff_expert=16,
    first_layer_dense=True, first_dense_d_ff=48, capacity_factor=2.0,
    compute_dtype="float32", attn_q_chunk=8, attn_kv_chunk=8)
B, PROMPT, MAX_LEN = 2, 8, 12

#: The scan's own work in its body: slicing each layer's weights and cache
#: out of the stacked arrays, stacking the new cache, the loop counter and
#: the residual adds between the stages.
SCAN_OPS = {"dynamic_slice", "dynamic_update_slice", "squeeze", "add",
            "closed_call"}


@pytest.fixture(scope="module")
def programs() -> dict[str, str]:
    """Optimized HLO text of both serving programs."""
    par = Parallel()
    params = steps.param_structs(CFG)
    prefill = jax.jit(steps.make_prefill_step(CFG, par, MAX_LEN)).lower(
        params, {"tokens": jax.ShapeDtypeStruct((B, PROMPT), jnp.int32)})
    serve = jax.jit(steps.make_serve_step(CFG, par), donate_argnums=(1,)).lower(
        params, steps.cache_structs(CFG, B, MAX_LEN),
        jax.ShapeDtypeStruct((B, 1), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32), None)
    return {"prefill_step": prefill.compile().as_text(),
            "serve_step": serve.compile().as_text()}


def _computations(text: str) -> dict[str, list[str]]:
    """Computation name -> its instruction lines."""
    comps, cur = {}, None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            name = line.removeprefix("ENTRY ").split(" ", 1)[0]
            cur = comps.setdefault(name.lstrip("%"), [])
        elif line.startswith("  ") and cur is not None:
            cur.append(line.strip())
    return comps


def _path(line: str) -> str | None:
    m = re.search(r'op_name="([^"]*)"', line)
    return m.group(1) if m else None


def test_bench_keeps_the_program_stage_names():
    assert trace_stages.STAGES == STAGES


@pytest.mark.parametrize("program", ["prefill_step", "serve_step"])
def test_every_stage_reaches_the_op_metadata(programs, program):
    paths = [p for lines in _computations(programs[program]).values()
             for p in map(_path, lines) if p]
    assert {p for p in paths if p.startswith(f"jit({program})")}
    found = {trace_stages.stage_of(p) for p in paths}
    assert set(STAGES) <= found, sorted(set(STAGES) - found)


def test_decode_scan_body_ops_have_a_stage(programs):
    comps = _computations(programs["serve_step"])
    bodies = {m.group(1)
              for lines in comps.values() for line in lines
              if " while(" in line and (_path(line) or "").endswith(
                  "/layers/while")
              for m in [re.search(r"body=%?([\w.\-]+)", line)] if m}
    assert len(bodies) == 1
    paths = [p for p in map(_path, comps[bodies.pop()]) if p]
    assert len(paths) > 20
    stages = [trace_stages.stage_of(p) for p in paths]
    assert trace_stages.NONE not in stages
    assert set(STAGES) - {"embed", "ffn.dense", "head"} <= set(stages)
    scan_ops = {p.rsplit("/", 1)[-1] for p, s in zip(paths, stages)
                if s == "layers"}
    assert scan_ops <= SCAN_OPS, scan_ops - SCAN_OPS
