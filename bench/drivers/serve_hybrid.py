"""Serving cells of hybrid Mamba-2 / attention MoE models (GraniteMoeHybrid):
the MLA cells' closed loop (``bench/drivers/serve_mla.py``) through the
program's own jitted prefill and decode step (``repro.launch.steps``),
judged against the hybrid reference (``bench/reference/hybrid_moe_lm.py``).

What differs from ``serve_mla.py`` is the model and the check.  The
program's config has one period of the published ``layer_types`` as its
pattern (``mamba2`` and ``attn`` mixers, every block with the MoE on the
file's held share of the routed experts), GraniteMoE's multipliers and no
position embedding; its weights carry the Mamba-2 leaves, made as the
reference makes them.  The checked requests are ``check_requests`` rows of
the first batch, which always runs to its end, drawn from the seed; besides
the gaps of their served tokens, the check compares the program's SSM state
after each one's last step, as the decode scan left it, with the
reference's recurrence over the same tokens (``state_err``).  The in-flight
decode loop (``serve_mla.Server.batch``), the draw of prompts and tracing
are ``serve_mla.py``'s and ``serve.py``'s own.  Set-up and ``run`` are
theirs too, copied because those modules look up their own model, weights
and judge by name.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, trace_reduce
from bench.drivers import serve, serve_mla
from bench.hybrid_dims import HybridDims, dims
from bench.reference import hybrid_moe_lm as reference
from bench.weights import _canonical, seed_key

#: Program parameter path (suffix after the block) -> canonical name, for
#: the Mamba-2 leaves the GQA map (``bench/weights.py``) does not have.
_SSM_LEAVES = {
    ("mixer", "w_in"): "ssm.in",
    ("mixer", "conv_w"): "ssm.conv",
    ("mixer", "conv_b"): "ssm.conv_bias",
    ("mixer", "dt_bias"): "ssm.dt_bias",
    ("mixer", "A_log"): "ssm.a_log",
    ("mixer", "D"): "ssm.d",
    ("mixer", "norm", "scale"): "norm.ssm",
    ("mixer", "w_out"): "ssm.out",
}
_MIXERS = {"mamba": "mamba2", "attention": "attn"}


def period(d: HybridDims) -> int:
    """The shortest run of layer types whose repeats make the served
    layers: the program's pattern unit."""
    n, kinds = d.n_layers, d.layer_types
    return next(p for p in range(1, n + 1)
                if n % p == 0 and kinds == kinds[:p] * (n // p))


def program_config(c: dict):
    """The program's ``ModelConfig`` for a hybrid config file: the held
    share of the routed experts, dropless routing."""
    from repro.models.config import LayerSpec, ModelConfig
    d = dims(c)
    pattern = tuple(LayerSpec(_MIXERS[k], "moe")
                    for k in d.layer_types[:period(d)])
    return ModelConfig(
        name=c["name"], n_layers=d.n_layers, d_model=d.d_model,
        n_heads=d.n_heads, n_kv_heads=d.n_kv_heads, d_ff=d.d_ff_expert,
        vocab_size=d.vocab, pattern=pattern, head_dim=d.head_dim,
        n_experts=d.n_experts, n_experts_held=d.n_held, top_k=d.top_k,
        n_shared_experts=d.n_shared, d_ff_expert=d.d_ff_expert,
        capacity_factor=d.n_experts / d.top_k, nope=True,
        mamba_d_state=d.ssm_state, mamba_expand=d.d_inner // d.d_model,
        mamba_d_conv=d.conv_kernel, mamba_n_heads=d.ssm_heads,
        mamba_head_dim=d.ssm_head_dim, mamba_n_groups=d.ssm_groups,
        mamba_chunk_size=d.ssm_chunk,
        embedding_multiplier=d.embedding_multiplier,
        residual_multiplier=d.residual_multiplier,
        attention_multiplier=d.attention_multiplier,
        logits_scaling=d.logits_scaling, tie_embeddings=d.tied,
        norm_eps=d.norm_eps, param_dtype=c["torch_dtype"],
        compute_dtype=c["torch_dtype"])


def program_params(structs, seed: int, d: HybridDims):
    """The program's parameter tree (``structs`` from ``jax.eval_shape`` of
    its ``init_params``) filled from ``seed`` in one jitted call, each leaf
    as the reference's ``make_leaf`` makes it.  Block ``b<i>`` of unit u
    is layer ``u * period + i``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(structs)
    paths = [tuple(k.key for k in kp) for kp, _ in flat]
    moe_blocks = {p[:2] for p in paths if p[-2:] == ("ffn", "router")}
    plan = [(p, _SSM_LEAVES.get(p[2:]) or _canonical(p, p[:2] in moe_blocks),
             s) for p, (_, s) in zip(paths, flat)]
    per = period(d)

    def build(key):
        leaves = []
        for path, name, s in plan:
            if path[0] == "units":
                i = int(path[1][1:])
                f = jax.vmap(lambda u, name=name, s=s, i=i: reference.make_leaf(
                    key, name, u * per + i, s.shape[1:], s.dtype, d.n_layers,
                    d.vocab))
                leaves.append(f(jnp.arange(s.shape[0])))
            else:
                leaves.append(reference.make_leaf(
                    key, name, -1, s.shape, s.dtype, d.n_layers, d.vocab,
                    d.tied, d.embedding_multiplier))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(seed_key(seed))


class Server(serve_mla.Server):
    """``serve_mla.Server`` for a hybrid model: weights made from the seed,
    the compiled prefill and decode step warmed for the cell's shapes; no
    calibration and no placement.  Of each batch that runs to its end it
    keeps the checked rows' final SSM states, on the device
    (``final_states``: one (Mamba-2 layers, rows, H, P, N) array a batch)."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.launch import steps
        from repro.models import Parallel, init_params
        self.d = dims(config)
        self.cfg = program_config(config)
        self.b, self.p = traffic["batch"], traffic["prompt_len"]
        self.n = traffic["output_len"]
        self.prompts = np.random.default_rng([seed, 1])
        structs = jax.eval_shape(functools.partial(init_params, self.cfg),
                                 jax.random.PRNGKey(0))
        self.params = program_params(structs, seed, self.d)
        par = Parallel(mesh=None)
        prompt = {"tokens": jnp.asarray(self.draw(self.prompts))}
        self.prefill = jax.jit(steps.make_prefill_step(
            self.cfg, par, self.p + self.n)).lower(self.params, prompt).compile()
        logits, cache = self.prefill(self.params, prompt)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        pos = jnp.full((self.b,), self.p, jnp.int32)
        self.compiled_step = jax.jit(steps.make_serve_step(self.cfg, par),
                                     donate_argnums=(1,)).lower(
            self.params, cache, tok, pos, None).compile()
        self.check_rows = np.sort(np.random.default_rng([seed, 2]).choice(
            self.b, min(traffic["check_requests"], self.b), replace=False))
        per = period(self.d)
        # Block and unit of each Mamba-2 layer, in layer order.
        layers = [(f"b{i}", u) for u in range(self.d.n_layers // per)
                  for i in range(per) if self.d.layer_types[i] == "mamba"]

        def take_states(cache):
            return jnp.stack([cache["units"][b]["ssm"][u, self.check_rows]
                              for b, u in layers])

        self.take_states = jax.jit(take_states).lower(cache).compile()
        del logits, cache, tok
        self.final_states, self.steps_run = [], 0
        # Warm every shape the window uses: one batch of three tokens.
        self.batch(self.draw(self.prompts), np.inf, limit=3)

    def batch(self, prompt: np.ndarray, deadline: float, tracer=None,
              limit: int | None = None) -> dict:
        """``serve_mla.Server.batch``, counting its decode steps."""
        self.steps_run = 0
        return super().batch(prompt, deadline, tracer, limit)

    def step(self, params, cache, tok, pos, embeds):
        """The compiled decode step; after the last step of a batch that
        runs to its end, the checked rows' SSM states are sliced out of
        the returned cache (on the device: the loop does not wait)."""
        out = self.compiled_step(params, cache, tok, pos, embeds)
        self.steps_run += 1
        if self.steps_run == self.n - 1:
            self.final_states.append(self.take_states(out[2]))
        return out

    def free(self):
        super().free()
        self.compiled_step = self.take_states = None


def state_err(states: np.ndarray, ref: np.ndarray) -> float:
    """Mean over requests and Mamba-2 layers of the relative (Frobenius)
    error of final SSM states (requests, layers, H, P, N)."""
    diff = (states.astype(np.float64) - ref).reshape(*ref.shape[:2], -1)
    norm = np.linalg.norm(ref.astype(np.float64).reshape(diff.shape), axis=-1)
    return float(np.mean(np.linalg.norm(diff, axis=-1) / norm))


def judge(config: dict, seed: int, prompts, served, states, quant=None):
    """Reference logits and final SSM states over each prompt and its
    served tokens; returns the gaps of the served tokens and the error of
    the program's final ``states`` and, with ``quant``, the gaps (under the
    f32 reference) of the tokens the ``quant`` reference would put first
    and the error of its states."""
    d = dims(config)
    p = prompts.shape[1]
    seqs = np.concatenate([prompts, served[:, :-1]], axis=1)
    key, dt = seed_key(seed), serve.DTYPES[config["torch_dtype"]]
    ref, ref_states = reference.logits(d, key, dt, seqs, p - 1)
    out = {"program": serve.gaps(d, ref, served),
           "state_err": state_err(states, ref_states)}
    if quant:
        ctl, ctl_states = reference.logits(d, key, dt, seqs, p - 1,
                                           quant=quant)
        out["control"] = serve.gaps(d, ref, ctl.argmax(-1))
        out["control_state_err"] = state_err(ctl_states, ref_states)
        del ctl
    return out


def run(config: dict, traffic: dict, limits: dict, seed: int,
        seconds: float, trace: bool, t0: float,
        counter: common.CompileCounter) -> dict:
    """One run of the cell: ``serve_mla.run`` with this module's server and
    judge."""
    srv = Server(config, traffic, seed)
    d, b, n = srv.d, srv.b, srv.n
    spec = traffic["trace"]
    tracer = (serve.Tracer(spec["slices"],
                           f"{common.CACHE}/trace/{config['name']}")
              if trace else None)
    trace_batch = spec["batch_index"]

    start = time.perf_counter()
    setup_s = start - t0
    deadline = start + seconds
    counter.armed = True
    batches = []
    while time.perf_counter() < deadline:
        tr = tracer if trace and len(batches) == trace_batch else None
        # Until a batch has finished, the one in flight runs to its end
        # (past the deadline if need be), so that there are answers to
        # check; its tokens after the deadline are not counted.
        finished = any(bt["tokens"].shape[1] == n for bt in batches)
        batches.append(srv.batch(srv.draw(srv.prompts),
                                 deadline if finished else np.inf, tr))
        if tr:
            tr.stop()
    counter.armed = False
    window_compiles = counter.count
    dev = jax.devices()[0]
    mem_peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    # The first batch ran to its end: its checked rows and their states.
    states = np.asarray(srv.final_states[0]).swapaxes(0, 1)
    prompts = batches[0]["prompt"][srv.check_rows]
    served = batches[0]["tokens"][srv.check_rows]
    srv.free()

    # ---- end-to-end numbers: all requests begun in the window ----------
    timed = [bt for bt in batches if bt["start"] < deadline]
    tokens_in = sum(b * sum(t <= deadline for t in bt["times"])
                    for bt in timed)
    ttft = [1e3 * (bt["times"][0] - bt["start"]) for bt in timed
            for _ in range(b)]
    itl = []
    steps_s, step_ctx = 0.0, []
    for bt in timed:
        ts = np.array([t for t in bt["times"] if t <= deadline])
        itl.append(np.diff(ts))          # every gap between two tokens
        steps_s += float(ts[-1] - ts[0]) if len(ts) > 1 else 0.0
        step_ctx += [np.full(b, srv.p + i + 1) for i in range(len(ts) - 1)]
    itl = np.concatenate(itl)
    e2e = {
        "tok_s": tokens_in / seconds,
        "itl_p95_ms": 1e3 * serve._quantile(itl, 95) if itl.size else None,
        "ttft_p90_ms": serve._quantile(ttft, 90),
        "setup_s": setup_s,
    }

    # ---- correctness: the reference on the checked requests ------------
    verdict = judge(config, seed, prompts, served, states)
    g = verdict["program"]
    values = {"gap_max": float(g.max()), "gap_mean": float(g.mean()),
              "state_err": verdict["state_err"]}
    checks = {name: {"value": values[name], "limit": lim["limit"]}
              for name, lim in limits.items()}
    info = {"tokens_compared": int(g.size), **values,
            "exact_share": float((g == 0).mean()),
            "window_compiles": window_compiles}

    ctx = {"dims": d, "device_kind": dev.device_kind,
           "decode_contexts": step_ctx, "decode_host_s": steps_s,
           "batch": b, "prompt_len": srv.p, "trace": None}
    if trace:
        tracer.stop()
    if trace and tracer.dirs and len(batches) > trace_batch:
        m = batches[trace_batch]["tokens"].shape[1] - 1    # decode steps run
        done = tracer.ranges[:len(tracer.dirs)]
        ctx["trace"] = trace_reduce.reduce(
            *[trace_reduce.load(p) for p in tracer.dirs])
        ctx["traced_decode_contexts"] = [
            np.full(b, srv.p + i + 1) for lo, hi in done
            for i in range(max(lo, 0), min(hi, m))]
        ctx["traced_prefill"] = any(lo < 0 for lo, _ in done)
    return {"e2e": e2e, "ctx": ctx, "checks": checks, "info": info,
            "attempted": b * len(timed),
            "failed": int(np.sum(~np.isfinite(g).all(axis=1))),
            "memory_peak_bytes": int(mem_peak)}
