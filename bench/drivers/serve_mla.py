"""Serving cells of latent-attention (MLA) models: the closed loop of
``bench/drivers/serve.py`` through the program's own jitted prefill and
decode step (``repro.launch.steps``), judged against the MLA reference
(``bench/reference/mla_moe_lm.py``).

What differs from ``serve.py`` is the model and the decode loop's depth.
The program's config has the ``("mla", "moe")`` pattern and holds the
config file's share of the routed experts, its weights carry the MLA
leaves, and no placement is applied (the Theorem-1 placement permutes
all experts and the program refuses it for a held share).  The decode
loop keeps ``IN_FLIGHT`` steps dispatched ahead of the token it fetches
(``Server.batch``), so that neither the host's round trip between steps
(about 2 ms on a one-chip v5e host) nor its pauses shorter than those
steps leave the device idle.  Tracing and the sample of finished
requests are ``serve.py``'s own (``Server.draw`` and ``free``,
``Tracer``, ``gaps``, ``sample_finished``); the end-to-end numbers are
computed exactly as there.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, trace_reduce
from bench.drivers import serve
from bench.mla_dims import MlaDims, dims
from bench.reference import mla_moe_lm as reference
from bench.weights import _canonical, make_leaf, seed_key

#: Decode steps dispatched and not yet fetched.  Each holds its logits
#: (B x vocab, bf16: 26 MB in the MLA cell) until it has run; 32 steps
#: (0.84 GB) are ~1 s of device work there, several times the 120-150 ms
#: pauses seen in the host loop on a one-chip v5e host.
IN_FLIGHT = 32

#: Program parameter path (suffix after the block) -> canonical name, for
#: the leaves the GQA map (``bench/weights.py``) does not have.
_MLA_LEAVES = {
    ("mixer", "w_kv_a"): "attn.kv_a",
    ("mixer", "kv_norm", "scale"): "norm.kv",
    ("mixer", "w_kv_b"): "attn.kv_b",
}


def program_config(c: dict):
    """The program's ``ModelConfig`` for an MLA config file: the held share
    of the routed experts, dropless routing."""
    from repro.models.config import LayerSpec, ModelConfig, YarnScaling
    d = dims(c)
    unsupported = {
        "leading dense layers beyond one": d.n_dense > 1,
        "norm_topk_prob false": not d.norm_topk,
    }
    bad = [k for k, v in unsupported.items() if v]
    if bad:
        raise ValueError(f"{c['name']}: the program cannot run {bad}")
    yarn = YarnScaling(
        factor=d.yarn_factor,
        original_max_position_embeddings=d.yarn_original_max,
        beta_fast=d.yarn_beta_fast, beta_slow=d.yarn_beta_slow,
        mscale=d.yarn_mscale, mscale_all_dim=d.yarn_mscale_all_dim)
    return ModelConfig(
        name=c["name"], n_layers=d.n_layers, d_model=d.d_model,
        n_heads=d.n_heads, n_kv_heads=d.n_heads, d_ff=d.d_ff_expert,
        vocab_size=d.vocab, pattern=(LayerSpec("mla", "moe"),),
        n_experts=d.n_experts, n_experts_held=d.n_held, top_k=d.top_k,
        n_shared_experts=d.n_shared, d_ff_expert=d.d_ff_expert,
        first_layer_dense=d.n_dense > 0, first_dense_d_ff=d.d_ff_dense,
        capacity_factor=d.n_experts / d.top_k, rope_theta=d.rope_theta,
        rope_scaling=yarn, kv_lora_rank=d.kv_lora_rank,
        qk_nope_head_dim=d.qk_nope, qk_rope_head_dim=d.qk_rope,
        v_head_dim=d.v_head, tie_embeddings=d.tied, norm_eps=d.norm_eps,
        param_dtype=c["torch_dtype"], compute_dtype=c["torch_dtype"])


def _name(path: tuple[str, ...], moe_block: bool) -> str:
    tail = path[1:] if path[0] == "first" else path[2:]
    return _MLA_LEAVES.get(tail) or _canonical(path, moe_block)


def program_params(structs, seed: int, d: MlaDims):
    """The program's parameter tree (``structs`` from ``jax.eval_shape`` of
    its ``init_params``) filled from ``seed`` in one jitted call, each leaf
    as ``make_leaf`` makes it for the reference.  Stacked leaves under
    ``units`` hold layers ``n_dense + u``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(structs)
    paths = [tuple(k.key for k in kp) for kp, _ in flat]
    moe_blocks = {p[:2] for p in paths if p[-2:] == ("ffn", "router")}
    plan = [(p, _name(p, p[:2] in moe_blocks), s)
            for p, (_, s) in zip(paths, flat)]

    def build(key):
        leaves = []
        for path, name, s in plan:
            if path[0] == "units":
                f = jax.vmap(lambda u, name=name, s=s: make_leaf(
                    key, name, d.n_dense + u, s.shape[1:], s.dtype,
                    d.n_layers, d.vocab))
                leaves.append(f(jnp.arange(s.shape[0])))
            else:
                layer = 0 if path[0] == "first" else -1
                leaves.append(make_leaf(key, name, layer, s.shape, s.dtype,
                                        d.n_layers, d.vocab, d.tied))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(seed_key(seed))


class Server(serve.Server):
    """``serve.Server`` for an MLA model: weights made from the seed, the
    compiled prefill and decode step warmed for the cell's shapes; no
    calibration and no placement."""

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
        self.step = jax.jit(steps.make_serve_step(self.cfg, par),
                            donate_argnums=(1,)).lower(
            self.params, cache, tok, pos, None).compile()
        del logits, cache, tok
        # Warm every shape the window uses: one batch of three tokens.
        self.batch(self.draw(self.prompts), np.inf, limit=3)

    def batch(self, prompt: np.ndarray, deadline: float, tracer=None,
              limit: int | None = None) -> dict:
        """``serve.Server.batch`` with up to ``IN_FLIGHT`` decode steps
        dispatched before the oldest one's token is fetched: JAX dispatches
        asynchronously and each step takes the one before's token on the
        device, so the host's fetch, bookkeeping and pauses overlap the
        device's queued steps instead of leaving it idle.  Tokens, the host
        time at which each fetch completed and the deadline (no step starts
        after it) are as there.  A traced batch fetches every step in
        flight at each slice's edges, so that a profiler session holds
        whole steps."""
        span = tracer.span if tracer else (lambda name: contextlib.nullcontext())
        n = self.n if limit is None else limit
        edges = {e for r in tracer.ranges for e in r} if tracer else set()
        rec = {"prompt": prompt, "start": time.perf_counter(), "times": [],
               "tokens": []}
        pending = collections.deque()    # dispatched steps' unfetched tokens

        def fetch():
            with span("bench.fetch"):
                rec["tokens"].append(np.asarray(pending.popleft()))
            rec["times"].append(time.perf_counter())

        if tracer:
            tracer.at_step(-1)
        with span("bench.prefill"):
            logits, cache = self.prefill(self.params,
                                         {"tokens": jnp.asarray(prompt)})
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            rec["tokens"].append(np.asarray(tok))
        rec["times"].append(time.perf_counter())
        del logits
        pos = jnp.full((self.b,), self.p, jnp.int32)
        for i in range(n - 1):
            if time.perf_counter() >= deadline:
                break
            if tracer:
                if i in edges:
                    while pending:
                        fetch()
                tracer.at_step(i)
            with span("bench.dispatch"):
                tok, logits, cache = self.step(self.params, cache, tok, pos,
                                               None)
                pos = pos + 1
            pending.append(tok)
            if len(pending) > IN_FLIGHT:
                fetch()
        while pending:
            fetch()
        if tracer:
            tracer.at_step(n)
        del cache
        rec["tokens"] = np.concatenate(rec["tokens"], axis=1)
        return rec


def judge(config: dict, seed: int, prompts, served, quant=None):
    """Reference logits over each prompt and its served tokens; returns the
    gaps of the served tokens and, with ``quant``, the gaps (under the f32
    reference) of the tokens the ``quant`` reference would put first."""
    d = dims(config)
    p = prompts.shape[1]
    seqs = np.concatenate([prompts, served[:, :-1]], axis=1)
    key, dt = seed_key(seed), serve.DTYPES[config["torch_dtype"]]
    ref = reference.logits(d, key, dt, seqs, p - 1)
    out = {"program": serve.gaps(d, ref, served)}
    if quant:
        ctl = reference.logits(d, key, dt, seqs, p - 1, quant=quant)
        out["control"] = serve.gaps(d, ref, ctl.argmax(-1))
        del ctl
    return out


def run(config: dict, traffic: dict, limits: dict, seed: int,
        seconds: float, trace: bool, t0: float,
        counter: common.CompileCounter) -> dict:
    """One run of the cell: ``serve.run``'s loop and numbers, with this
    module's server and judge."""
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

    # ---- correctness: the reference on a sample of finished requests ----
    prompts, served = serve.sample_finished(
        batches, n, traffic["check_requests"], seed)
    g = judge(config, seed, prompts, served)["program"]
    values = {"gap_max": float(g.max()), "gap_mean": float(g.mean())}
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
