"""Serving cells: a closed loop of static batches through the program's
own serving path (``repro.launch.serve`` phases 1-3 and the jitted steps
of ``repro.launch.steps``), judged against ``bench/reference``.

Set-up makes the weights from the seed on the device, calibrates the
router on a batch of the cell's own shape, applies the Theorem-1 expert
placement, compiles prefill and the decode step and warms every shape the
window uses.  The window then serves batches back to back: prefill, the
first token fetched to the host, then one decode step per further token,
each token fetched as a server streams it.  Once the window has closed the
device state is freed and the reference scores a sample of the finished
requests: for every served token, the gap by which its reference logit
lies below the reference's best.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, trace_reduce
from bench.model_dims import Dims, dims
from bench.reference import moe_lm as reference
from bench.weights import program_params, seed_key

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def program_config(c: dict):
    """The program's ``ModelConfig`` for a config file, dropless routing."""
    from repro.models.config import LayerSpec, ModelConfig
    d = dims(c)
    unsupported = {
        "leading dense layers beyond one": d.n_dense > 1,
        "embedding_multiplier": d.embedding_multiplier != 1.0,
        "residual_multiplier": d.residual_multiplier != 1.0,
        "logits_scaling": d.logits_scaling != 1.0,
        "attention_multiplier": d.attention_multiplier != d.head_dim ** -0.5,
        "norm_topk_prob false": not d.norm_topk,
    }
    bad = [k for k, v in unsupported.items() if v]
    if bad:
        raise ValueError(f"{c['name']}: the program cannot run {bad}")
    return ModelConfig(
        name=c["name"], n_layers=d.n_layers, d_model=d.d_model,
        n_heads=d.n_heads, n_kv_heads=d.n_kv_heads, d_ff=d.d_ff_expert,
        vocab_size=d.vocab, pattern=(LayerSpec("attn", "moe"),),
        head_dim=d.head_dim, n_experts=d.n_experts, top_k=d.top_k,
        n_shared_experts=d.n_shared, d_ff_expert=d.d_ff_expert,
        first_layer_dense=d.n_dense > 0, first_dense_d_ff=d.d_ff_dense,
        capacity_factor=d.n_experts / d.top_k, rope_theta=d.rope_theta,
        tie_embeddings=d.tied, norm_eps=d.norm_eps,
        param_dtype=c["torch_dtype"], compute_dtype=c["torch_dtype"])


class Server:
    """The program's serving path for one seed: weights, placement and the
    compiled prefill and decode step, warmed for the cell's shapes."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.launch import serve, steps
        from repro.models import Parallel, init_params
        self.d: Dims = dims(config)
        self.cfg = program_config(config)
        self.b, self.p = traffic["batch"], traffic["prompt_len"]
        self.n = traffic["output_len"]
        self.prompts = np.random.default_rng([seed, 1])
        structs = jax.eval_shape(functools.partial(init_params, self.cfg),
                                 jax.random.PRNGKey(0))
        params = program_params(structs, seed, self.d.n_layers,
                                self.d.n_dense, self.d.vocab,
                                self.d.tied)
        calib = {"tokens": jnp.asarray(self.draw(np.random.default_rng(
            [seed, 0])))}
        counts = serve.calibrate_router_stats(self.cfg, params, calib)
        self.params, _, _ = serve.plan_and_apply_placement(
            self.cfg, params, counts,
            ep_ring=config["deployment"]["ep_ring"])
        del params, calib
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

    def draw(self, rng) -> np.ndarray:
        return rng.integers(0, self.d.vocab, (self.b, self.p), dtype=np.int32)

    def batch(self, prompt: np.ndarray, deadline: float, tracer=None,
              limit: int | None = None) -> dict:
        """Serve one batch; stop before a step that would start after
        ``deadline``.  Returns the prompt, the tokens fetched (B, n) and
        the host time at which each fetch completed."""
        span = tracer.span if tracer else (lambda name: contextlib.nullcontext())
        n = self.n if limit is None else limit
        rec = {"prompt": prompt, "start": time.perf_counter(), "times": [],
               "tokens": []}
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
                tracer.at_step(i)
            with span("bench.dispatch"):
                tok, logits, cache = self.step(self.params, cache, tok, pos,
                                               None)
                pos = pos + 1
            with span("bench.fetch"):
                rec["tokens"].append(np.asarray(tok))
            rec["times"].append(time.perf_counter())
        if tracer:
            tracer.at_step(n)
        del cache
        rec["tokens"] = np.concatenate(rec["tokens"], axis=1)
        return rec

    def free(self):
        self.params = self.prefill = self.step = None
        gc.collect()


class Tracer:
    """Profiles slices of one batch, each in a profiler session of its own
    inside a ``bench.window`` host span.  A slice is a run of decode steps,
    with the batch's prefill (step -1) where it asks for it; slices come in
    step order and do not overlap."""

    def __init__(self, slices: list[dict], trace_dir: str):
        self.ranges = [(-1 if s.get("prefill") else s.get("from_step", 0),
                        s.get("from_step", 0) + s["decode_steps"])
                       for s in slices]
        self.dir = trace_dir
        self.dirs: list[str] = []
        self.on = False
        self.window = None
        shutil.rmtree(trace_dir, ignore_errors=True)

    def span(self, name):
        return (jax.profiler.TraceAnnotation(name) if self.on
                else contextlib.nullcontext())

    def at_step(self, i: int):
        if self.on and i >= self.ranges[len(self.dirs) - 1][1]:
            self.stop()
        k = len(self.dirs)
        if (not self.on and k < len(self.ranges)
                and self.ranges[k][0] <= i < self.ranges[k][1]):
            self.dirs.append(os.path.join(self.dir, str(k)))
            jax.profiler.start_trace(self.dirs[-1])
            self.window = jax.profiler.TraceAnnotation("bench.window")
            self.window.__enter__()
            self.on = True

    def stop(self):
        if self.on:
            self.window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.on = False


def _quantile(x, q: float) -> float:
    return float(np.percentile(np.asarray(x, dtype=np.float64), q))


def gaps(d: Dims, ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far each served token's reference logit lies below the best."""
    ok = tokens < d.vocab
    pick = np.take_along_axis(ref_logits, np.where(ok, tokens, 0)[..., None],
                              axis=-1)[..., 0]
    return np.where(ok, ref_logits.max(-1) - pick, np.inf)


def sample_finished(batches: list[dict], n: int, k: int, seed: int):
    """Up to ``k`` finished requests, drawn from the seed: (prompt, tokens)."""
    done = [(bi, r) for bi, b in enumerate(batches)
            if b["tokens"].shape[1] == n for r in range(b["tokens"].shape[0])]
    rng = np.random.default_rng([seed, 2])
    pick = rng.choice(len(done), size=min(k, len(done)), replace=False)
    prompts = np.stack([batches[done[i][0]]["prompt"][done[i][1]]
                        for i in sorted(pick)])
    served = np.stack([batches[done[i][0]]["tokens"][done[i][1]]
                       for i in sorted(pick)])
    return prompts, served


def judge(config: dict, seed: int, prompts, served, quant=None):
    """Reference logits over each prompt and its served tokens; returns the
    gaps of the served tokens and, with ``quant``, the gaps (under the f32
    reference) of the tokens the ``quant`` reference would put first."""
    d = dims(config)
    p = prompts.shape[1]
    seqs = np.concatenate([prompts, served[:, :-1]], axis=1)
    key, dt = seed_key(seed), DTYPES[config["torch_dtype"]]
    ref = reference.logits(d, key, dt, seqs, p - 1)
    out = {"program": gaps(d, ref, served)}
    if quant:
        ctl = reference.logits(d, key, dt, seqs, p - 1, quant=quant)
        out["control"] = gaps(d, ref, ctl.argmax(-1))
        del ctl
    return out


def run(config: dict, traffic: dict, limits: dict, seed: int,
        seconds: float, trace: bool, t0: float,
        counter: common.CompileCounter) -> dict:
    srv = Server(config, traffic, seed)
    d, b, n = srv.d, srv.b, srv.n
    spec = traffic["trace"]
    tracer = (Tracer(spec["slices"], f"{common.CACHE}/trace/{config['name']}")
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
        "itl_p95_ms": 1e3 * _quantile(itl, 95) if itl.size else None,
        "ttft_p90_ms": _quantile(ttft, 90),
        "setup_s": setup_s,
    }

    # ---- correctness: the reference on a sample of finished requests ----
    prompts, served = sample_finished(batches, n, traffic["check_requests"],
                                      seed)
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
