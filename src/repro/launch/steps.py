"""Step functions + ShapeDtypeStruct input specs for lowering.

``input_specs(cfg, shape)`` produces weak-type-correct, shardable
stand-ins for every model input (no device allocation): train batches,
prefill prompts, or (cache, token, pos) decode triples — the same pattern
the multi-pod dry-run lowers with.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.shapes import ShapeSpec
from repro.models import (ModelConfig, Parallel, batch_specs, decode_step,
                          init_cache, init_params, loss_fn, prefill)
from repro.optim import AdamWConfig, adamw_init, adamw_update


# --------------------------------------------------------------------- #
# Step functions (pure; jit/lower at the call site)
# --------------------------------------------------------------------- #


def make_train_step(cfg: ModelConfig, par: Parallel,
                    opt_cfg: AdamWConfig = AdamWConfig(), schedule=None,
                    micro_batches: int = 1):
    """One optimizer step; with ``micro_batches > 1`` the global batch is
    processed as a ``lax.scan`` of gradient-accumulation slices, so live
    activation memory (incl. per-layer saved residuals) scales with the
    micro-batch, not the global batch."""
    schedule = schedule or (lambda s: 1.0)
    grad_fn = jax.value_and_grad(
        functools.partial(loss_fn, cfg, par=par), has_aux=True
    )

    def train_step(params, opt_state, batch):
        if micro_batches == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            def split(x):
                b = x.shape[0]
                if b % micro_batches:
                    raise ValueError(
                        f"batch {b} not divisible by {micro_batches} slices")
                return x.reshape(micro_batches, b // micro_batches,
                                 *x.shape[1:])

            mb = jax.tree.map(split, batch)
            if par.mesh is not None:
                # keep the batch sharded over the data axes after the
                # (global, ...) -> (micro, global/micro, ...) reshape —
                # without this XLA may replicate the microbatch slices.
                from jax.sharding import PartitionSpec as P
                baxes = (par.data_axes if len(par.data_axes) > 1
                         else par.data_axes[0])
                mb = jax.tree.map(
                    lambda x: jax.lax.with_sharding_constraint(
                        x, P(None, baxes, *([None] * (x.ndim - 2)))
                    ),
                    mb,
                )

            def acc_step(grads, mb_batch):
                (l, m), g = grad_fn(params, mb_batch)
                grads = jax.tree.map(jnp.add, grads, g)
                return grads, (l, m["ce"], m["aux"])

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            grads, (ls, ces, auxs) = jax.lax.scan(acc_step, zeros, mb)
            grads = jax.tree.map(lambda g: g / micro_batches, grads)
            loss = ls.mean()
            metrics = {"ce": ces.mean(), "aux": auxs.mean()}
        params, opt_state, gnorm = adamw_update(
            opt_cfg, params, grads, opt_state, schedule(opt_state["count"])
        )
        out_metrics = {
            "loss": loss, "ce": metrics["ce"], "aux": metrics["aux"],
            "grad_norm": gnorm,
        }
        return params, opt_state, out_metrics

    return train_step


def make_prefill_step(cfg: ModelConfig, par: Parallel, max_len: int):
    def prefill_step(params, batch):
        return prefill(cfg, params, batch, max_len=max_len, par=par)

    return prefill_step


def make_serve_step(cfg: ModelConfig, par: Parallel):
    """One decode step: greedy next token + updated cache.

    ``embeds`` is positional (pjit forbids kwargs with in_shardings); pass
    None for token-input archs.
    """

    def serve_step(params, cache, tokens, pos, embeds):
        logits, cache = decode_step(cfg, params, cache, tokens, pos, par=par,
                                    embeds=embeds)
        with jax.named_scope("head"):
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return next_tok, logits, cache

    return serve_step


# --------------------------------------------------------------------- #
# ShapeDtypeStruct stand-ins
# --------------------------------------------------------------------- #


def param_structs(cfg: ModelConfig):
    return jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.PRNGKey(0)
    )


def opt_structs(params_structs):
    return jax.eval_shape(adamw_init, params_structs)


def cache_structs(cfg: ModelConfig, batch: int, max_len: int):
    return jax.eval_shape(
        functools.partial(init_cache, cfg, batch, max_len)
    )


def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> dict[str, Any]:
    """All lowering inputs for one (arch x shape) cell, as structs.

    train:   {params, opt_state, batch}
    prefill: {params, batch}            (batch without labels)
    decode:  {params, cache, tokens, pos [, embeds]}
    """
    p = param_structs(cfg)
    if shape.kind == "train":
        return {
            "params": p,
            "opt_state": opt_structs(p),
            "batch": batch_specs(cfg, shape.global_batch, shape.seq_len),
        }
    if shape.kind == "prefill":
        b = batch_specs(cfg, shape.global_batch, shape.seq_len)
        b.pop("labels")
        return {"params": p, "batch": b}
    if shape.kind == "decode":
        out = {
            "params": p,
            "cache": cache_structs(cfg, shape.global_batch, shape.seq_len),
            "pos": jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32),
        }
        if cfg.frontend == "audio":
            out["tokens"] = None
            out["embeds"] = jax.ShapeDtypeStruct(
                (shape.global_batch, 1, cfg.d_model), jnp.bfloat16
            )
        else:
            out["tokens"] = jax.ShapeDtypeStruct(
                (shape.global_batch, 1), jnp.int32
            )
        return out
    raise ValueError(shape.kind)
