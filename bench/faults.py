"""The control and the planted faults a serving cell's comparison has to
catch, each put under a run as ``run.run_cell`` drives it.

Modes:

- ``program``: nothing planted, a sound run;
- ``control``: the reference in float8 (the precision below the bfloat16
  the configurations state) in the program's place: at each position of
  the served requests the token that float8 puts first is scored, under the
  float32 reference, instead of the served one;
- ``state_unchanged``: the decode step returns the cache it was given;
- ``half_batch``: the second half of the batch is left out of every decode
  step: its rows keep the token they had;
- ``token_altered``: the token every request produces at its third decode
  step is replaced by the next id.

No cell runs on several chips, so there is no exchange between chips to
leave out.
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp

MODES = ("program", "control", "state_unchanged", "half_batch",
         "token_altered")
#: Decode step (0 = the step after prefill) whose token ``token_altered``
#: replaces.
ALTERED_STEP = 2


def broken_step(fault: str, good_factory, prompt_len: int, vocab: int):
    """A ``make_serve_step`` whose steps carry ``fault``."""
    def factory(cfg, par):
        good = good_factory(cfg, par)

        def step(params, cache, tokens, pos, embeds):
            tok, logits, new_cache = good(params, cache, tokens, pos, embeds)
            if fault == "state_unchanged":
                return tok, logits, cache
            if fault == "half_batch":
                h = tokens.shape[0] // 2
                return tok.at[h:].set(tokens[h:]), logits, new_cache
            if fault == "token_altered":
                hit = pos[:, None] == prompt_len + ALTERED_STEP
                return (jnp.where(hit, (tok + 1) % vocab, tok), logits,
                        new_cache)
            raise ValueError(fault)
        return step
    return factory


@contextlib.contextmanager
def planted(mode: str, config: dict, traffic: dict):
    """Run the body with ``mode`` planted under the serving driver."""
    from bench.drivers import serve
    from bench.model_dims import dims
    from repro.launch import steps
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}; known: {MODES}")
    good_step, good_judge = steps.make_serve_step, serve.judge
    if mode == "control":
        def judge(config, seed, prompts, served, quant=None):
            ctl = good_judge(config, seed, prompts, served, quant="fp8")
            return {"program": ctl["control"]}
        serve.judge = judge
    elif mode != "program":
        steps.make_serve_step = broken_step(
            mode, good_step, traffic["prompt_len"], dims(config).vocab)
    try:
        yield
    finally:
        steps.make_serve_step, serve.judge = good_step, good_judge
