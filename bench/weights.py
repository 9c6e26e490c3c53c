"""Seeded model weights, made by the benchmark and not by the program.

Every weight is a function of ``(seed, name, layer)`` alone, so the served
model and the plain reference (``bench/reference``) can each make the same
values without sharing a buffer: the program gets the whole tree in one
jitted call on the device, laid out as its own ``init_params`` lays it out;
the reference makes one layer at a time after the program's state is freed.

Values are uniform with the standard deviations of the usual GPT-2 style
initialisation (0.02, and 0.02 / sqrt(2 L) on the projections back into the
residual stream).  They are built from integer random bits by one
multiplication and one cast, so the two programs round them alike.
Embedding and head rows past the published vocabulary are zero: the
program pads its vocabulary, the model has no such tokens.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

STD = 0.02
#: A tied embedding is also the head.  At 0.02 the last input token's own
#: row dominates the random residual stream and greedy decoding only
#: echoes it; a tenth of that lets the layers decide the next token.
TIED_EMBED_STD = 0.002

# Program parameter path (suffix after the block) -> canonical name.
_BLOCK_LEAVES = {
    ("norm1", "scale"): "norm.attn",
    ("norm2", "scale"): "norm.ffn",
    ("mixer", "w_q"): "attn.q",
    ("mixer", "w_k"): "attn.k",
    ("mixer", "w_v"): "attn.v",
    ("mixer", "w_o"): "attn.o",
    ("ffn", "router"): "moe.router",
    ("ffn", "shared", "w_gate"): "shared.gate",
    ("ffn", "shared", "w_up"): "shared.up",
    ("ffn", "shared", "w_down"): "shared.down",
}
_TOP_LEAVES = {
    ("embed",): "embed",
    ("head",): "head",
    ("final_norm", "scale"): "norm.final",
}
#: Projections back into the residual stream take the scaled-down std.
_OUT_PROJ = {"attn.o", "ffn.down", "moe.down", "shared.down"}


def seed_key(seed: int) -> jax.Array:
    """A JAX key from any whole-number seed, 64-bit ones included."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def _name_id(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def leaf_std(name: str, n_layers: int, tied: bool = False) -> float:
    if name in _OUT_PROJ:
        return STD / math.sqrt(2 * n_layers)
    if name == "embed" and tied:
        return TIED_EMBED_STD
    return STD


def make_leaf(key, name: str, layer, shape, dtype, n_layers: int,
              vocab: int | None = None, tied: bool = False):
    """One weight (or one layer's slice of it): ``layer`` is -1 for the
    embedding, head and final norm, and may be traced (under ``vmap``)."""
    if vocab is not None and name in ("embed", "head"):
        # Make the published vocabulary's rows, then pad with zeros: the
        # values do not depend on how far the program pads.
        axis = 0 if name == "embed" else 1
        real = list(shape)
        real[axis] = vocab
        x = make_leaf(key, name, layer, tuple(real), dtype, n_layers,
                      tied=tied)
        pad = [(0, 0), (0, 0)]
        pad[axis] = (0, shape[axis] - vocab)
        return jnp.pad(x, pad)
    k = jax.random.fold_in(jax.random.fold_in(key, _name_id(name)), layer + 1)
    bits = jax.random.bits(k, shape, jnp.uint32)
    centred = (bits >> 8).astype(jnp.int32) - (1 << 23)       # [-2^23, 2^23)
    if name.startswith("norm."):
        # Scales near 1, so that a norm applied without its scale shows.
        x = (centred.astype(jnp.float32) * np.float32(0.1 / 2**23)
             ).astype(jnp.bfloat16).astype(jnp.float32) + np.float32(1.0)
        return x.astype(dtype)
    width = np.float32(leaf_std(name, n_layers, tied) * math.sqrt(3.0)
                       / 2**23)
    return (centred.astype(jnp.float32) * width).astype(dtype)


def _canonical(path: tuple[str, ...], moe_block: bool) -> str:
    if path in _TOP_LEAVES:
        return _TOP_LEAVES[path]
    tail = path[1:] if path[0] == "first" else path[2:]
    if tail in _BLOCK_LEAVES:
        return _BLOCK_LEAVES[tail]
    if tail[0] == "ffn" and tail[1] in ("w_gate", "w_up", "w_down"):
        return ("moe." if moe_block else "ffn.") + tail[1][2:]
    raise KeyError(f"no canonical name for parameter {'/'.join(path)}")


def program_params(structs, seed: int, n_layers: int, first_dense: int,
                   vocab: int, tied: bool):
    """The program's parameter tree (``structs`` from ``jax.eval_shape`` of
    its ``init_params``) filled from ``seed`` in one jitted call.

    Stacked leaves under ``units`` hold layers ``first_dense + u``.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(structs)
    paths = [tuple(k.key for k in kp) for kp, _ in flat]

    def block(path):
        return path[:1] if path[0] == "first" else path[:2]

    moe_blocks = {block(p) for p in paths if p[-2:] == ("ffn", "router")}
    plan = [(p, _canonical(p, block(p) in moe_blocks), s)
            for p, (_, s) in zip(paths, flat)]

    def build(key):
        leaves = []
        for path, name, s in plan:
            if path[0] == "units":
                f = jax.vmap(lambda u, name=name, s=s: make_leaf(
                    key, name, first_dense + u, s.shape[1:], s.dtype,
                    n_layers, vocab))
                leaves.append(f(jnp.arange(s.shape[0])))
            else:
                layer = 0 if path[0] == "first" else -1
                leaves.append(make_leaf(key, name, layer, s.shape, s.dtype,
                                        n_layers, vocab, tied))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(seed_key(seed))
