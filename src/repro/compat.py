"""Thin wrappers over the jax API surface this repo uses (jax >= 0.9).

- ``shard_map`` is ``jax.shard_map``; its replication-check keyword is
  ``check_vma`` (``check_rep`` is accepted as its older alias).
- ``axis_size`` is ``jax.lax.axis_size``.
- ``cost_analysis`` returns ``Compiled.cost_analysis()`` as a flat dict.
"""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, *,
              check_vma: bool | None = None,
              check_rep: bool | None = None, **kwargs):
    """Map ``f`` over shards of its inputs (see ``jax.shard_map``).

    ``check_vma`` and its older name ``check_rep`` are the same flag;
    pass either.  Defaults to ``jax.shard_map``'s default when both are
    None.
    """
    if check_vma is not None and check_rep is not None and check_vma != check_rep:
        raise ValueError("check_vma and check_rep are aliases; got conflicting values")
    flag = check_vma if check_vma is not None else check_rep
    if flag is not None:
        kwargs["check_vma"] = flag
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def axis_size(axis_name) -> int:
    """Static size of a mapped axis."""
    return jax.lax.axis_size(axis_name)


def cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as one flat dict."""
    return compiled.cost_analysis()
