"""Device time of the ops inside the latent-attention scopes, read from
the paths of a profiler trace.

The program nests these scopes inside its attention stages
(``repro.models.layers.MLA_SCOPES``, which a test holds equal to this
copy): an op under ``attn.core/mla.latent`` has the stage ``attn.core``
for ``bench/trace_stages.py`` and, here, also the scope ``mla.latent``.
An op's scopes are the components of its path (of its first part, where
the compiler joined several with ``;``).
"""
from __future__ import annotations

from bench import trace_stages

MLA_SCOPES = ("mla.expand", "mla.absorb", "mla.latent")


def scopes(*slices: list[tuple]) -> dict[str, dict[str, float]]:
    """Program -> scope -> device seconds, summed over the slices and
    averaged over the devices as ``trace_stages.stages`` does."""
    out: dict[str, dict[str, float]] = {}
    for events in slices:
        for path, _, t in trace_stages._device_ops(events):
            parts = path.split(";", 1)[0].split("/")
            for scope in MLA_SCOPES:
                if scope in parts:
                    acc = out.setdefault(trace_stages.program_of(path), {})
                    acc[scope] = acc.get(scope, 0.0) + t
    return out


def program_scopes(ctx: dict, part: str) -> dict[str, float] | None:
    """Scope -> device seconds of the program whose name holds ``part``
    (``serve_step``) in the run's trace, read once and kept in ``ctx``;
    None where no op of it lies in an MLA scope, as in a trace of a
    program without them."""
    if "mla_scopes" not in ctx:
        slices = trace_stages.run_slices(ctx)
        ctx["mla_scopes"] = scopes(*slices) if slices else {}
    for name, acc in ctx["mla_scopes"].items():
        if part in name and any(acc.values()):
            return acc
    return None
