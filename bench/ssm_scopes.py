"""Device time of the ops inside the Mamba-2 mixer's scopes, read from
the paths of a profiler trace.

The program names the parts of its Mamba-2 mixer with these scopes
(``repro.models.layers.SSM_SCOPES``, which a test holds equal to this
copy).  They lie in no stage of ``bench/trace_stages.py``: in the decode
scan their ops have the stage ``layers`` there.  An op's scopes are the
components of its path (of its first part, where the compiler joined
several with ``;``).  ``bench/mla_scopes.py`` reads the MLA scopes the
same way.
"""
from __future__ import annotations

from bench import trace_stages

SSM_SCOPES = ("ssm.proj", "ssm.conv", "ssm.ssd", "ssm.state", "ssm.out")


def scopes(*slices: list[tuple]) -> dict[str, dict[str, float]]:
    """Program -> scope -> device seconds, summed over the slices and
    averaged over the devices as ``trace_stages.stages`` does."""
    out: dict[str, dict[str, float]] = {}
    for events in slices:
        for path, _, t in trace_stages._device_ops(events):
            parts = path.split(";", 1)[0].split("/")
            for scope in SSM_SCOPES:
                if scope in parts:
                    acc = out.setdefault(trace_stages.program_of(path), {})
                    acc[scope] = acc.get(scope, 0.0) + t
    return out


def program_scopes(ctx: dict, part: str) -> dict[str, float] | None:
    """Scope -> device seconds of the program whose name holds ``part``
    (``serve_step``) in the run's trace, read once and kept in ``ctx``;
    None where no op of it lies in an SSM scope, as in a trace of a
    program without them."""
    if "ssm_scopes" not in ctx:
        slices = trace_stages.run_slices(ctx)
        ctx["ssm_scopes"] = scopes(*slices) if slices else {}
    for name, acc in ctx["ssm_scopes"].items():
        if part in name and any(acc.values()):
            return acc
    return None
