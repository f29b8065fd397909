"""The program's own XFA fold, for the readers of its serve counters
(not a metric itself).

A reader is handed no fold, so these read the process tracer's fold as
the run leaves it: the window, its drain and the check's steps.  The
warm-up calls the compiled programs directly and folds none of the
serve engine's tick edges.  A program without the edge (one older than
the counter) reads None."""

from __future__ import annotations


def edge(api: str, component: str = "serve"):
    """`component.api` merged over every caller, or None."""
    from repro.profile import tracer_folded
    hits = [e for k, e in tracer_folded().edges.items()
            if k[1] == component and k[2] == api]
    if not hits:
        return None
    out = hits[0]
    for e in hits[1:]:
        out = out.merge(e)
    return out
