"""What the program's ``serve.decode`` span records of each decode step
of the window (``position``; ``held_routes`` for a model with MoE
layers), and the bytes its ``pager.write`` wrote."""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from chipbench import program_spans as ps


def decode_steps(ctx: Any) -> Optional[List[Dict[str, Any]]]:
    """Per decode step of the window: its ``serve.decode`` attrs, with
    ``write_bytes`` added where the step wrote through the pager.  None
    without the program's spans, or where a step lacks ``held_routes``."""
    steps = ps.decode_steps(ctx)
    if steps is None:
        return None
    out = []
    for step in steps:
        dec = next((s for s in step if s.name == "serve.decode"), None)
        if dec is None or "held_routes" not in dec.attrs:
            return None
        attrs = dict(dec.attrs)
        writes = [s.attrs["bytes"] for s in step if s.name == "pager.write"]
        if writes:
            attrs["write_bytes"] = sum(writes)
        out.append(attrs)
    return out
