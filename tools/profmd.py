"""Shared profile-record writer for the kernel tools (kexp/kattr).

Each run appends a markdown section to ``profiles/<tag>_PROFILE.md``
(tag from $PROFILE_TAG, default LOCAL), so kernel measurements stop
living only in scrollback: the round-5 optimization notes referenced a
hand-maintained profiles/R05_PROFILE.md — this makes the tools produce
that file themselves.
"""

from __future__ import annotations

import os
import sys
import time


def require_tpu():
    """The device a kernel profile may be taken on.  A profile is a
    device measurement: without a TPU there is none to take (the Pallas
    interpreter on a CPU times the interpreter), so the tool stops."""
    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"no TPU, no profile: found {device} — run this tool "
                 "on the chip")
    return device


def profile_path(tag: str | None = None) -> str:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tag = tag or os.environ.get("PROFILE_TAG", "LOCAL")
    return os.path.join(root, "profiles", f"{tag}_PROFILE.md")


def append_section(tool: str, device: str, shape: dict,
                   rows: list[tuple], columns: tuple,
                   tag: str | None = None, notes: str = "") -> str:
    """Append one run's results table; creates the file with a header
    on first write.  Returns the path written."""
    path = profile_path(tag)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    new = not os.path.exists(path)
    stamp = time.strftime("%Y-%m-%d %H:%M:%S", time.gmtime())
    with open(path, "a", encoding="utf-8") as fh:
        if new:
            fh.write(f"# {os.path.basename(path)[:-11]} kernel "
                     "profile\n\nAppended by tools/kexp.py and "
                     "tools/kattr.py (PROFILE_TAG selects the file).\n")
        shape_s = ", ".join(f"{k}={v}" for k, v in shape.items())
        fh.write(f"\n## {tool} — {stamp} UTC\n\n"
                 f"device: `{device}`; {shape_s}\n\n")
        fh.write("| " + " | ".join(columns) + " |\n")
        fh.write("|" + "---|" * len(columns) + "\n")
        for row in rows:
            fh.write("| " + " | ".join(str(c) for c in row) + " |\n")
        if notes:
            fh.write(f"\n{notes}\n")
    return path
