"""A configuration file (``perf/configs/<name>.json``): the published
``config.json`` keys verbatim at the top level (depth cut as ``reduced``
says), beside the benchmark's own keys, which are listed here."""

from __future__ import annotations

import json
from pathlib import Path

OWN_KEYS = ("family", "source", "reduced", "published", "assumed", "deployment", "weights_seed", "server_args", "server_args_why", "servers")


def load(path: Path, name: str) -> dict:
    """The file's own keys, plus ``name`` and ``config``: the model's keys as
    the model directory's ``config.json`` gets them."""
    body = json.loads(Path(path).read_text())
    out = {k: body[k] for k in OWN_KEYS if k in body}
    out["name"] = name
    out["config"] = {k: v for k, v in body.items() if k not in OWN_KEYS}
    return out
