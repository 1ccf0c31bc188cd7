"""Published peaks, one file a device, keyed by jax's `device_kind` with
spaces written as `_`. A device that has no file is an error, never a
default."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load(device_kind: str) -> dict:
    path = os.path.join(HERE, device_kind.replace(" ", "_") + ".json")
    try:
        with open(path) as f:
            doc = json.load(f)
    except FileNotFoundError:
        raise LookupError(
            f"no published peaks for device kind {device_kind!r}: add "
            f"{os.path.relpath(path)} with their source"
        ) from None
    if doc["device_kind"] != device_kind:
        raise LookupError(f"{path} is for {doc['device_kind']!r}")
    return doc
