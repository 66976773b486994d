"""The table of published peaks, keyed by the exact ``device_kind`` JAX
reports. A device that is not in the table is an error, never a default:
a utilization against a guessed peak is a wrong number under a right
name."""

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} in {_PATH}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]
