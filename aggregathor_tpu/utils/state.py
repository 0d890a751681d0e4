"""Tiny atomic JSON state files for a resumable harness
(benchmarks/robustness.py's per-cell resume): load tolerates a
missing/corrupt/non-dict file by returning the default, save goes through a
tmp file + os.replace so a kill mid-write can never leave a half-written
state behind.
"""

import json
import os


def load_json(path, default=None):
    """The dict stored at ``path``, or ``default`` (fresh {}) if unreadable."""
    try:
        with open(path) as fd:
            data = json.load(fd)
    except (OSError, ValueError):
        data = None
    if not isinstance(data, dict):
        return {} if default is None else default
    return data


def save_json_atomic(path, state):
    tmp = path + ".tmp"
    with open(tmp, "w") as fd:
        json.dump(state, fd, indent=1)
    os.replace(tmp, path)
