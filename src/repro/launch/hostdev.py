"""Force CPU host devices BEFORE jax initializes its backend.

``--xla_force_host_platform_device_count`` is an XLA flag, not a
runtime toggle, so CLIs that offer ``--host-devices N`` must apply it
from ``sys.argv`` before their first ``import jax``.  This module
deliberately imports nothing heavy so it is safe at the very top of an
entry point.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence


def parse_host_devices(argv: Sequence[str]) -> Optional[int]:
    """The value of ``--host-devices N`` / ``--host-devices=N`` in
    ``argv``, or None.  Malformed forms (missing or non-integer value)
    return None and are left for argparse to reject with a real usage
    error after jax import."""
    value = None
    for i, tok in enumerate(argv):
        if tok == "--host-devices" and i + 1 < len(argv):
            value = argv[i + 1]
        elif tok.startswith("--host-devices="):
            value = tok.split("=", 1)[1]
    if value is None:
        return None
    try:
        return int(value)
    except ValueError:
        return None


def force_host_devices(argv: Sequence[str]) -> None:
    """Apply ``--host-devices`` from ``argv`` to XLA_FLAGS (idempotent
    no-op when the flag is absent/malformed)."""
    n = parse_host_devices(argv)
    if n is None:
        return
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n}")
