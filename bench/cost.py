"""Bytes the algorithms need, computed from shapes.

These are the yardstick's own numbers: a roofline share divides them by a
measured time.  Padding and work that the served result does not need are
not counted.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np


def port_payload_bytes(args: Sequence) -> int:
    """Bytes one port request must read and write: every pointer argument
    is either read (inputs) or written (outputs) once, at its length."""
    return int(sum(a.nbytes for a in args if isinstance(a, np.ndarray)))
