"""Plain reference of WordCount: the count of every whitespace-separated
word of the input, computed on the host with no part of the system under
test.

``control`` puts the same reference in a lower precision: each count is
accumulated one occurrence at a time in ``"bfloat16"``, which holds whole
numbers exactly only up to 256.
"""

from __future__ import annotations

from collections import Counter
from typing import Dict, Optional

import ml_dtypes
import numpy as np


def word_counts(text: bytes, control: Optional[str] = None) -> Dict[bytes, int]:
    words = text.split()
    if control is None:
        return dict(Counter(words))
    if control != "bfloat16":
        raise ValueError(f"unknown control precision {control!r}")
    index: Dict[bytes, int] = {}
    idx = np.fromiter((index.setdefault(w, len(index)) for w in words),
                      np.int64, len(words))
    acc = np.zeros(len(index), ml_dtypes.bfloat16)
    np.add.at(acc, idx, ml_dtypes.bfloat16(1))
    return dict(zip(index, acc.astype(np.int64).tolist()))


def mismatches(got: Dict[bytes, int], want: Dict[bytes, int]) -> int:
    """Words whose count differs, is missing or is extra."""
    return sum(1 for w in set(got) | set(want) if got.get(w) != want.get(w))
