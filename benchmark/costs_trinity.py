"""Operations and bytes of the window layers' attention calls, from
shapes alone (``costs.py`` prices the full layers' calls as it stands).

A call named ``attention_window*`` attends a sliding window of
``WINDOW`` keys (the configuration's ``sliding_window``). What it is
handed is wider: the gathered tail holds the window plus the blocks at
its edges (5120 positions for 4096), and a chunk's call holds the tail
and the chunk. The algorithm needs, for a query, at most ``WINDOW``
keys and never more than the call holds causally; that is what is
counted, so a call is never credited with work it cannot have done.
The count is an upper bound of the algorithm's work where the context
is shorter than the window (a prompt's first chunks, short lanes in a
decode call): the shapes do not say how much of the tail is written.
"""

from __future__ import annotations

import json
import os
from typing import Tuple

from benchmark.costs import ITEMSIZE

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "configs", "trinity-large-preview.json")) as _f:
    WINDOW = json.load(_f)["sliding_window"]


def _size(t, dims) -> int:
    n = ITEMSIZE[t]
    for dim in dims:
        n *= dim
    return n


def attention_window_call(results, operands) -> Tuple[float, float]:
    """(operations, bytes) of one windowed call of the flash kernel.
    q is the first bf16 operand, k the second, v the third.

    A decode call carries the query heads that share a kv head as the
    rows of one block (q's leading dimensions are k's): every row is
    the one new token and sees ``min(WINDOW, sk)`` keys. A chunk call
    (q ``[lanes * heads, sq, d]``, k ``[lanes * kv_heads, sk, d]``, the
    chunk's own keys last) gives query ``i`` the keys up to its own:
    ``min(WINDOW, sk - sq + i + 1)``. Two products, QK^T and PV: 4 d
    operations a pair. Bytes: q, the results and the masks' operands
    once, and of K and V the keys some query sees."""
    big = [(t, dims) for t, dims in operands
           if t == "bf16" and len(dims) >= 3]
    (_, q), (kt, k) = big[0], big[1]
    heads = 1
    for n in q[:-2]:
        heads *= n
    kv_heads = 1
    for n in k[:-2]:
        kv_heads *= n
    sq, d, sk = q[-2], q[-1], k[-2]
    if heads == kv_heads:                  # decode: rows are heads
        pairs = sq * min(WINDOW, sk)
        keys = min(WINDOW, sk)
    else:
        pairs = sum(min(WINDOW, sk - sq + i + 1) for i in range(sq))
        keys = min(sk, WINDOW + sq - 1)
    flops = 4.0 * heads * pairs * d
    nbytes = sum(_size(t, dims) for t, dims in results)
    seen = 0
    for t, dims in operands:
        if t == kt and list(dims) == list(k) and seen < 2:
            seen += 1                      # K, then V
            nbytes += _size(t, dims) // sk * keys
        else:
            nbytes += _size(t, dims)
    return flops, float(nbytes)
