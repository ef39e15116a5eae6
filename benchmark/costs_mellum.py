"""Operations and bytes of ``mellum2-12b-a2.5b``'s window layers'
attention calls, from shapes alone: the rule of
``costs_trinity.attention_window_call`` at this configuration's window
(``costs.py`` prices the full layers' calls as it stands).

A call named ``attention_window*`` attends a sliding window of
``WINDOW`` keys (the configuration's ``sliding_window``, 1024). What it
is handed is wider: the gathered tail holds the window plus the blocks
at its edges (80 blocks, 1280 positions), and a chunk's call holds the
tail and the chunk. The algorithm needs, for a query, at most
``WINDOW`` keys and never more than the call holds causally; that is
what is counted, so a call is never credited with work it cannot have
done. The count is an upper bound of the algorithm's work where the
context is shorter than the window (a prompt's first chunk, a lane's
first thousand positions in a decode call): the shapes do not say how
much of the tail is written.
"""

from __future__ import annotations

import json
import os
from typing import Tuple

from benchmark.costs import ITEMSIZE

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "configs", "mellum2-12b-a2.5b.json")) as _f:
    WINDOW = json.load(_f)["sliding_window"]


def _size(t, dims) -> int:
    n = ITEMSIZE[t]
    for dim in dims:
        n *= dim
    return n


def window_call(results, operands, window: int) -> Tuple[float, float]:
    """(operations, bytes) of one call of the flash kernel under a
    sliding window of ``window`` keys. q is the first bf16 operand, k
    the second, v the third.

    A decode call carries the query heads that share a kv head as the
    rows of one block (q's leading dimensions are k's): every row is
    the one new token and sees ``min(window, sk)`` keys. A chunk call
    (q ``[lanes * heads, sq, d]``, k ``[lanes * kv_heads, sk, d]``, the
    chunk's own keys last) gives query ``i`` the keys up to its own:
    ``min(window, sk - sq + i + 1)``. Two products, QK^T and PV: 4 d
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
        pairs = sq * min(window, sk)
        keys = min(window, sk)
    else:
        pairs = sum(min(window, sk - sq + i + 1) for i in range(sq))
        keys = min(sk, window + sq - 1)
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


def attention_window_call(results, operands) -> Tuple[float, float]:
    """:func:`window_call` at this configuration's ``WINDOW``."""
    return window_call(results, operands, WINDOW)
