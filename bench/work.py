"""The work the algorithm needs, computed from shapes: what a kernel's
roofline share and the step's ``mfu`` are read against.  Nothing here
looks at how the program computes it (no HLO, no padding, no page
rounding), so a kernel that reads fewer pages or a fused step is read
against the same work.

Paged attention, per layer:

* a decode row attending ``n`` positions needs ``4 * H * hd * n`` FLOPs
  (``q k^T`` and ``p v``, two FLOPs per multiply-add) and reads the K and V
  of those ``n`` positions once, plus its query and writes its output;
* a prefill chunk is rows that share one block list: row ``i`` attends
  ``start + i + 1`` positions (FLOPs per row as above), and the chunk
  needs the K/V of its ``end`` positions once, not once per row.

A whole token, for ``mfu``: two FLOPs per weight it multiplies (the
attention and MLP projections of every layer, and the output head for a
token that is sampled) plus the attention FLOPs at its context.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

__all__ = ["kv_bytes_per_position", "decode_row", "prefill_chunk",
           "token_flops", "roofline_time"]

Q_ITEMSIZE = 4          # the query and the output cross the kernel in f32


def kv_bytes_per_position(m: Dict, itemsize: int = 2) -> int:
    """K and V of one position in one layer."""
    return 2 * m["n_kv_heads"] * m["head_dim"] * itemsize


def _qo_bytes(m: Dict, rows: int) -> int:
    return 2 * rows * m["n_heads"] * m["head_dim"] * Q_ITEMSIZE


def decode_row(m: Dict, n: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode row attending ``n`` positions, one
    layer."""
    return (4.0 * m["n_heads"] * m["head_dim"] * n,
            float(n * kv_bytes_per_position(m) + _qo_bytes(m, 1)))


def prefill_chunk(m: Dict, start: int, end: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of a prefill chunk of positions ``[start, end)``,
    one layer: row ``p`` attends ``p + 1`` positions."""
    rows = end - start
    attended = (start + 1 + end) * rows / 2          # sum of p + 1
    return (4.0 * m["n_heads"] * m["head_dim"] * attended,
            float(end * kv_bytes_per_position(m) + _qo_bytes(m, rows)))


def roofline_time(calls: Iterable[Tuple[float, float]],
                  peaks: Dict) -> Dict[str, float]:
    """Least time for ``(FLOPs, bytes)`` calls on a chip with ``peaks``:
    each call takes the larger of its FLOPs over peak FLOP/s and its bytes
    over peak bandwidth.  Returns the total and the part bound by each."""
    pf, pb = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    t_f = t_b = 0.0
    for f, b in calls:
        tf, tb = f / pf, b / pb
        if tf >= tb:
            t_f += tf
        else:
            t_b += tb
    return {"s": t_f + t_b, "flops_bound_s": t_f, "bytes_bound_s": t_b}


def token_flops(m: Dict, context: int, *, sampled: bool) -> float:
    """Model FLOPs of one token whose attention reads ``context``
    positions in every layer."""
    D, H, Hkv, hd, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["head_dim"], m["d_ff"])
    n_in = 2 if m["act"] == "silu" else 1
    per_layer = D * H * hd + 2 * D * Hkv * hd + H * hd * D + (n_in + 1) * D * F
    flops = m["layers"] * (2.0 * per_layer + 4.0 * H * hd * context)
    if sampled:
        flops += 2.0 * D * m["vocab"]
    return flops
