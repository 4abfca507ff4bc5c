"""Operations and bytes the held experts' grouped matmul must do.

Counted from what the algorithm has to read and write, as ``kernels.py``
counts its kernels: the (token, expert) rows routed to the held experts,
not the padded rows a kernel is handed.
"""
from __future__ import annotations


def expert_ffn_cost(rows: float, n_held: int, d_in: int, d_out: int,
                    itemsize: int) -> tuple:
    """One grouped matmul of the held experts' projection (d_in, d_out)
    over ``rows`` routed rows: one multiply-add per row and weight, each
    held expert's weights read once, each row's input read and its
    output written once."""
    ops = 2.0 * rows * d_in * d_out
    nbytes = itemsize * (n_held * d_in * d_out + rows * (d_in + d_out))
    return float(ops), float(nbytes)
