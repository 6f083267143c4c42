"""What one call of the candidate selection (``select_fuse``) has to do.

After the sharded engine's stage 2, each query holds ``width`` candidate
tools (the tools of its ``top_s`` candidate servers).  The step needs,
per query: the top ``k`` of those by BM25 score (one comparison per
candidate and kept slot), the Eq. 5 softmax over the ``k`` (an exp, an
add and a divide each), the fusion of each of the ``k`` with its QoS
score, load penalty and failed flag (three multiplies and two adds) and
the argmax.  It reads each candidate's score and its per-tool operands
once, at their stored width (4-byte floats), and writes four outputs per
query (tool, C, N, S).  Padding to lanes or query tiles is not counted,
so a kernel that skips work can never read above 100%.
"""
from __future__ import annotations


def flops(n_queries: int, width: int, k: int) -> float:
    return float(n_queries) * (width * k + 3 * k + 5 * k + k)


def bytes_accessed(n_queries: int, width: int, tool_operands: int = 4,
                   outputs: int = 4) -> float:
    """``tool_operands``: the score and the per-tool QoS, load and failed
    rows (the softmax values are the scores themselves)."""
    return float(n_queries * width * tool_operands * 4
                 + n_queries * outputs * 4)
