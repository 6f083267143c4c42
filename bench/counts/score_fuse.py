"""What one call of the stage-2 selection (``score_fuse``) has to do.

The routing step needs, for each query, BM25 scores of the tools hosted by
its ``top_s`` candidate servers and nothing else: one multiply and one add
per vocabulary term of each such tool row.  It has to read each distinct
candidate tool row once at its stored width, each query's term counts,
the per-tool rows those tools need (host server, QoS score, load), each
query's candidate servers, and write four outputs per query (tool, C, N,
S).  No intermediate of one implementation (stripe flags, padding,
membership matrices) is counted, so a kernel that skips work can never
read above 100%.
"""
from __future__ import annotations

from typing import Sequence


def flops(cand_tools: Sequence[int], vocab: int) -> float:
    """``cand_tools[q]``: tools on query q's candidate servers."""
    return 2.0 * float(sum(cand_tools)) * vocab


def bytes_accessed(n_queries: int, distinct_tools: int, vocab: int,
                   top_s: int, weight_bytes: int = 4,
                   tool_row_operands: int = 3, outputs: int = 4) -> float:
    return float(
        distinct_tools * vocab * weight_bytes
        + n_queries * vocab * 4
        + distinct_tools * tool_row_operands * 4
        + n_queries * top_s * 4
        + n_queries * outputs * 4
    )
