"""Wall time of the decode calls after each turn's first token, over the
tokens they produced."""
from __future__ import annotations

def read(run):
    if not run["decode_tokens"]:
        return None
    return run["decode_s"] / run["decode_tokens"] * 1e3
