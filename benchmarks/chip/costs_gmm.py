"""Operations and bytes the routed experts of a PREFILL chunk need, from the
published widths and from what the chunk's routing touched. Beside
`costs_moe.py` and `costs_mla.py`, which size a decode step.

A chunk routes tokens x `num_experts_per_tok` (token, expert) pairs. In one
expert layer the program must read, once, the gate, up and down matrices of
every expert HELD here that got at least one pair (the program counts them
over layers and chunks: `moe_prefill_touched_n`), and must run every pair
that landed on a held expert (`moe_prefill_held_pairs_n`; every pair where
all experts are held) through them: 2 x 3 x hidden x inner flops a pair.
The sorted rows and the result (2 x pairs x hidden values a layer, under 5%
of the expert bytes in both long-document cells) are left out of the need,
so a share of this roofline cannot pass 100 by counting them.
"""

from __future__ import annotations

import costs


def expert_params(model: dict) -> int:
    """One routed expert: gate, up and down (4,718,592 for
    Kanana-2-30B-A3B, 9,437,184 for Laguna-S-2.1)."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def prefill_expert_bytes(model: dict, touched: float,
                         weight_dtype: str) -> float:
    """Bytes the touched experts' matrices hold, each read once."""
    return touched * expert_params(model) * costs._DTYPE_BYTES[weight_dtype]


def prefill_expert_flops(model: dict, held_pairs: float) -> float:
    """A multiply and an add per weight and pair."""
    return 2.0 * held_pairs * expert_params(model)
