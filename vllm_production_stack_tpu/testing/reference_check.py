"""The served engine against the plain reference (reference_decoder.py):
what `tests/test_reference_decoder.py` runs at tiny size on the CPU and
`chip_smoke.py --child reference` at published widths on the chip.

The engine prefills (chunked) and decodes greedily through the paged cache
with top-N logprobs; the reference computes the full forward of prompt +
the engine's own tokens; the engine's logprobs are compared with the
reference's log-softmax at the same ids and positions — the last prefill
position and every decode position. Logits, not tokens: with random weights
the largest logit changes on rounding.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..engine.request import SamplingParams
from ..models import llama


def served_logprobs(engine, prompts: list[list[int]], max_tokens: int,
                    ) -> list[dict]:
    """Greedy generation through the engine's own step loop, keeping what
    `generate()` drops: per produced token its (chosen logprob, top ids,
    top logprobs). All prompts are in flight together, as served."""
    sampling = SamplingParams(max_tokens=max_tokens, temperature=0.0,
                              ignore_eos=True, logprobs=8)
    ids = [engine.add_request(prompt_token_ids=p, sampling=sampling)
           for p in prompts]
    done = {i: {"token_ids": [], "logprobs": []} for i in ids}
    while engine.has_unfinished():
        with engine.step_lock:
            outs = engine.step()
        for out in outs:
            done[out.request_id]["token_ids"].extend(out.new_token_ids)
            done[out.request_id]["logprobs"].extend(out.new_logprobs or [])
    return [done[i] for i in ids]


def system_routing(cfg, params, tokens: list[int]) -> list[np.ndarray]:
    """Each MoE layer's chosen experts (T, k) as the system's own model code
    computes them (`llama.forward`, the model's dtype, the served weights) in
    one pass over the whole sequence on a scratch pool. The served programs
    export no routing, so this stands for them: the same arithmetic, one
    chunk instead of several."""
    t, block = len(tokens), 32
    nb = -(-t // block)
    kv = llama.init_kv_cache(cfg, nb + 1, block)
    table = jnp.arange(1, nb + 1, dtype=jnp.int32)[None]
    pos = jnp.arange(t, dtype=jnp.int32)
    slots = table[0, pos // block] * block + pos % block

    @jax.jit
    def run(params, kv):
        stats: list = []
        llama.forward(cfg, params, jnp.asarray([tokens], jnp.int32),
                      pos[None], kv, table, slots,
                      jnp.asarray([t], jnp.int32), moe_stats=stats)
        return [s["experts"][0] for s in stats]

    return [np.asarray(e) for e in run(params, kv)]


def compare(reference, cfg, params, prompts: list[list[int]],
            served: list[dict]) -> dict:
    """`reference` is the reference module (the package's, or the
    benchmark's byte-identical copy). Returns the worst |Δlogprob| over
    every compared (position, id), how many were compared, and for an MoE
    model the share of (token, layer) pairs whose expert set differs from
    the reference's, with the reference's margin there. The system's routing
    is compared, never fed into the reference."""
    ref_cfg = reference.published_keys(cfg)
    worst, positions, ids_compared = 0.0, 0, 0
    chosen_worst = 0.0
    routed = differing = 0
    margins: list[float] = []
    for prompt, got in zip(prompts, served):
        seq = list(prompt) + list(got["token_ids"][:-1])
        logits, routing = reference.forward(ref_cfg, params, seq)
        ref_lp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
        for k, (chosen_lp, top_ids, top_lps) in enumerate(got["logprobs"]):
            row = ref_lp[len(prompt) - 1 + k]
            diff = np.abs(row[np.asarray(top_ids)] - np.asarray(top_lps))
            worst = max(worst, float(diff.max()))
            chosen_worst = max(chosen_worst, abs(
                float(row[got["token_ids"][k]]) - chosen_lp))
            positions += 1
            ids_compared += len(top_ids)
        if cfg.num_experts:
            for ours, ref in zip(system_routing(cfg, params, seq), routing):
                same = (np.sort(ours, -1) == np.sort(
                    np.asarray(ref["experts"]), -1)).all(-1)
                routed += same.size
                differing += int((~same).sum())
                margins.extend(np.asarray(ref["margin"])[~same].tolist())
    out = {"max_abs_dlogprob": max(worst, chosen_worst),
           "positions": positions, "ids_compared": ids_compared}
    if cfg.num_experts:
        out.update(
            routing_pairs=routed,
            routing_disagreement_share=differing / max(routed, 1),
            routing_margin_max_where_differing=max(margins, default=0.0))
    return out
