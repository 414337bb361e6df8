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

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..engine.request import SamplingParams
from ..models import llama


def served_logprobs(engine, prompts: list[list[int]], max_tokens: int,
                    ) -> list[dict]:
    """Greedy generation through the engine's own step loop, keeping what
    `generate()` drops: per produced token its (chosen logprob, top ids,
    top logprobs), and the row's block table as the last step it was still
    running left it (`served_pages` reads the pool through it). All prompts
    are in flight together, as served."""
    sampling = SamplingParams(max_tokens=max_tokens, temperature=0.0,
                              ignore_eos=True, logprobs=8)
    ids = [engine.add_request(prompt_token_ids=p, sampling=sampling)
           for p in prompts]
    done = {i: {"token_ids": [], "logprobs": [], "block_table": []}
            for i in ids}
    while engine.has_unfinished():
        with engine.step_lock:
            outs = engine.step()
        for out in outs:
            done[out.request_id]["token_ids"].extend(out.new_token_ids)
            done[out.request_id]["logprobs"].extend(out.new_logprobs or [])
        for req in engine.scheduler.running:
            if req.request_id in done:
                done[req.request_id]["block_table"] = list(req.block_table)
    return [done[i] for i in ids]


class RoutingTap:
    """The experts the SERVED programs chose, token by token: set as
    `llama.ROUTING_TAP` while an engine is built and driven (the tap has to
    be there when a program is traced), it receives every execution's
    (token ids, positions, experts (Lm, tokens, k)) and gives them back per
    sequence, keyed by (position, token id) — which names a fed token of
    random prompts uniquely; padding rows and positions match nothing."""

    def __init__(self):
        self.records: list[tuple] = []
        self._by_key: dict | None = None
        # (position, token id) keys under which two executions recorded
        # DIFFERENT experts: two sequences in flight with the same token at
        # the same position (a decoded token that equals another prompt's,
        # say). The key cannot say whose choice is whose: `routing_of(...,
        # mark_ambiguous=True)` hands such a token back as a row of -1
        self.ambiguous: set = set()

    def __call__(self, token_ids, positions, experts) -> None:
        self.records.append((np.asarray(token_ids), np.asarray(positions),
                             np.asarray(experts)))

    def __enter__(self) -> "RoutingTap":
        llama.ROUTING_TAP = self
        return self

    def __exit__(self, *exc) -> None:
        llama.ROUTING_TAP = None
        jax.effects_barrier()  # every callback has run

    def routing_of(self, tokens: list[int], mark_ambiguous: bool = False,
                   ) -> list[np.ndarray]:
        """Per expert layer the (T, k) experts chosen for `tokens` fed at
        positions 0..T-1 (whichever program fed each). `mark_ambiguous`: a
        token whose key two sequences share comes back as a row of -1 (a
        reference that takes such rows follows its own choice there)."""
        if self._by_key is None:
            self._by_key = {}
            for toks, pos, experts in self.records:
                for i, key in enumerate(zip(pos.tolist(), toks.tolist())):
                    seen = self._by_key.get(key)
                    if seen is not None and not np.array_equal(
                            seen, experts[:, i]):
                        self.ambiguous.add(key)
                    self._by_key[key] = experts[:, i]
        rows = [self._by_key[(p, t)] for p, t in enumerate(tokens)]
        if mark_ambiguous:
            rows = [np.full_like(r, -1) if (p, t) in self.ambiguous else r
                    for (p, t), r in zip(enumerate(tokens), rows)]
        return list(np.stack(rows, axis=1))  # Lm x (T, k)


class SelectionTap:
    """The key positions the SERVED programs selected, token by token, in a
    model whose latent layers attend over selected keys (LayerSpec.indexer;
    docs/55-selected-keys.md): set as `llama.SELECTION_TAP` while an engine
    is built and driven (as `RoutingTap` is), it receives every execution's
    (index of the layer among those that own an indexer, token ids,
    positions, selected positions (tokens, K) ascending, -1 padded) and
    gives them back per sequence, keyed by (position, token id)."""

    def __init__(self):
        self.records: list[tuple] = []
        self._by_key: dict | None = None
        # (layer, position, token id) keys under which two executions
        # recorded DIFFERENT selections: two sequences in flight with the
        # same token at the same position (`RoutingTap.ambiguous`). Such a
        # token comes back as a row of -2: the reference selects for it
        self.ambiguous: set = set()

    def __call__(self, layer, token_ids, positions, chosen) -> None:
        self.records.append((
            int(layer), np.asarray(token_ids), np.asarray(positions),
            np.asarray(chosen).astype(np.int32)))

    def __enter__(self) -> "SelectionTap":
        llama.SELECTION_TAP = self
        return self

    def __exit__(self, *exc) -> None:
        llama.SELECTION_TAP = None
        jax.effects_barrier()  # every callback has run

    def selection_of(self, tokens: list[int]) -> list[np.ndarray]:
        """Per layer that owns an indexer the (T, K) positions selected for
        `tokens` fed at positions 0..T-1 (whichever program fed each; a
        token fed twice with the same selection — a preemption — is one),
        -1 past a query's count; a row of -2 where the key names two
        selections."""
        if self._by_key is None:
            self._by_key = {}
            for layer, toks, pos, chosen in self.records:
                for i, key in enumerate(zip(pos.tolist(), toks.tolist())):
                    key = (layer, *key)
                    seen = self._by_key.get(key)
                    if seen is not None and not np.array_equal(
                            seen, chosen[i]):
                        self.ambiguous.add(key)
                    self._by_key[key] = chosen[i]
            self.records = []
        layers = 1 + max(k[0] for k in self._by_key)
        out = []
        for layer in range(layers):
            rows = [self._by_key[(layer, p, t)] for p, t in enumerate(tokens)]
            width = max(len(r) for r in rows)
            out.append(np.stack([
                np.full(width, -2, np.int32)
                if (layer, p, t) in self.ambiguous else
                np.pad(r, (0, width - len(r)), constant_values=-1)
                for (p, t), r in zip(enumerate(tokens), rows)]))
        return out


def system_routing(cfg, params, tokens: list[int], chunk: int | None = None,
                   backend: str = "xla") -> list[np.ndarray]:
    """Each MoE layer's chosen experts (T, k) as the system's own model code
    computes them (`llama.forward`, the model's dtype, the served weights)
    on a scratch pool: in one pass over the whole sequence, or — `chunk`,
    what a long sequence needs: one pass holds (heads, T, T) scores — in
    chunks of that many tokens through the pool, as served. The served
    programs export no routing, so this stands for them: the same
    arithmetic."""
    t, block = len(tokens), 32
    chunk = chunk or t
    # (the table's width a power of two: sequences of many lengths share
    # a few programs)
    nb = 1 << (-(-t // block) - 1).bit_length()
    kv = llama.init_kv_cache(cfg, nb + 1, block)
    table = jnp.arange(1, nb + 1, dtype=jnp.int32)[None]

    @functools.partial(jax.jit, donate_argnums=1)
    def run(params, kv, toks, pos, ctx):
        stats: list = []
        slots = table[0, pos // block] * block + pos % block
        _, kv = llama.forward(cfg, params, toks[None], pos[None], kv, table,
                              slots, ctx[None], backend=backend,
                              moe_stats=stats)
        return kv, [s["experts"][0] for s in stats]

    parts = []
    for lo in range(0, t, chunk):
        # the last chunk is padded to the others' length (one program):
        # its padding positions lie past the context and are cut below
        pos = jnp.minimum(jnp.arange(lo, lo + chunk, dtype=jnp.int32), t - 1)
        toks = jnp.asarray(tokens, jnp.int32)[pos]
        kv, experts = run(params, kv, toks, pos,
                          jnp.int32(min(lo + chunk, t)))
        parts.append([np.asarray(e)[: t - lo] for e in experts])
    return [np.concatenate(layer) for layer in zip(*parts)]


class _Matrices:
    """A leaf the served tree stores (L, heads, head_dim, in) as the stack
    of (in, heads * head_dim) matrices a reference reads — a layer at a
    time, `view[i]`: no second copy of the stack is held."""

    def __init__(self, leaf):
        self.leaf = leaf

    def __getitem__(self, i):
        w = self.leaf[i]
        return w.reshape(-1, w.shape[-1]).T


class _KvB:
    """W_kv_b, which the served tree holds as two leaves (llama._split_kv_b),
    as the stack of (rank, heads * (nope + v)) matrices a reference reads."""

    def __init__(self, wk_b, wv_b):
        self.halves = (wk_b, wv_b)

    def __getitem__(self, i):
        w = jnp.concatenate([half[i] for half in self.halves], axis=1)
        return w.reshape(-1, w.shape[-1]).T


def reference_tree(params: dict) -> dict:
    """The served tree as the references that read it directly take it
    (reference_decoder.py, reference_sdar.py, reference_falcon_h1.py,
    reference_olmo_hybrid.py,
    reference_glm_dsa.py): every matrix (in, out). The leaves of
    `llama.HEADS_MAJOR` are viewed layer by layer (`_Matrices`; a latent
    stack's two halves of W_kv_b as one "wkv_b", `_KvB`), an int8 leaf's
    scale as the (L, 1, out) it is for every other matrix; the rest of the
    tree is the served arrays themselves."""
    layers = dict(params["layers"])
    for stack, names in llama.HEADS_MAJOR.items():
        if stack not in layers:
            continue
        attn = layers[stack] = dict(layers[stack])
        if "wk_b" in attn:
            attn["wkv_b"] = _KvB(attn.pop("wk_b"), attn.pop("wv_b"))
        for name in names:
            leaf = attn.get(name)
            if isinstance(leaf, dict):
                s = leaf["s"]
                attn[name] = {"q": _Matrices(leaf["q"]),
                              "s": s.reshape(s.shape[0], 1, -1)}
            elif leaf is not None:
                attn[name] = _Matrices(leaf)
    return {**params, "layers": layers}


# the architectures whose tree is stacked by layer kind: the view
# (models/loader.py) that hands the plain reference the served weights
# under its own tensor names
_BY_KIND_VIEWS = {"laguna": "LagunaCheckpointView",
                  "solar_open2": "SolarOpen2CheckpointView",
                  "ling_flash": "LingCheckpointView"}


def compare(reference, cfg, params, prompts: list[list[int]],
            served: list[dict], routing_chunk: int | None = None,
            routing_backend: str = "xla",
            served_routing: RoutingTap | None = None,
            reference_arms: dict | None = None,
            served_selection: SelectionTap | None = None) -> dict:
    """`reference` is the reference module (the package's, or the
    benchmark's byte-identical copy). Returns the worst |Δlogprob| over
    every compared (position, id), how many were compared, and for an MoE
    model the share of (token, layer) pairs whose expert set differs from
    the reference's, with the reference's margin there.

    Without `served_routing` the system's routing (`system_routing`) is
    compared and never fed into the reference: right where a flipped
    boundary expert carries little weight (OLMoE's raw softmax mass), or in
    float32. With it (`reference_mla` only) the reference FOLLOWS the
    experts the served programs chose and reports how far each choice lay
    from its own: for renormalised top-6-of-128 weights in bf16 a
    free-running comparison measures the flips, amplified, and nothing
    else (3.25 nats at 8 layers on the chip, PERF.md §6 PR 31).
    `reference_arms`: keywords the reference's `forward` takes to compute
    something ELSE (reference_falcon_h1.py's fault arms: the check shows
    each lands outside a limit). `served_selection` (`reference_glm_dsa`
    only): the reference also FOLLOWS the key positions the served programs
    selected and reports how far they lay from its own top-k
    (`selection_*`; reference_glm_dsa.py says why); without it the
    reference selects for itself."""
    ref_cfg = reference.published_keys(cfg)
    worst, positions, ids_compared, total = 0.0, 0, 0, 0.0
    chosen_worst = 0.0
    routed = differing = 0
    margins: list[float] = []
    # reference_mla.py and reference_laguna.py read checkpoint-style names
    # and orientation, and take `logit_positions` and `forced_experts`
    selects = bool(cfg.selecting_layers)  # (reads the served tree itself)
    by_name = (cfg.mla and not selects) or (
        cfg.architecture in _BY_KIND_VIEWS)
    served_params = params
    if by_name:
        from ..models import loader

        view = (loader.DeepseekCheckpointView if cfg.mla
                else getattr(loader, _BY_KIND_VIEWS[cfg.architecture]))
        params_ref = view(cfg, params)
    else:
        params = reference_tree(params)
    selection_reports: list = []
    if (cfg.linear_layers or selects) and cfg.num_experts and (
            served_routing is None):
        # (`system_routing` drives `llama.forward` row-major on a scratch
        # pool, which has no state slots)
        raise ValueError(
            f"{cfg.model}: the routing of a model with layers that hold "
            "state, or that select their keys, comes from the served "
            "programs' tap (`served_routing`)")
    for prompt, got in zip(prompts, served):
        seq = list(prompt) + list(got["token_ids"][:-1])
        first = len(prompt) - 1  # the first compared position
        if by_name:
            # ... and gives the logits of the asked positions only (all of
            # a long sequence's are GBs of float32)
            forced = (
                served_routing.routing_of(
                    seq, mark_ambiguous=cfg.architecture in _BY_KIND_VIEWS)
                if served_routing is not None else None)
            # (laguna: 72 heads x 16k keys of float32 scores a query —
            # 64 queries a block keep a block's scores under 0.4 GB beside
            # the served weights, which stay on the chip)
            block = ({"query_block": 64}
                     if cfg.architecture in _BY_KIND_VIEWS else {})
            logits, routing = reference.forward(
                ref_cfg, params_ref, seq, range(first, len(seq)),
                forced_experts=forced, **block)
            first = 0
        elif selects:
            # reference_glm_dsa.py reads the served tree's own leaves and
            # takes `logit_positions`, the served routing and selection,
            # and its fault arms
            forced = served_routing.routing_of(seq, mark_ambiguous=True)
            logits, routing, reports = reference.forward(
                ref_cfg, params, seq, range(first, len(seq)),
                forced_experts=forced, forced_selection=(
                    served_selection.selection_of(seq)
                    if served_selection is not None else None),
                **(reference_arms or {}))
            selection_reports.extend(reports)
            first = 0
        elif cfg.ssm_layers or cfg.architecture == "olmo_hybrid":
            # reference_falcon_h1.py and reference_olmo_hybrid.py read the
            # served tree's own leaves and take `logit_positions` and their
            # fault arms
            logits, routing = reference.forward(
                ref_cfg, params, seq, range(first, len(seq)),
                **(reference_arms or {}))
            first = 0
        else:
            logits, routing = reference.forward(ref_cfg, params, seq)
        ref_lp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
        for k, (chosen_lp, top_ids, top_lps) in enumerate(got["logprobs"]):
            row = ref_lp[first + k]
            diff = np.abs(row[np.asarray(top_ids)] - np.asarray(top_lps))
            worst = max(worst, float(diff.max()))
            total += float(diff.sum())
            chosen_worst = max(chosen_worst, abs(
                float(row[got["token_ids"][k]]) - chosen_lp))
            positions += 1
            ids_compared += len(top_ids)
        if cfg.num_experts:
            ours_all = forced if (by_name or selects) and (
                forced is not None) else (
                system_routing(cfg, served_params, seq, routing_chunk,
                               routing_backend))
            # (a leading dense layer routes nothing: the reference says None)
            for ours, ref in zip(ours_all, [r for r in routing if r]):
                same = (np.sort(ours, -1) == np.sort(
                    np.asarray(ref["experts"]), -1)).all(-1)
                same |= (np.asarray(ours) < 0).all(-1)  # ambiguous: unforced
                routed += same.size
                differing += int((~same).sum())
                margins.extend(np.asarray(ref["margin"])[~same].tolist())
    # (the mean beside the max: rounding scatters, with a few large
    # outliers among small logprobs; a fault in the mathematics shifts
    # every position)
    out = {"max_abs_dlogprob": max(worst, chosen_worst),
           "mean_abs_dlogprob": total / max(ids_compared, 1),
           "positions": positions, "ids_compared": ids_compared}
    if served_routing is not None:
        out["routing_keys_ambiguous"] = len(served_routing.ambiguous)
    if cfg.num_experts:
        out.update(
            routing_pairs=routed,
            routing_disagreement_share=differing / max(routed, 1),
            routing_margin_max_where_differing=max(margins, default=0.0))
    if served_selection is not None:
        out.update(reference.selection_summary(selection_reports))
        out["selection_keys_ambiguous"] = len(served_selection.ambiguous)
    return out


def served_index_pages(reference, cfg, params, pool,
                       prompts: list[list[int]], served: list[dict],
                       key_dtype: str | None = None) -> dict:
    """The INDEX KEYS the engine holds beside its latents, where neither
    the logprobs nor the selection the reference follows can see them: a
    key that is stored wrong moves the selection of every LATER query, which
    the reference then follows. `pool` is the first layer's second cache
    leaf as the engine left it after `served_logprobs` (1, blocks, block
    size, index dim): each row's pages, found through the block table it had
    when it last ran (`served_pages` says which rows those are), against
    `reference.first_index_keys` of the tokens it was fed (`key_dtype`: the
    reference's arm that holds its keys as a cache of that dtype would).
    Returns the largest relative error (root of the summed squares) over the
    rows."""
    keys = reference.published_keys(cfg)
    pool = np.asarray(pool)
    block = pool.shape[2]
    worst, tokens = 0.0, 0
    for prompt, row in zip(prompts, served):
        fed = (list(prompt) + list(row["token_ids"]))[:-1]
        fed = fed[:len(row["block_table"]) * block]
        got = jnp.asarray(pool[0, row["block_table"]].reshape(
            -1, pool.shape[3])[:len(fed)]).astype(jnp.float32)
        want = reference.first_index_keys(
            keys, reference_tree(params), fed, key_dtype)
        worst = max(worst, float(
            jnp.linalg.norm(got - want) / jnp.linalg.norm(want)))
        tokens += len(fed)
    return {"layer": 0, "rows": len(served), "tokens": tokens,
            "index_pages_rel_err": worst}


class StateTap:
    """What the SERVED programs fed the recurrence of one layer that holds
    state, token by token: set as `llama.STATE_TAP` while an engine is built
    and driven (as `RoutingTap` is), it receives every execution's (pool
    index each token's row wrote its state at, positions, q, k, v, g, beta)
    and gives them back per state slot, in the order of the positions (a
    state-space layer's scan is fed x, B, C, dt)."""

    def __init__(self, layer: int):
        self.layer = layer
        self.records: list[tuple] = []
        self._rows: dict | None = None

    def __call__(self, *fed) -> None:
        self.records.append(tuple(np.asarray(x) for x in fed))

    def __enter__(self) -> "StateTap":
        llama.STATE_TAP = self
        return self

    def __exit__(self, *exc) -> None:
        llama.STATE_TAP = None
        jax.effects_barrier()  # every callback has run

    def rows(self, state_slots: int) -> dict[int, tuple]:
        """{slot: (the pool index its last token wrote, q, k, v, g, beta over
        the row's tokens)} of every slot a row held (`state_slots` a bank;
        slot 0, the padding rows', left out). A slot has to have been fed
        positions 0..n-1 once each: one row, never preempted nor rolled
        back — the check drives the engine so."""
        if self._rows is not None:
            return self._rows
        slots = np.concatenate([r[0] for r in self.records])
        positions = np.concatenate([r[1] for r in self.records])
        fed = [np.concatenate([r[2 + i] for r in self.records])
               for i in range(len(self.records[0]) - 2)]
        out = {}
        for slot in np.unique(slots % state_slots):
            if slot == 0:
                continue
            at = np.flatnonzero(slots % state_slots == slot)
            at = at[np.argsort(positions[at], kind="stable")]
            if not np.array_equal(positions[at], np.arange(len(at))):
                raise ValueError(
                    f"state slot {slot} was fed positions other than 0.."
                    f"{len(at) - 1} once each (a second row, a preemption "
                    "or a rollback): the check of the served state covers "
                    "one uninterrupted row a slot")
            out[int(slot)] = (int(slots[at[-1]]), *(x[at] for x in fed))
        self._rows, self.records = out, []
        return out


def served_state(reference, cfg, tap: StateTap, pool,
                 token_block: int = 2048, params: dict | None = None,
                 state_dtype: str | None = None) -> dict:
    """The recurrent STATE the engine holds in its slots, where a comparison
    of logprobs cannot see it: bf16 weights and activations through 8 layers
    scatter logprobs by as much as a state rounded to bfloat16 at every
    token does (PERF.md §6, PR 39), so the state has a limit of its own.
    `pool` is layer `tap.layer`'s state pool as the engine left it after
    serving (`runner.kv_caches[layer]["s"]`, two banks of slots); each row's
    slot is compared with what the REFERENCE's token-by-token recurrence
    (`reference.delta_rule`, from a zero state, in the state dtype the
    reference's configuration states) makes of the very q, k, v, g, beta
    the served programs fed that layer — every prefill chunk, seam, decode
    window and bank flip of the row lies between the two. Returns the
    largest |difference| over the reference's largest magnitude, over the
    rows. A state-space layer's recurrence (`reference.ssd_recurrence`)
    also reads the layer's A_log and D, from `params`; `state_dtype`
    replaces the configuration's (the arm that has to fail)."""
    keys = reference.published_keys(cfg)
    if cfg.layer_specs[tap.layer].recurrence == "ssd":
        sp = jax.tree.map(lambda a: a[cfg.ssm_layers.index(tap.layer)],
                          params["layers"]["attn_ssm"])
        recurrence = functools.partial(
            reference.ssd_recurrence, state_dtype=jnp.dtype(
                state_dtype or keys["ssm_state_dtype"]))

        def step(x, b, c, dt, s):
            return recurrence(x, b, c, dt, s, sp["A_log"], sp["D"])

        step = jax.jit(step)
    else:
        step = jax.jit(functools.partial(
            reference.delta_rule, state_dtype=jnp.dtype(
                state_dtype or keys.get("kda_state_dtype", "float32"))))
    worst, tokens = 0.0, 0
    rows = tap.rows(pool.shape[0] // 2)
    for wrote, *fed in rows.values():
        s = jnp.zeros(pool.shape[1:], jnp.float32)
        for lo in range(0, len(fed[0]), token_block):
            _, s = step(*(jnp.asarray(x[lo:lo + token_block]) for x in fed), s)
        err = float(jnp.max(jnp.abs(s - jnp.asarray(pool[wrote]))))
        worst = max(worst, err / float(jnp.max(jnp.abs(s))))
        tokens += len(fed[0])
    return {"layer": tap.layer, "rows": len(rows), "tokens": tokens,
            "state_rel_err": worst}


def served_pages(reference, cfg, params, pool, prompts: list[list[int]],
                 served: list[dict]) -> dict:
    """The K/V PAGES the engine holds, where a comparison of logprobs cannot
    see them: over thousands of keys the rounding of a float8 pool averages
    away (Falcon-H1 at published widths: the largest |Δlogprob| 0.12–0.14
    over float8 pages beside 0.085–0.093 over bfloat16 ones, the mean
    unmoved; PERF.md §6, PR 50), so the pages have a limit of their own.
    `pool` is the FIRST layer's page array as the engine left it after
    `served_logprobs` (2, blocks, block size, kv heads, head dim): each
    row's pages, found through the block table it had when it last ran,
    against `reference.first_layer_kv` of the tokens it was fed. The rows
    were in flight together and nothing was admitted behind them, so a page
    given back still holds what its row wrote. Returns the largest relative
    error (root of the summed squares, K and V each) over the rows."""
    keys = reference.published_keys(cfg)
    params = reference_tree(params)
    pool = np.asarray(pool)
    block = pool.shape[2]
    worst, tokens = 0.0, 0
    for prompt, row in zip(prompts, served):
        # (the last produced token was never fed; a row's last step may have
        # been given pages the table kept here does not list yet)
        fed = (list(prompt) + list(row["token_ids"]))[:-1]
        fed = fed[:len(row["block_table"]) * block]
        held = pool[:, row["block_table"]].reshape(
            2, -1, *pool.shape[3:])[:, :len(fed)]
        for want, got in zip(
                reference.first_layer_kv(keys, params, fed), held):
            # (a page may hold more kv heads than the model has, zeros:
            # ModelConfig.page_kv_heads)
            got = jnp.asarray(got[:, :want.shape[1]]).astype(jnp.float32)
            worst = max(worst, float(
                jnp.linalg.norm(got - want) / jnp.linalg.norm(want)))
        tokens += len(fed)
    return {"layer": 0, "rows": len(served), "tokens": tokens,
            "pages_rel_err": worst}


class RouterTap:
    """What the SERVED programs fed the router of one expert layer, and what
    it chose: set as `llama.ROUTER_TAP` while an engine is built and driven
    (as `RoutingTap` is), it receives every execution's (router input (N, h),
    chosen experts (N, k)) of expert layer `moe_layer` (its index among the
    expert layers), padding rows and positions included — they are routed by
    the same arithmetic."""

    def __init__(self, moe_layer: int):
        self.moe_layer = moe_layer
        self.records: list[tuple] = []

    def __call__(self, x, experts) -> None:
        self.records.append((np.asarray(x), np.asarray(experts)))

    def __enter__(self) -> "RouterTap":
        llama.ROUTER_TAP = self
        return self

    def __exit__(self, *exc) -> None:
        llama.ROUTER_TAP = None
        jax.effects_barrier()  # every callback has run


def served_router(reference, cfg, tap: RouterTap, params_ref,
                  max_tokens: int = 65536) -> dict:
    """The ROUTER the engine serves, where a comparison of logprobs cannot
    see it and the routing the reference follows cannot either: the served
    programs' bf16 activations move a fifth of the (token, layer) choices
    off the float32 reference's own (PERF.md §6, PR 45), so a router
    computed in a lower precision hides among them. Here the reference's
    `route` is given the VERY inputs the served router was given (tapped,
    widened to float32) and its choice is held against the served one: what
    is left between the two is the router's own arithmetic — its product's
    precision, its scores, the group limit, the ties. Returns the share of
    tokens whose expert SETS differ and, where they do, how far the worst
    served expert's choice score lies under the reference's k-th best."""
    ref_cfg = reference.published_keys(cfg)
    layer = [i for i, s in enumerate(cfg.layer_specs) if s.experts][
        tap.moe_layer]
    prefix = f"model.layers.{layer}."

    def get(name):
        return reference.f32(params_ref[name])

    tokens = differing = 0
    margin = 0.0
    for x, served in tap.records:
        if tokens >= max_tokens:
            break
        with jax.default_matmul_precision("highest"):
            _, own, gap = reference.route(
                reference.check_config(ref_cfg), get, prefix,
                reference.f32(x), served)
        same = (np.sort(served, -1) == np.sort(np.asarray(own), -1)).all(-1)
        tokens += same.size
        differing += int((~same).sum())
        margin = max(margin, float(np.max(
            np.where(same, 0.0, np.asarray(gap)), initial=0.0)))
    return {"moe_layer": tap.moe_layer, "tokens": tokens,
            "choice_disagreement_share": differing / max(tokens, 1),
            "choice_margin_max_where_differing": margin}


# -- generation by diffusion over blocks (reference_sdar.py) -----------------

def served_block_logprobs(engine, prompts: list[list[int]], max_tokens: int,
                          ) -> list[dict]:
    """`served_logprobs`, keeping each request's id beside its tokens and
    logprobs (what `BlockTap` keys a window's rows by)."""
    sampling = SamplingParams(max_tokens=max_tokens, temperature=0.0,
                              ignore_eos=True, logprobs=8)
    ids = [engine.add_request(prompt_token_ids=p, sampling=sampling)
           for p in prompts]
    done = {i: {"request_id": i, "token_ids": [], "logprobs": []}
            for i in ids}
    while engine.has_unfinished():
        with engine.step_lock:
            outs = engine.step()
        for out in outs:
            done[out.request_id]["token_ids"].extend(out.new_token_ids)
            done[out.request_id]["logprobs"].extend(out.new_logprobs or [])
    return [done[i] for i in ids]


class BlockTap:
    """Every pass of the served windows of block passes, in order: set as
    `llama.BD_TAP` while an engine is built and driven, it hears from the
    runner which requests a window's rows are (`window`, at dispatch) and
    from the program each pass's block states and routing (`__call__`; a
    window's first pass is block 0, pass 0). `blocks_of(request id)` gives a
    row's blocks back: {first position: [pass, ...]}, a pass = {"before",
    "picked", "after" (T,), "experts" (Lm, T, k)}; the last pass of a block
    is its commit pass. A window that was rolled back and dispatched again
    is heard twice; the later hearing replaces the earlier."""

    def __init__(self):
        self.windows: list[list[str]] = []
        self.passes: list[tuple] = []

    def window(self, request_ids: list[str]) -> None:
        self.windows.append(list(request_ids))

    def __call__(self, blk, pas, positions, before, picked, after,
                 experts) -> None:
        self.passes.append(tuple(np.asarray(a) for a in (
            blk, pas, positions, before, picked, after, experts)))

    def __enter__(self) -> "BlockTap":
        llama.BD_TAP = self
        return self

    def __exit__(self, *exc) -> None:
        llama.BD_TAP = None
        jax.effects_barrier()  # every callback has run

    def blocks_of(self, request_id: str) -> dict[int, list[dict]]:
        blocks: dict[int, list[dict]] = {}
        w = -1
        for blk, pas, pos, before, picked, after, experts in self.passes:
            if blk == 0 and pas == 0:
                w += 1
            rows = self.windows[w]
            if request_id not in rows:
                continue
            i, t = rows.index(request_id), before.shape[1]
            first = int(pos[i, 0])
            if pas == 0:
                blocks[first] = []  # (a replayed block starts over)
            blocks[first].append({
                "before": before[i], "picked": picked[i], "after": after[i],
                "experts": experts[:, i * t:(i + 1) * t]})
        return blocks


    def touched_split(self, mask_id: int, num_experts: int) -> dict:
        """Why the passes touched the share of a layer's experts they did,
        for the tokens of a pass's LIVE rows together (`all`; padding rows
        feed one token at one position and choose as one), its mask tokens
        and its other tokens apart: `tokens` a pass and layer (mean),
        `touched` the share of the experts they chose, `independent` what
        as many tokens touch choosing independently with the layer's own
        expert frequencies over every tapped pass (the routers' skew alone)
        and `balanced` with equal frequencies. Tokens that route ALIKE read
        under `independent`; skewed routers read `independent` under
        `balanced`."""
        chosen, w = [], -1
        for blk, pas, _pos, before, _picked, _after, experts in self.passes:
            if blk == 0 and pas == 0:
                w += 1
            live = len(self.windows[w]) * before.shape[1]
            chosen.append((experts[:, :live],
                           before.reshape(-1)[:live] == mask_id))
        k = chosen[0][0].shape[-1]
        counts = np.zeros((chosen[0][0].shape[0], num_experts))
        for experts, _ in chosen:
            for layer, ids in enumerate(experts):
                counts[layer] += np.bincount(
                    ids.reshape(-1), minlength=num_experts)
        freq = counts / (counts.sum(axis=1, keepdims=True) / k)  # (Lm, E)
        split = {}
        for name, pick in (("all", lambda m: np.ones_like(m)),
                           ("masks", lambda m: m), ("others", lambda m: ~m)):
            n, touched, independent, balanced = [], [], [], []
            for experts, is_mask in chosen:
                rows = pick(is_mask)
                if not rows.any():
                    continue
                n.append(int(rows.sum()))
                touched.append(np.mean([
                    len(np.unique(ids[rows])) for ids in experts])
                    / num_experts)
                independent.append(np.mean(1 - (1 - freq) ** n[-1]))
                balanced.append(1 - (1 - k / num_experts) ** n[-1])
            split[name] = {
                "tokens": float(np.mean(n)), "touched": float(np.mean(touched)),
                "independent": float(np.mean(independent)),
                "balanced": float(np.mean(balanced))}
        return split


def compare_blocks(reference, cfg, params, prompts: list[list[int]],
                   served: list[dict], tap: BlockTap,
                   routing: RoutingTap | None = None, *,
                   blocks: str = "all", commit_pass: bool = True,
                   shift: bool = False, ref_cfg: dict | None = None,
                   query_block: int = 256, pad_to: int = 32) -> dict:
    """The served windows of block passes against `reference.forward`
    (reference_sdar.py), teacher-forced: for every pass that unmasked a
    token, the reference's forward over the row's finished blocks + the
    block AS THAT PASS SAW IT (from `tap`), and the engine's logprobs of
    the tokens the pass unmasked against the reference's log-softmax at
    their positions. Logits, not tokens, and the engine's own order of
    unmasking: with random weights both the argmax and the confidence order
    change on rounding (`order_agreement_share` says how often the
    reference would have unmasked the same positions). With `routing` (the
    prompt's, from `RoutingTap`) the reference follows the served programs'
    experts: the passes' from `tap`, a finished block's from its commit
    pass. `blocks`: "all", or "ends" (a row's first and last block).

    A sequence is padded with masks to a multiple of `pad_to` (no query at or
    before the open block sees what lies behind it): the eager reference
    compiles each operation once a length.

    Two ways to hold a WRONG procedure against the same served numbers:
    `commit_pass` False — finished blocks stand in the reference's context
    as their last denoising pass saw them (what a cache holds that keeps
    that pass's K/V) — and `shift` — the token at position i is read off
    the logits at position i - 1."""
    ref_cfg = ref_cfg or reference.published_keys(cfg)
    params = reference_tree(params)
    t = ref_cfg["block_length"]
    worst = total = 0.0
    positions = ids_compared = passes = same_order = 0
    for prompt, got in zip(prompts, served):
        final = list(prompt) + list(got["token_ids"])
        p = len(prompt)
        row_blocks = tap.blocks_of(got["request_id"])
        starts = sorted(row_blocks)
        # what the context holds of a finished block, and its routing
        held = {a: (row_blocks[a][-1]["after"] if commit_pass else next(
            x["before"] for x in reversed(row_blocks[a])
            if x["picked"].any())) for a in starts}
        pre = p // t * t
        # (a (position, token) key that two rows share names neither's
        # choice: the reference follows its own there)
        pre_routing = (routing.routing_of(final[:pre], mark_ambiguous=True)
                       if routing is not None and pre else None)
        # (the blocks that hold a delivered token: what lies past
        # max_tokens was computed and cut)
        starts = [a for a in starts if a < len(final)]
        check = starts if blocks == "all" else sorted({starts[0], starts[-1]})
        for a in check:
            context = list(final[:pre])
            for s in starts:
                if s < a:
                    context += [int(x) for x in held[s]]
            for pas, one in enumerate(row_blocks[a]):
                if not one["picked"].any():
                    continue
                seq = context + [int(x) for x in one["before"]]
                pad = -len(seq) % pad_to
                seq += [ref_cfg["mask_token_id"]] * pad
                forced = None
                if routing is not None:
                    own = np.full((pad, one["experts"].shape[-1]), -1)
                    forced = [
                        np.concatenate(
                            ([pre_routing[layer]] if pre_routing else [])
                            + [row_blocks[s][-1]["experts"][layer]
                               for s in starts if s < a]
                            + [one["experts"][layer], own])
                        for layer in range(one["experts"].shape[0])]
                at = range(a - shift, a - shift + t)
                if at[0] < 0:
                    continue  # (position 0 has nothing before it to shift to)
                logits, _ = reference.forward(
                    ref_cfg, params, seq, at, forced_experts=forced,
                    query_block=query_block)
                if not ref_cfg["float32_statistics"]:
                    logits = logits.astype(jnp.bfloat16)
                ref_lp = np.asarray(
                    jax.nn.log_softmax(logits, axis=-1).astype(jnp.float32))
                passes += 1
                # would the reference have unmasked the same positions?
                masked = [int(x) == ref_cfg["mask_token_id"] and i + a >= p
                          for i, x in enumerate(one["before"])]
                conf = np.exp(ref_lp.max(-1))
                same_order += set(reference.transfer(
                    ref_cfg, conf, masked, pas)) == set(
                        np.flatnonzero(one["picked"]).tolist())
                for i in np.flatnonzero(one["picked"]):
                    k = a + int(i) - p
                    if k >= len(got["logprobs"]):
                        continue  # (past max_tokens: cut, not delivered)
                    chosen_lp, top_ids, top_lps = got["logprobs"][k]
                    diff = np.abs(
                        ref_lp[i][np.asarray(top_ids)] - np.asarray(top_lps))
                    worst = max(worst, float(diff.max()), abs(
                        float(ref_lp[i][got["token_ids"][k]]) - chosen_lp))
                    total += float(diff.sum())
                    positions += 1
                    ids_compared += len(top_ids)
    return {"max_abs_dlogprob": worst,
            "mean_abs_dlogprob": total / max(ids_compared, 1),
            "positions": positions, "ids_compared": ids_compared,
            "passes_compared": passes,
            "order_agreement_share": same_order / max(passes, 1)}
