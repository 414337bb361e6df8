"""Which cells `decode_step_roofline` may list, told from what a
configuration FILE shows and never from a cell's name:
`costs.decode_step_bytes` counts K and V of `num_key_value_heads x head_dim`
a token in every layer and one `intermediate_size`-wide MLP a layer, so the
metric is a dense GQA model's. The second half keeps what that count made of
the two cells that were on the list until PR 48 (the Kanana cell read 114% of
a roofline once its seats filled; the OLMoE cell reads a third of its own
share)."""

import json
import os

import costs
import costs_mla
import costs_moe
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(HERE))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# a latent pool, routed experts, layers of more than one kind
NOT_DENSE = ("kv_lora_rank", "n_routed_experts", "num_experts",
             "num_local_experts", "layer_types")


def load(path):
    with open(path) as f:
        return json.load(f)


MANIFEST = load(os.path.join(REPO, "BENCHMARK.json"))


def config_of(cell):
    name = next(w["config"] for w in MANIFEST["workloads"]
                if w["name"] == cell)
    entry = next(c for c in MANIFEST["configs"] if c["name"] == name)
    return load(os.path.join(REPO, entry["file"]))


def listed(metric):
    return next(m for m in MANIFEST["per_layer"]
                if m["name"] == metric)["workloads"]


def dense_share(cfg, live, step_s):
    """What `readers/decode_step_roofline.py` makes of a step of `step_s`
    seconds of device time with `live` tokens in the pool."""
    least, _ = costs.roofline_seconds(
        costs.decode_step_flops(cfg, cfg["max_num_seqs"], live, cfg["tp"]),
        costs.decode_step_bytes(cfg, live, cfg["weight_dtype"],
                                cfg["kv_dtype"], cfg["tp"]), PEAK)
    return 100 * least / step_s


@pytest.mark.parametrize("cell", listed("decode_step_roofline"))
def test_a_listed_cell_has_a_dense_configuration(cell):
    cfg = config_of(cell)
    assert not [k for k in NOT_DENSE if k in cfg], cell
    # and every key the count reads
    for key in ("num_key_value_heads", "head_dim", "intermediate_size",
                "num_attention_heads", "num_hidden_layers", "vocab_size"):
        assert key in cfg, (cell, key)


def test_every_cell_keeps_a_whole_step_share():
    # a cell off the dense list has a whole-step metric of its own that
    # moves the same end-to-end metric (one of the `*decode_step_roofline`s)
    whole = [m for m in MANIFEST["per_layer"]
             if m["name"].endswith("decode_step_roofline")]
    dense = set(listed("decode_step_roofline"))
    for w in MANIFEST["workloads"]:
        mine = [m["name"] for m in whole if w["name"] in m["workloads"]]
        assert len(mine) == 1, (w["name"], mine)
        assert (mine == ["decode_step_roofline"]) == (w["name"] in dense)
    assert {m["moves"] for m in whole} == {"out_tok_per_s"}


def test_the_dense_count_of_the_kanana_cell():
    cfg = config_of("kanana-2-30b-a3b-bf16-8l.longdoc-saturated")
    assert "kv_lora_rank" in cfg and "n_routed_experts" in cfg
    # 32 x 64 K and V a token and layer against ONE latent of 576 values
    assert costs.kv_bytes_per_token(cfg) == 65_536
    assert 8 * costs_mla.latent_bytes_per_token_per_layer(cfg) == 9_216
    # the refused PR 47's change side: ~92,800 live tokens, 29.8% of the
    # experts touched, 7.98 ms of device time a step
    live, share, step_s = 92_800.0, 0.298, 7.98e-3
    dense = costs.decode_step_bytes(
        cfg, live, cfg["weight_dtype"], cfg["kv_dtype"], cfg["tp"])
    latent = costs_mla.decode_step_bytes(
        cfg, live, share, cfg["weight_dtype"], cfg["kv_dtype"])
    assert dense == pytest.approx(7.48e9, rel=1e-3)
    assert latent == pytest.approx(4.53e9, rel=1e-3)
    # 6.08 GB of K and V that never go to memory, where the pool's latents
    # are 0.86 GB; and no expert, where a step reads 2.5 GB of them
    assert costs.decode_attn_bytes(cfg, live) == pytest.approx(
        6.08e9, rel=1e-3)
    assert costs_mla.decode_attn_bytes(cfg, live) == pytest.approx(
        0.855e9, rel=1e-3)
    least_latent, _ = costs.roofline_seconds(
        costs_mla.decode_step_flops(cfg, cfg["max_num_seqs"], live),
        latent, PEAK)
    assert dense_share(cfg, live, step_s) == pytest.approx(114.4, abs=0.1)
    assert 100 * least_latent / step_s == pytest.approx(69.4, abs=0.1)
    # the parent side (~37,300 live tokens, 5.76 ms): the two errors
    # cancelled to a share that looked like one
    assert dense_share(cfg, 37_300.0, 5.76e-3) == pytest.approx(81.4, abs=0.1)


def test_the_dense_count_of_the_olmoe_cell():
    cfg = config_of("olmoe-1b-7b-int8.chat-saturated")
    assert "num_experts" in cfg
    # one expert's MLP a layer: 0.47 GB of weights where a step at 40% of
    # the experts touched reads 0.37 GB + 2.58 GB of them
    assert costs.weight_bytes(cfg, cfg["weight_dtype"]) == pytest.approx(
        0.472e9, rel=1e-3)
    assert costs_moe.touched_expert_bytes(
        cfg, 0.40, cfg["weight_dtype"]) == pytest.approx(2.577e9, rel=1e-3)
    live = 8_200.0
    dense = costs.decode_step_bytes(
        cfg, live, cfg["weight_dtype"], cfg["kv_dtype"], cfg["tp"])
    moe = costs_moe.decode_step_bytes(
        cfg, live, 0.40, cfg["weight_dtype"], cfg["kv_dtype"])
    # 16.2 beside 42.2 in the ledger's PR 46 line: the same step time
    # under both, so the shares stand as the bytes do
    assert dense / moe == pytest.approx(16.2 / 42.2, abs=0.01)
