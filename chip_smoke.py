#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served stack starts on the chip.

Drives the normal served path once, through the entry points the README's
quick start uses, at the full width and depth of one model the repo supports
(registry preset qwen3-8b, int8 weights, random from --seed):

  kernels   each Pallas kernel that compiles for v5e, run NON-interpreted
            against ops/attention.py's XLA path: decode at the qwen3-8b head
            shape, prefill at the chat and long-document cells' head shapes,
            the latent prefill kernel at Kanana-2's (ops/mla_attention.py),
            the chunk write kernel bit for bit over the whole pool
  serve     `python -m ...engine.server` and `python -m ...router.app` as two
            OS processes; through the router: one chat completion, one
            streamed (SSE, every chunk parsed), four concurrent completions,
            one request repeating an earlier prompt's first >=1,024 tokens
            (the engine's prefix-hit counter must rise); /metrics of both
            parse; zero mid-traffic compiles and zero failed builds after
            warm-up; both processes stop on SIGTERM with exit 0

`--child reference [--config benchmarks/chip/configs/<name>.json]` runs ONLY
the comparison with the plain float32 reference (ISSUE 26), in this process:
the engine at the configuration's own flags and seeded weights, 8 seeded
prompts of 128-1,024 tokens prefilled in 512-token chunks and decoded for 32
greedy tokens through the paged cache and the Pallas decode kernel with
top-8 logprobs; then the reference's full forward over prompt + the engine's
own tokens (benchmarks/chip/reference/decoder.py, layer by layer) and the
engine's logprobs against its log-softmax. A second arm with an fp8 KV pool
has to FAIL the same tolerance (`--fp8-arm 0` leaves it out). `--rehearse`
runs it at the configuration's tiny size on the CPU.

`--child batch_probe --config benchmarks/chip/configs/<name>.json` runs ONLY
the batch-invariance probe of a model that selects its keys, with a prompt
of twice its top-k (`child_batch_probe`).

`--chips 4` runs ONLY the four-chip comparison: the same engine at tp=1 on
the first device, then, after it has exited, at --tensor-parallel-size 4 —
same prompts, greedy, logprobs on.

This process never imports JAX: a chip belongs to one process at a time, so
every chip-holding phase is a child, and each child has exited before the
next starts. JAX_PLATFORMS is not set here; whatever platform the children
find is REPORTED, and anything but `tpu` fails the run. One JSON line per
phase; the last line of stdout is only
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
and the exit code is 0 only when that line says ok. Nothing here catches a
failure without recording it as one.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import traceback
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
# compilation included; the driver allows 1200 s
DEADLINE_S = 1140.0
BLOCK_SIZE = 32
# bf16 attention outputs of O(1) magnitude (queries are scaled so the softmax
# is peaked): both paths accumulate in f32 but round at different points — the
# XLA path feeds bf16 probabilities to the MXU, the kernel f32 ones — so an
# element may differ by a few bf16 ulps (2^-8 relative each). Allowed per
# element: KERNEL_ATOL + KERNEL_RTOL * |reference|, i.e. 4 ulps and a floor.
KERNEL_ATOL = 1e-2
KERNEL_RTOL = 2.0 ** -6
# tp=4 reorders every 4096-long bf16 reduction of arm (a) into four partial
# sums + a psum; on first-token logprobs (|lp| ~ ln V ~ 12) that is a few
# hundredths of a nat. Judged on tokens present in both top-8 lists.
LOGPROB_ATOL = 0.25
MIN_SHARED_TOP8 = 4
SPREAD_TOL = 0.10  # per-device bytes within 10% of equal under tp=4
REFERENCE_CONFIG = "benchmarks/chip/configs/olmoe-1b-7b-int8.json"
# The `reference` phase: the engine computes in bf16 (activations, KV) on
# int8 weights, the reference in float32 on the same weights widened, so
# what is compared is bf16 rounding carried through 16 layers into logprobs
# of size ~ln V ~ 11 (PR 21 measured 0.0746 between two bf16 engines of 36
# layers, tp=1 against tp=4). REFERENCE_ATOL lies between its two readings
# on the chip at OLMoE-1B-7B's published widths (PR 26, PERF.md §6): the
# largest max |Δlogprob| the served configuration gave over its four seeds,
# 0.0724 (0.0501 to 0.0724), and what the nearest precision below gives —
# the same engine with an fp8 KV pool, 0.1099 and 0.1434 on its two seeds,
# which has to come out as not correct.
REFERENCE_ATOL = 0.09
# bf16 router inputs flip the 8th and 9th expert where the reference's
# margin between them is within rounding: 8.7% to 9.1% of (token, layer)
# pairs differed in their expert set (PR 26, four seeds), every one at a
# reference margin of at most 0.0084 of softmax mass. The share is reported
# and bounded; the margin is the check: a flip across a wide margin would
# be a fault, not rounding.
ROUTING_DISAGREEMENT_MAX = 0.15
ROUTING_MARGIN_MAX = 0.02
# Kanana-2-30B-A3B at 8 layers (bf16 weights, activations and latents, the
# absorbed kernels) against reference/mla.py (float32, expanded form),
# which FOLLOWS the served programs' routing (reference_check.RoutingTap):
# its top-6 of 128 with renormalised weights makes a boundary expert
# flipped by bf16 rounding swap a sixth of the routed output, and the
# free-running comparison read 3.25 nats of that (PR 31, first chip run).
# REFERENCE_ATOL_MLA lies between its two readings on the chip at the
# published widths (PR 31, PERF.md §6; prompts 2k-16k, 32 decoded
# positions, 1,536 logprobs a seed): the largest max |Δlogprob| the served
# configuration gave over its three seeds, 0.0661 (0.0642, 0.0656, 0.0661),
# and what the nearest precision below gives — the same engine and
# kernels over float8 latents, 0.1930 — which has to come out as not
# correct. The same bf16 rounding carried through 8 layers as OLMoE's 16
# (0.0724 there): logprobs of size ~ln V ~ 12.
REFERENCE_ATOL_MLA = 0.09
# the served choice differed from the reference's own in 9.3% of (token,
# layer) pairs, every one with the served set's worst expert within 0.0151
# of the reference's 6th best biased score (sigmoid score + bias; 0.0138 to
# 0.0151 over the seeds; the float8 arm: 28% of pairs, up to 0.064)
ROUTING_MARGIN_MAX_MLA = 0.02
# Laguna-S-2.1 at 9 layers (bf16 weights, activations and KV, the window
# and full kernels over two page groups) against reference/laguna.py
# (float32), which FOLLOWS the served programs' routing as the MLA
# comparison does and for the same reason: top-10 of 256 sigmoid scores,
# renormalised. Each limit lies between its two readings on the chip at the
# published widths (PR 33, PERF.md §6; prompts 2k-16k, 32 decoded positions,
# 1,536 logprobs a seed): what the served configuration gave over its seeds,
# and what has to come out as not correct — the same engine and kernels over
# a float8 KV pool (the nearest precision below), and the served logprobs of
# the SHORTEST prompt against references that are wrong in one way each (the
# window one page too long, yarn left out, the gate left out).
# max |Δlogprob|: served 0.1015-0.1753 over six runs; float8 pool 0.6204,
# 0.6658; yarn left out 6.52-7.65, the gate left out 4.76-5.59. The window a
# page too long reads 0.145-0.225 — a max over 256 logprobs cannot tell
# that from rounding's few outliers, so the MEAN is held too: rounding
# scatters, a fault in the mathematics shifts every position.
REFERENCE_ATOL_LAGUNA = 0.30
# mean |Δlogprob|: served 0.0244, 0.0248, 0.0263; the window a page too long
# 0.0477, 0.0507, 0.0530 (yarn left out 3.52-3.68, the gate left out
# 2.28-2.39)
REFERENCE_MEAN_ATOL_LAGUNA = 0.037
# the served choice differed from the reference's own in 21.3-21.7% of
# (token, layer) pairs (top-10 of 256: more boundaries than top-6 of 128),
# each within 0.0197-0.0237 of the reference's 10th best score; the float8
# arm: 76.8-77.4% of pairs, up to 0.138
ROUTING_DISAGREEMENT_MAX_LAGUNA = 0.35
ROUTING_MARGIN_MAX_LAGUNA = 0.04
# Solar-Open2 at 8 layers (bf16 weights, activations and pages, float32
# state slots, the KDA and GQA kernels) against reference/solar_open2.py
# (float32, the token-by-token recurrence), which FOLLOWS the served
# programs' routing (top-8 of 320 sigmoid scores + bias, renormalised). Each
# limit lies between its two readings on the chip at the published widths
# (PR 39, PERF.md §6; prompts of 16,320, 9,184, four in 2k-16k and one of 48
# tokens, 32 decoded positions each, 1,792 logprobs a seed): what the served
# configuration gave over its seeds, and what has to come out as not correct
# — the served logprobs of the SHORTEST prompt (what a slot's previous holder
# left is forgotten a few hundred tokens into a row) against references that
# are wrong in one way each.
# max |Δlogprob|: served 0.1236-0.1396 over six seeds; a
# convolution history not zeroed 1.01-1.31, a state not zeroed 2.01-2.67,
# beta without its factor 2 2.27-2.58, the gate left out 5.39-6.87.
REFERENCE_ATOL_SOLAR = 0.40
# mean |Δlogprob|: served 0.0287-0.0299; the same faults 0.256-0.297,
# 0.549-0.630, 0.664-0.707, 2.56-2.75
REFERENCE_MEAN_ATOL_SOLAR = 0.09
# the served choice differed from the reference's own in 16.0-16.4% of
# (token, layer) pairs, every one with the served set's worst expert within
# 0.0174-0.0210 of the reference's 8th best biased score
ROUTING_DISAGREEMENT_MAX_SOLAR = 0.30
ROUTING_MARGIN_MAX_SOLAR = 0.04
# A precision below the configuration's is a bfloat16 STATE, and
# logprobs cannot tell it: on the shortest prompt it reads 0.121-0.141 /
# mean 0.0345-0.0392 beside the served 0.124-0.140 / 0.0287-0.0299 — bf16
# weights and activations through 8 layers scatter as much. So the SERVED
# state has a limit of its own (reference_check.served_state): after the
# check's prompts were served, each row's slot of the last KDA layer's pool
# against reference/solar_open2.py's token-by-token recurrence over the q,
# k, v, g, beta the served programs fed that layer (tapped) — every chunk,
# seam, decode window and bank flip of the row lies between; the largest
# |difference| over the reference's largest magnitude, over the rows. Served
# 1.26e-4, 1.54e-4, 1.58e-4 over three seeds (seven rows, 44k-56k tokens a
# seed), the reference with its state rounded to bfloat16 at every token
# 1.65e-2, 1.81e-2, 2.14e-2 (PR 39, chip, PERF.md §6): the limit lies
# between, 6 x over the one and 16 x under the other.
KDA_STATE_RTOL = 1e-3
# Ling-3.0-flash's language model at 7 layers (bf16 weights, activations and
# latent pages, float32 state slots, the KDA and the latent kernels in one
# program; benchmarks/chip/configs/ling-hybrid-bf16-ep4-7l.json) against
# reference/ling.py (float32, the token-by-token recurrence, the expanded
# MLA equations), which FOLLOWS the served programs' routing (top-8 of 512
# sigmoid scores + bias under a group limit, renormalised). Each limit lies
# between its two readings on the chip at the published widths (PR 45,
# PERF.md §6; prompts of 16,320, 9,184, four in 2k-16k and one of 48 tokens,
# 32 decoded positions each, 1,792 logprobs a seed; seeds 4000000007 and
# 3100000033): what the served configuration gave, and what has to come out
# as not correct — the served logprobs of the SHORTEST prompt against
# references that are wrong in one way each, by ONE of the limits and not by
# each.
# max |Δlogprob|: served 0.3232, 0.1155; a convolution history not zeroed
# 0.646, 0.644, a state not zeroed 0.918, 1.883, beta with a factor 2 2.46,
# 2.33, the unbounded gate 5.63, 4.96. The latent layer's gate left out reads
# 0.412, 0.450 — one gated layer of seven: the MEAN and the routing tell it.
REFERENCE_ATOL_LING = 0.50
# mean |Δlogprob|: served 0.0251, 0.0248; the latent layer's gate left out
# 0.1175, 0.1279 (the same faults as above 0.151-2.54): 2.4 x over the one,
# 2 x under the other
REFERENCE_MEAN_ATOL_LING = 0.06
# the served choice differed from the reference's own in 20.2% of (token,
# layer) pairs on both seeds (top-8 of 512 under a group limit: a flipped
# GROUP moves several experts), each within 0.0544, 0.0475 of the
# reference's 8th best choice score; the gate left out: 37.8%, 39.9% (the
# KDA faults 85-99%)
ROUTING_DISAGREEMENT_MAX_LING = 0.30
ROUTING_MARGIN_MAX_LING = 0.085
# A bfloat16 STATE (served state 3.2e-4, 8.8e-5 against 2.5e-2, 1.9e-2:
# KDA_STATE_RTOL above) and a bfloat16 ROUTER are the nearest precisions
# below the float32 the configuration states, and neither shows in the
# logprobs (0.088-0.122 / mean 0.022-0.027, inside) nor in the routing the
# reference follows (19.6-26.2%, as the served 20.2%: the served programs'
# bf16 ACTIVATIONS already move a fifth of the choices). So the served ROUTER
# has a limit of its own (reference_check.served_router): the reference's
# `route` on the very inputs the last expert layer's router was fed (tapped)
# against the experts it chose, token by token. Served: 0 of 38,528 and 0 of
# 55,552 tokens differ; a router whose product and scores are bfloat16:
# 12.59%, 12.64% (margins up to 0.035). The limit lies between.
ROUTER_CHOICE_DISAGREEMENT_MAX_LING = 0.005
# Falcon-H1-34B at 6 layers (bf16 weights, activations and K/V pages, float32
# state slots, the SSD and the paged GQA kernels of the SAME layer in one
# program; benchmarks/chip/configs/falcon-h1-34b-bf16-6l.json) against
# reference/falcon_h1.py (float32, the token-by-token recurrence, plain
# softmax attention, the multipliers literally as published; nothing routes).
# Rows of the cell's own lengths — 16,320, 9,184 and thirteen log-uniform in
# 2k-16k, all 16 seats in flight with one of 48 tokens — and 32 decoded
# positions each (4,096 logprobs, ~100k tokens a seed; seeds 4000000007 and
# 3100000033; PR 50, PERF.md §6). Each limit lies between what the served
# configuration gave and what has to come out as not correct: the served
# logprobs of the SHORTEST prompt against the reference's fault arms
# (reference/falcon_h1.py: a branch zeroed, a multiplier left out, the norm
# before the gate, the groups' B and C exchanged, D or the convolution's bias
# left out).
# max |Δlogprob|: served 0.0933, 0.0853; the gentlest arm, the embedding's
# multiplier left out (every norm rescales it away: only the branches'
# weight beside the residual moves), 0.846, 0.855; attention zeroed 3.78,
# 4.21, the groups exchanged 3.96, 4.80, the norm before the gate 4.64, 4.32,
# the convolution's bias left out 5.02, 5.56, D left out 5.67, 5.39, a
# multiplier of a branch or of the MLP left out 6.6-10.6, the state-space
# branch zeroed 9.29, 9.42, the head's multiplier left out 230, 226.
REFERENCE_ATOL_FALCON_H1 = 0.30
# mean |Δlogprob|: served 0.0189, 0.0184; the same arms 0.251, 0.233 (the
# embedding's multiplier), 1.41-5.59, 65.5, 68.8: 2.6 x over the one, 4.7 x
# under the other
REFERENCE_MEAN_ATOL_FALCON_H1 = 0.05
# A precision below the configuration's is a bfloat16 STATE, and
# logprobs cannot tell it (0.0924 / mean 0.0201 and 0.0831 / 0.0203 beside
# the served 0.0933 / 0.0189 and 0.0853 / 0.0184: bf16 weights and
# activations scatter as much): the SERVED state
# has a limit of its own (reference_check.served_state), each row's slot of
# the last layer's pool against the reference's recurrence over the x, B, C,
# dt the served programs fed that layer (tapped). Served 6.7e-5, 1.5e-5 (16
# rows, 101,122 and 105,506 tokens), the reference with its state rounded to
# bfloat16 at every token 1.48e-2, 1.23e-2: the limit lies between, 15 x
# over the one and 12 x under the other, and that arm fails by it ALONE.
SSD_STATE_RTOL = 1e-3
# A float8 K/V pool (`--kv-cache-dtype fp8`; the configuration states
# bfloat16 pages, which every layer holds: ~1.3 GiB live beside 0.4 GiB of
# state) is the other precision below, and logprobs cannot tell it either:
# over thousands of diffuse keys the pages' rounding averages away. The fp8
# arm read 0.1378 / mean 0.0194 and 0.1154 / 0.0196 on the two seeds, INSIDE
# the limits above, beside the served 0.0933 / 0.0189 and 0.0853 / 0.0184:
# the mean does not move, and between 0.0933 and 0.1154 no limit on a
# maximum over 4,096 values has room (the seeds alone move it by 0.008 and
# 0.022). So the served PAGES have a limit of their own
# (reference_check.served_pages): each row's pages of the FIRST layer, found
# through the block table the row last ran with, against the reference's K
# (times `key_multiplier`, rotated) and V of the tokens it was fed — one
# norm and two projections from the embedding. Served 3.51e-3, 3.51e-3 (16
# rows, 101,106 and 105,490 tokens: bfloat16's 2^-9 twice over), the fp8 arm
# 2.69e-2, 2.69e-2: the limit lies between, 2.8 x over the one and 2.7 x
# under the other, and that arm fails by it ALONE (its state reads 5.6e-5,
# 1.1e-5: float32 whatever the pages are).
KV_PAGES_RTOL = 1e-2
FALCON_H1_ARMS = (
    {"zero_branch": "attention"}, {"zero_branch": "ssm"},
    {"norm_before_gate": True}, {"swap_groups": True}, {"no_d": True},
    {"no_conv_bias": True},
    *({"skip_multiplier": k} for k in (
        "embedding_multiplier", "lm_head_multiplier",
        "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
        "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers")))
# Olmo-Hybrid-7B at 12 layers (bf16 weights, activations and K/V pages,
# float32 state slots of 30 x 96 x 192; the Gated DeltaNet kernels in 9
# layers and the paged kernels in 3, in one program;
# benchmarks/chip/configs/olmo-hybrid-7b-bf16-12l.json) against
# reference/olmo_hybrid.py (float32, the token-by-token recurrence, plain
# softmax attention; nothing routes). Rows as Falcon-H1's: the cell's own
# lengths, all 16 seats in flight with one of 48 tokens, 32 decoded positions
# each. Each limit lies between what the served configuration gave and what
# has to come out as not correct (PR 58, PERF.md §6; the readings are beside
# each limit): the served logprobs of the SHORTEST prompt against the
# reference's fault arms — every `assumed` reading's alternative
# (OLMO_HYBRID_ARMS).
# max |Δlogprob| (my chip runs, PR 58; seeds 5800000101 and 3300000217, 16
# rows, 512 positions, 4,096 logprobs a seed; under these limits again at
# seeds 5800000501 and 3300000607 from the committed files: served 0.1608,
# 0.1460 / mean 0.0314, 0.0314, the gentlest arm 0.3173, 0.4043 / 0.0859,
# 0.0869, pages 1.57e-2, 1.59e-2 against the fp8 arm's 3.09e-2, 3.08e-2,
# exit 0 both): served 0.1593, 0.1651; the
# gentlest arm, a QK-norm a head instead of the flat one, 0.4030, 0.3797; a
# rope of base 500,000 1.73, 1.59, of 10,000 1.77, 1.94; a sigmoid output
# gate 8.21, 7.46; a pre-norm linear block 7.79, 8.08. The limit lies
# between: 1.6 x over the one, 1.5 x under the other.
REFERENCE_ATOL_OLMO_HYBRID = 0.26
# mean |Δlogprob|: served 0.0307, 0.0314; the same arms 0.0880, 0.0972 (the
# QK-norm a head), 0.46-0.62 (a rope), 4.28-4.50 (the gate, the pre-norm
# block): 1.75 x over the one, 1.6 x under the other
REFERENCE_MEAN_ATOL_OLMO_HYBRID = 0.055
# A precision below the configuration's is a bfloat16 STATE, and logprobs
# cannot tell it (0.1993 / mean 0.0509 and 0.2200 / 0.0470 on the shortest
# prompt, inside both limits above): the SERVED state's limit does
# (KDA_STATE_RTOL, 1e-3: each row's slot of the last linear layer against the
# reference's recurrence over the q, k, v, g, beta the served programs fed
# it): served 4.7e-6, 1.7e-6 (16 rows, 138,920 and 102,268 tokens), the
# reference with its state rounded to bfloat16 at every token 9.2e-3,
# 1.2e-2 — that arm fails by the state's limit ALONE.
# A float8 K/V pool is the other precision below (the 3 full layers hold 48
# KiB a token: most of the cache's bytes), and logprobs cannot tell it
# either: the fp8 arm read 0.1494 / mean 0.0309 and 0.2036 / 0.0322 beside
# the served 0.1593 / 0.0307 and 0.1651 / 0.0314. The served PAGES have a
# limit of their own (reference_check.served_pages): each row's pages of the
# first FULL layer (layer 3: behind three Gated DeltaNet layers and their
# SwiGLUs, all bf16 — not one norm and two projections from the embedding as
# Falcon-H1's first layer, so the served reading is what those layers'
# activations scatter by, not bfloat16's 2^-9) against the reference's K
# (after the flat norm) and V. Served 1.54e-2 (16 rows, 102,252 tokens), the
# fp8 arm 3.10e-2 (1.54e-2 and float8's 2^-4 / sqrt 3 in quadrature): the
# limit lies between, 1.4 x over the one and 1.4 x under the other, and the
# fp8 arm fails by it ALONE (its state reads 5.3e-6). (The first seed's rows
# summed to 138,920 tokens, more than the pool's 131,072: two rows waited,
# pages were given back and written again, and both arms read 0.98 — the
# rows now fit the pool: `_fit_pool`.)
KV_PAGES_RTOL_OLMO_HYBRID = 2.2e-2
OLMO_HYBRID_ARMS = (
    {"pre_norm_linear": True}, {"output_gate": "sigmoid"},
    {"rope_theta": 10000.0}, {"rope_theta": 500000.0},
    {"qk_norm_per_head": True})
# SDAR-30B-A3B (generation by diffusion over blocks, bf16 weights, 8 of 48
# layers; benchmarks/chip/configs/sdar-30b-a3b-bf16-8l.json): the served
# windows of block passes against reference/sdar.py's forward over the same
# block states, teacher-forced with the engine's own order of unmasking and
# the served programs' routing (testing/reference_check.py:compare_blocks),
# at the logprobs of the tokens each pass unmasked. Both limits lie between
# the served arm's readings and the faulty references' (PERF.md §6, PR 41,
# three seeds on the chip): the largest |Δlogprob| and the mean, which a
# fault in the mathematics shifts at every position while rounding scatters.
# Largest: served 0.0225-0.0268; no commit pass 0.139-0.242, no q/k norm
# 0.179-0.211, shifted logits and a causal mask 0.64-1.26.
REFERENCE_ATOL_SDAR = 0.09
# Mean: served 0.0063-0.0067 over 8 rows (36-44 positions); the router, the
# softmax and the confidence in bfloat16 (the nearest precision below the
# float32 the configuration states) 0.0199 and 0.0208 over the same rows on
# two seeds (its largest, 0.067-0.078, is inside the limit above: the mean
# alone tells it); no commit pass 0.028-0.036. 1.8 x over the one, 1.7 x
# under the other.
REFERENCE_MEAN_ATOL_SDAR = 0.012


# GLM-5.2 at 7 layers (bf16 weights, activations, latents and index keys,
# ops/dsa_attention.py's selection) against reference/glm_dsa.py (float32,
# expanded form), which FOLLOWS the served programs' routing as the MLA
# comparison does AND the key positions they selected
# (reference_check.SelectionTap): a bf16 index score flips the 2,048th and
# the 2,049th key as a bf16 router flips a boundary expert, and a
# free-running comparison would measure the flips. Each limit lies between
# its readings on the chip at the published widths (PR 55, PERF.md §6): what
# the served configuration gave over its seeds, and what has to come out as
# not correct — the reference wrong in one way each (GLM_DSA_ARMS), on the
# prompt of 2.5 x the top-k. The readings below are this child's own, as
# committed, on seeds 3100000019 and 3100000073 (both exit 0; five prompts
# of 4.2k-16.3k tokens in flight together, 160 positions, 1,280 logprobs a
# seed); two earlier runs of six prompts (3100000019, 3100000033) and a
# scratch run of one 5,200-token prompt (3100000047) read alike.
# Served: max |dlogprob| 0.0875, 0.1078 (earlier 0.0847, 0.1001, 0.0740);
# mean 0.0213, 0.0219 — the Kanana arm's rounding (0.066 at 8 layers of 32
# heads) at 64 heads and hidden 6,144. What has to come out as not correct
# by these two: a borrowing layer handed the selection of the full layer
# before the right one — 0.226 / 0.056 and 0.270 / 0.057 (0.2035 / 0.0512 on
# the scratch prompt). On the SHORTEST prompt of the earlier runs, 2.1-2.4k
# tokens, where nearly every key is selected, it read 0.108 / 0.030 and
# 0.096 / 0.024: no limit can tell it there, which is why the arms are held
# to a prompt of 2.5 x the top-k.
REFERENCE_ATOL_GLM_DSA = 0.15
REFERENCE_MEAN_ATOL_GLM_DSA = 0.035
# the served expert choice differed from the reference's own in 11.9% /
# 11.6% of (token, layer) pairs (top-8 of 256: more boundary pairs than
# Kanana's top-6 of 128), the served set's worst expert within 0.0097 /
# 0.0099 of the reference's 8th best biased score (0.0102 earlier); the
# wrong lender 0.030 / 0.023
ROUTING_DISAGREEMENT_MAX_GLM_DSA = 0.20
ROUTING_MARGIN_MAX_GLM_DSA = 0.02
# the served (token, slot) choices outside the reference's own top-2,048:
# 0.67% / 0.59% of 187 M / 147 M slots (bf16 scores AND the bf16 hidden state
# under them flip keys near the 2,048th), 0.33% on the arms' prompt. There
# the ReLU left out reads 10.3% / 10.5%, the heads' weights left out 29.5% /
# 29.8%, the last 2,048 keys 29.5% / 29.2%, top-1,024 43.8% / 43.8%, the key
# un-normed 3.7% / 3.6%. How far the worst served slot lies under the
# reference's 2,048th best score, in standard deviations of the scores:
# 0.27 / 0.25 (0.92 / 0.63 in the earlier six-prompt runs: a MAXIMUM over
# 2e8 slots of what four layers of bf16 activations move a score by, so a
# wide limit); the ReLU 3.0 / 3.1, the weights 3.8 / 4.1, top-1,024 5.3 /
# 5.4; the key un-normed 1.42 / 1.19 is inside: the share tells it.
# Index keys rounded to float8 read 0.73% / 0.73% and 0.45 / 0.44: INSIDE
# both — the pages' limit below tells that arm. (The FIRST layer's share
# alone, reported beside the whole and not limited yet: served 0.23% / 0.20%,
# 0.12% on the arms' prompt, where float8 keys read 0.63% / 0.62%.)
SELECTION_DISAGREEMENT_MAX_GLM_DSA = 0.02
SELECTION_MARGIN_SPREADS_MAX_GLM_DSA = 1.5
# the index keys the first layer holds (bf16) against the reference's
# float32 ones: 0.0031 on every seed (a bf16 key's rounding); against the
# keys a float8 cache would hold — the nearest precision below the
# configuration's, the `key_dtype` arm — 0.0267 / 0.0267 (a float8 key's
# rounding; 0.0256 in float32 on the CPU at tiny size)
INDEX_PAGES_RTOL_GLM_DSA = 1e-2
GLM_DSA_ARMS = (
    {"relu": False}, {"head_weights": False}, {"last_k": True},
    {"topk_share": 0.5}, {"key_norm": False},
    {"key_dtype": "float8_e4m3fn"}, {"borrow_lag": True})


def engine_flags(model: str, seed: int, tp: int = 1,
                 warmup: bool = True) -> list[str]:
    """The §1 engine: full published widths and depth, int8 weights so 8B
    fits one 16 GB chip, a bf16 pool of 32-token pages at max_model_len 4096
    so `auto` selects the Pallas decode kernel on a TPU, and a program set
    bounded to one prefill bucket x one decode bucket x one context width so
    a cold run compiles little (coarse warm-up covers every key traffic can
    ask for: rows 1/2/4 x chunk 512, batch 4 x windows 1/2/4/8)."""
    flags = [
        "--model", model, "--quantization", "int8", "--seed", str(seed),
        "--block-size", str(BLOCK_SIZE), "--max-model-len", "4096",
        "--num-blocks", "768",
        "--max-num-seqs", "4", "--max-num-batched-tokens", "512",
        "--prefill-buckets", "512", "--decode-buckets", "4",
        "--width-floor-blocks", "128",
        "--tensor-parallel-size", str(tp),
    ]
    if warmup:
        flags += ["--warmup", "--warmup-scope", "coarse"]
    return flags


# -- plumbing ----------------------------------------------------------------

_T0 = time.monotonic()


def remaining() -> float:
    return DEADLINE_S - (time.monotonic() - _T0)


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


class Failures:
    """Every caught failure lands here; the exit code is non-zero if any."""

    def __init__(self) -> None:
        self.items: list[str] = []

    def add(self, what: str) -> None:
        self.items.append(what)

    def check(self, cond: bool, what: str) -> bool:
        if not cond:
            self.add(what)
        return bool(cond)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(method: str, url: str, body: dict | None = None,
         timeout: float = 300.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    return urllib.request.urlopen(req, timeout=timeout)


def http_json(method: str, url: str, body: dict | None = None,
              timeout: float = 300.0) -> dict:
    try:
        with http(method, url, body, timeout) as r:
            return json.loads(r.read())
    except urllib.error.HTTPError as e:
        raise RuntimeError(
            f"{method} {url} -> HTTP {e.code}: {e.read()[:400]!r}"
        ) from e


def log_tail(path: str, n: int = 1500) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")
    except OSError as e:
        return f"<no log: {e}>"


class Child:
    """One OS process of the stack, its log, and its orderly stop."""

    def __init__(self, name: str, argv: list[str]):
        os.makedirs(OUT_DIR, exist_ok=True)
        self.name = name
        self.log_path = os.path.join(OUT_DIR, f"{name}.log")
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, *argv], cwd=REPO,
            stdout=self._log, stderr=subprocess.STDOUT,
        )

    def wait_healthy(self, url: str, timeout_s: float) -> float:
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout_s:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"{self.name} exited rc={self.proc.returncode} before "
                    f"healthy: {log_tail(self.log_path)}"
                )
            try:
                with http("GET", url + "/health", timeout=2) as r:
                    if r.status == 200:
                        return time.monotonic() - t0
            except (urllib.error.URLError, OSError):
                pass  # not listening yet; the loop's deadline decides
            time.sleep(1.0)
        raise TimeoutError(
            f"{self.name} not healthy after {timeout_s:.0f}s: "
            f"{log_tail(self.log_path)}"
        )

    def stop(self, fails: Failures, grace_s: float = 60.0) -> int | None:
        """SIGTERM, wait, and hold the process to exit code 0. A process
        that needs SIGKILL is a failure — and is killed all the same, so
        nothing outlives the smoke."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)
                fails.add(f"{self.name} ignored SIGTERM for {grace_s:.0f}s")
        self._log.close()
        rc = self.proc.returncode
        fails.check(rc == 0, f"{self.name} exited rc={rc}: "
                             f"{log_tail(self.log_path, 600)}")
        return rc


_SAMPLE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(?:\{(?:[^"{}]|"(?:[^"\\]|\\.)*")*\})?)'
    r' (\S+)(?: -?[0-9]+)?$'
)


def parse_metrics(text: str) -> dict[str, float]:
    """Prometheus text exposition -> {series-with-labels: value}; a line
    that is neither a comment nor a sample raises (float() takes +Inf and
    NaN, and raises on anything that is not a number)."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line)
        if m is None:
            raise ValueError(f"unparseable metrics line: {line[:200]!r}")
        out[m.group(1)] = float(m.group(2))
    return out


def scrape(url: str) -> dict[str, float]:
    with http("GET", url + "/metrics") as r:
        return parse_metrics(r.read().decode())


def metric_sum(samples: dict[str, float], name: str) -> float:
    return sum(v for k, v in samples.items()
               if k == name or k.startswith(name + "{"))


def filler(n: int, salt: int) -> str:
    """n printable ASCII chars — n tokens under the byte tokenizer the
    presets serve with (no vocabulary files, no network)."""
    words = ("lorem", "ipsum", "dolor", "sit", "amet", "tpu", "page", "kv")
    out, i = [], salt
    while sum(len(w) + 1 for w in out) < n:
        out.append(f"{words[i % len(words)]}{i * 7919 % 1000}")
        i += 1
    return " ".join(out)[:n]


# -- device report -----------------------------------------------------------

def check_device(dev: dict, fails: Failures, want_count: int,
                 want_decode: str | None) -> None:
    """Hold the engine's own statement of what it runs on (GET
    /debug/programs "device") to the chip: the platform is asserted HERE,
    not inferred from an exit code."""
    fails.check(dev["platform"] == "tpu",
                f"engine runs on platform {dev['platform']!r}, not 'tpu'")
    fails.check(dev["count"] == want_count,
                f"engine sees {dev['count']} devices, expected {want_count}")
    att = dev["attention"]
    for phase in ("decode", "prefill"):
        fails.check(att[phase]["resolved"] != "pallas_interpret",
                    f"{phase} attention resolved to interpret mode")
    if want_decode is not None and dev["platform"] == "tpu":
        fails.check(
            att["decode"]["resolved"] == want_decode,
            f"decode attention resolved to {att['decode']['resolved']!r}, "
            f"expected {want_decode!r}",
        )


def device_summary(dev: dict | None) -> dict | None:
    if dev is None:
        return None
    return {"platform": dev["platform"], "kind": dev["kind"],
            "count": dev["count"]}


# -- requests ----------------------------------------------------------------

def chat_plain(url: str, model: str) -> dict:
    body = {
        "model": model, "max_tokens": 32, "temperature": 0.0,
        "ignore_eos": True,
        "messages": [{"role": "user", "content": "Say hello to the chip."}],
    }
    out = http_json("POST", url + "/v1/chat/completions", body)
    text = out["choices"][0]["message"]["content"]
    if not isinstance(text, str):
        raise RuntimeError(f"chat content is not text: {out!r}"[:400])
    tokens = out["usage"]["completion_tokens"]
    if tokens != 32:
        raise RuntimeError(f"asked 32 tokens, got {tokens}")
    return {"request": "chat", "tokens": tokens}


def chat_streamed(url: str, model: str) -> dict:
    want = 80
    body = {
        "model": model, "max_tokens": want, "temperature": 0.0,
        "ignore_eos": True, "stream": True,
        "stream_options": {"include_usage": True},
        "messages": [{"role": "user", "content": "Stream me a story."}],
    }
    chunks = tokens = 0
    done = False
    with http("POST", url + "/v1/chat/completions", body) as resp:
        if resp.status != 200:
            raise RuntimeError(f"stream -> HTTP {resp.status}")
        for raw in resp:
            line = raw.decode().strip()
            if not line:
                continue
            if not line.startswith("data: "):
                raise RuntimeError(f"not an SSE data line: {line[:200]!r}")
            payload = line[len("data: "):]
            if payload == "[DONE]":
                done = True
                break
            chunk = json.loads(payload)  # every chunk must parse
            if chunk.get("error"):
                raise RuntimeError(f"stream error chunk: {chunk!r}"[:400])
            chunks += 1
            if chunk.get("usage"):
                tokens = chunk["usage"]["completion_tokens"]
    if not done:
        raise RuntimeError("stream ended without [DONE]")
    # tokens, not text: random weights over a 151,936-entry vocabulary
    # mostly sample ids the byte tokenizer renders as nothing
    if tokens < 64 or chunks < 2:
        raise RuntimeError(
            f"stream returned {tokens} tokens in {chunks} chunks"
        )
    return {"request": "chat_stream", "tokens": tokens, "chunks": chunks}


def completion(url: str, model: str, prompt: str, max_tokens: int,
               logprobs: int | None = None) -> dict:
    body = {
        "model": model, "prompt": prompt, "max_tokens": max_tokens,
        "temperature": 0.0, "ignore_eos": True,
    }
    if logprobs is not None:
        body["logprobs"] = logprobs
    out = http_json("POST", url + "/v1/completions", body)
    tokens = out["usage"]["completion_tokens"]
    if tokens != max_tokens:
        raise RuntimeError(f"asked {max_tokens} tokens, got {tokens}")
    return out


def concurrent_completions(url: str, model: str, prompts: list[str]) -> list:
    results: list = [None] * len(prompts)

    def one(i: int) -> None:
        try:
            out = completion(url, model, prompts[i], 32)
            results[i] = {"request": f"completion_{i}",
                          "tokens": out["usage"]["completion_tokens"],
                          "prompt_tokens": out["usage"]["prompt_tokens"]}
        except Exception as e:  # re-raised below, from the parent thread
            results[i] = e

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    for r in results:
        if r is None:
            raise RuntimeError("a concurrent completion never returned")
        if isinstance(r, Exception):
            raise r
    return results


# -- phases ------------------------------------------------------------------

def phase_kernels(args, fails: Failures) -> None:
    """Chip-holding child #1; it has exited before the engine starts."""
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", "kernels",
         "--seed", str(args.seed)],
        cwd=REPO, capture_output=True, text=True,
        timeout=max(30.0, min(400.0, remaining() - 600.0)),
    )
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    report = json.loads(lines[-1]) if lines else {}
    report.update(phase="kernels", rc=proc.returncode,
                  seconds=round(time.monotonic() - t0, 1))
    if proc.returncode != 0 or not report.get("ok"):
        report["ok"] = False
        report["stderr_tail"] = proc.stderr[-1200:]
        fails.add(f"kernels: rc={proc.returncode} "
                  f"{report.get('error') or report.get('kernels')}")
    emit(report)


def phase_serve(args, fails: Failures) -> dict | None:
    """Engine + router as two processes, the seven requests, the checks."""
    device = None
    engine = router = None
    engine_port, router_port = free_port(), free_port()
    engine_url = f"http://127.0.0.1:{engine_port}"
    url = f"http://127.0.0.1:{router_port}"
    try:
        engine = Child("engine", [
            "-m", "vllm_production_stack_tpu.engine.server",
            "--port", str(engine_port), "--host", "127.0.0.1",
            *engine_flags(args.model, args.seed),
        ])
        boot_s = engine.wait_healthy(engine_url, max(60.0, remaining() - 240))
        programs = http_json("GET", engine_url + "/debug/programs")
        device = dev = programs["device"]
        check_device(dev, fails, want_count=1, want_decode="pallas")
        emit({
            "phase": "engine_up", "ok": dev["platform"] == "tpu",
            "model": args.model, "quantization": "int8",
            "engine_pid": engine.proc.pid,
            "boot_to_healthy_s": round(boot_s, 1),
            "warmup_programs": len(programs["programs"]),
            "warmup_compile_wall_s": round(sum(
                p["compile_wall_s"] for p in programs["programs"]), 1),
            "device": dev,
        })

        router = Child("router", [
            "-m", "vllm_production_stack_tpu.router.app",
            "--port", str(router_port), "--host", "127.0.0.1",
            "--service-discovery", "static",
            "--static-backends", engine_url,
            "--static-models", args.model,
        ])
        router.wait_healthy(url, min(120.0, max(30.0, remaining() - 200)))

        served: list[dict] = []
        served.append(chat_plain(url, args.model))
        served.append(chat_streamed(url, args.model))
        # four at once; the first carries the >=1,024-token prefix the last
        # request of the smoke repeats
        shared = filler(1280, salt=1)
        prompts = [shared + " first tail"] + [
            filler(200 + 60 * i, salt=100 + i) for i in range(1, 4)
        ]
        served += concurrent_completions(url, args.model, prompts)
        before = scrape(engine_url)
        out = completion(url, args.model, shared + " a different tail", 16)
        served.append({"request": "prefix_repeat",
                       "tokens": out["usage"]["completion_tokens"],
                       "prompt_tokens": out["usage"]["prompt_tokens"]})
        engine_metrics, router_metrics = scrape(engine_url), scrape(url)
        hit_blocks = (
            metric_sum(engine_metrics, "tpu:hbm_prefix_cache_hits_total")
            - metric_sum(before, "tpu:hbm_prefix_cache_hits_total")
        )
        hit = fails.check(
            hit_blocks * BLOCK_SIZE >= 1024,
            f"prefix repeat hit {hit_blocks:.0f} blocks "
            f"({hit_blocks * BLOCK_SIZE:.0f} tokens), expected >=1024 tokens",
        )
        emit({"phase": "requests", "ok": hit, "via": "router",
              "router_pid": router.proc.pid, "served": served,
              "prefix_hit_blocks": hit_blocks,
              "engine_metric_samples": len(engine_metrics),
              "router_metric_samples": len(router_metrics)})

        programs = http_json("GET", engine_url + "/debug/programs")
        mid = sum(v for k, v in programs["compiles"].items()
                  if k.endswith("/mid_traffic"))
        failed = programs["failed_builds"]["total"]
        fails.check(mid == 0, f"{mid} mid-traffic compiles after warm-up: "
                              f"{programs['compiles']}")
        fails.check(failed == 0, f"{failed} failed program builds: "
                                 f"{programs['failed_builds']['last']}")
        dev = programs["device"]
        emit({"phase": "after_traffic", "ok": mid == 0 and failed == 0,
              "mid_traffic_compiles": mid, "failed_builds": failed,
              "compiles": programs["compiles"],
              "program_cache": programs["cache"],
              "kvhash": dev["kvhash"], "attention": dev["attention"],
              "hbm": dev["devices"]})
    except Exception as e:
        fails.add(f"serve: {type(e).__name__}: {e}"[:1500])
        emit({"phase": "serve", "ok": False,
              "error": f"{type(e).__name__}: {e}"[:1500],
              "trace": traceback.format_exc()[-800:]})
    finally:
        codes = {}
        for child in (router, engine):
            if child is not None:
                codes[child.name] = child.stop(fails)
        emit({"phase": "shutdown", "signal": "SIGTERM", "exit_codes": codes,
              "ok": bool(codes) and all(c == 0 for c in codes.values())})
    return device


def run_arm(args, fails: Failures, tp: int, prompts: list[str]) -> dict:
    """One arm of the four-chip comparison: the §1 engine at `tp`, asked
    directly (the router path is the one-chip run's business), greedy with
    logprobs. The engine has exited when this returns."""
    name = f"tp{tp}"
    port = free_port()
    url = f"http://127.0.0.1:{port}"
    arm: dict = {"tp": tp, "device": None, "outputs": []}
    # no warm-up: logprobs programs are their own static variants, so the
    # requests compile exactly what they use
    engine = Child(f"engine_{name}", [
        "-m", "vllm_production_stack_tpu.engine.server",
        "--port", str(port), "--host", "127.0.0.1",
        *engine_flags(args.model, args.seed, tp=tp, warmup=False),
    ])
    try:
        boot_s = engine.wait_healthy(url, max(60.0, remaining() - 120))
        arm["device"] = http_json("GET", url + "/debug/programs")["device"]
        check_device(arm["device"], fails, want_count=4,
                     want_decode="pallas" if tp == 1 else None)
        for prompt in prompts:
            out = completion(url, args.model, prompt, 16, logprobs=8)
            lp = out["choices"][0]["logprobs"]
            arm["outputs"].append({
                "tokens": lp["tokens"],
                "first_top8": lp["top_logprobs"][0],
            })
        dev = http_json("GET", url + "/debug/programs")["device"]
        arm["device"] = dev
        ids = dev["mesh_device_ids"]
        fails.check(len(set(ids)) == tp,
                    f"{name}: mesh device ids {ids}, expected {tp} distinct")
        emit({"phase": f"arm_{name}", "ok": True,
              "boot_to_healthy_s": round(boot_s, 1),
              "served": len(arm["outputs"]), "mesh_device_ids": ids,
              "attention": dev["attention"], "hbm": dev["devices"]})
    except Exception as e:
        fails.add(f"arm {name}: {type(e).__name__}: {e}"[:1500])
        emit({"phase": f"arm_{name}", "ok": False,
              "error": f"{type(e).__name__}: {e}"[:1500]})
    finally:
        engine.stop(fails)
    return arm


def phase_four_chips(args, fails: Failures) -> dict | None:
    prompts = [filler(300 + 150 * i, salt=40 + i) for i in range(4)]
    one = run_arm(args, fails, 1, prompts)  # exits before tp=4 starts
    four = run_arm(args, fails, 4, prompts)
    report: dict = {"phase": "tp1_vs_tp4", "logprob_atol": LOGPROB_ATOL,
                    "min_shared_top8": MIN_SHARED_TOP8,
                    "spread_tol": SPREAD_TOL}
    ok = fails.check(
        len(one["outputs"]) == len(prompts)
        and len(four["outputs"]) == len(prompts),
        "an arm did not serve every request",
    )
    if ok:
        worst, shared_min, prefixes = 0.0, 8, []
        for a, b in zip(one["outputs"], four["outputs"]):
            common = set(a["first_top8"]) & set(b["first_top8"])
            shared_min = min(shared_min, len(common))
            for tok in common:
                worst = max(worst,
                            abs(a["first_top8"][tok] - b["first_top8"][tok]))
            n = 0
            for x, y in zip(a["tokens"], b["tokens"]):
                if x != y:
                    break
                n += 1
            prefixes.append(n)  # printed, not judged: flat random logits
        report.update(first_token_max_abs_logprob_diff=round(worst, 4),
                      first_token_min_shared_top8=shared_min,
                      common_greedy_prefix_lens=prefixes)
        fails.check(shared_min >= MIN_SHARED_TOP8,
                    f"tp=4 shares only {shared_min}/8 first-token top "
                    "logprob tokens with tp=1")
        fails.check(worst <= LOGPROB_ATOL,
                    f"tp=4 first-token logprobs differ from tp=1 by {worst}")
    dev = four["device"]
    if dev is not None:
        used = [d.get("bytes_in_use") for d in dev["devices"]]
        report["tp4_bytes_in_use"] = used
        if fails.check(all(u is not None for u in used) and len(used) == 4,
                       f"tp=4 per-device bytes not reported: {used}"):
            mean = sum(used) / len(used)
            spread = max(abs(u - mean) for u in used) / mean
            report["tp4_spread"] = round(spread, 4)
            fails.check(spread <= SPREAD_TOL,
                        f"tp=4 weight+KV bytes spread {spread:.3f} over "
                        f"devices {dev['mesh_device_ids']}: {used}")
    report["ok"] = not fails.items
    emit(report)
    return dev or one["device"]


# -- the kernels child (the only code here that imports JAX) -----------------

def child_kernels(seed: int) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, REPO)
    from vllm_production_stack_tpu.ops.attention import (
        causal_page_mask, paged_attention_with_staged, paged_attention_xla,
        write_kv_pages_blockwise,
    )
    from vllm_production_stack_tpu.ops.mla_attention import (
        latent_attention_xla,
    )
    from vllm_production_stack_tpu.ops.mla_attention_pallas import (
        mla_prefill_attention,
    )
    from vllm_production_stack_tpu.ops.paged_attention_pallas import (
        paged_decode_attention, paged_kv_write, paged_prefill_attention,
        paged_window_prefill_attention,
    )
    from vllm_production_stack_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    dev = jax.devices()[0]
    report: dict = {"platform": dev.platform, "kind": dev.device_kind,
                    "atol": KERNEL_ATOL, "rtol": KERNEL_RTOL, "kernels": {}}
    if dev.platform != "tpu":
        # a Mosaic kernel runs nowhere else, and interpret mode proves
        # nothing about the chip
        report.update(ok=False, error=f"platform is {dev.platform!r}: "
                                      "kernels not run")
        emit(report)
        return 1
    configure_compile_cache()
    nh, kvh, d = 32, 8, 128  # qwen3-8b
    bs, num_blocks, nb, w = BLOCK_SIZE, 1024, 128, 8
    scale = d ** -0.5
    rng = np.random.RandomState(seed)

    def arr(*shape, gain=1.0):
        return jnp.asarray(gain * rng.randn(*shape), jnp.bfloat16)

    def tables(b):  # read-only checks: rows may share pages
        return jnp.asarray(rng.randint(1, num_blocks, size=(b, nb)), jnp.int32)

    def diff(got, want) -> dict:
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        err = np.abs(got - want)
        allowed = KERNEL_ATOL + KERNEL_RTOL * np.abs(want)
        return {
            "max_abs_diff": float(np.max(err)),
            "ref_abs_max": float(np.max(np.abs(want))),
            "ok": bool(np.all(np.isfinite(got)) and np.all(err <= allowed)),
        }

    kv = arr(2, num_blocks, bs, kvh, d)
    for name, pool in (("decode", kv),
                       ("decode_fp8_pool", kv.astype(jnp.float8_e4m3fn))):
        b, step_k = 8, 3
        q, tb = arr(b, 1, nh, d, gain=3.0), tables(b)
        hist = jnp.asarray(rng.randint(1, nb * bs, size=b), jnp.int32)
        sk, sv = arr(w, b, kvh, d), arr(w, b, kvh, d)
        got = paged_decode_attention(
            q[:, 0], pool, tb, hist, sk, sv, jnp.int32(step_k), scale=scale,
        )
        want = paged_attention_with_staged(
            q, pool, tb, jnp.arange(nb * bs)[None, :] < hist[:, None],
            sk, sv, jnp.arange(w) <= step_k, scale=scale,
        )[:, 0]
        report["kernels"][name] = diff(got, want)

    # prefill at the benchmark cells' head shapes: a continued chunk, a first
    # chunk, a short chunk that starts mid-page, a padding row (never read)
    t = 512
    start = jnp.asarray([1024, 0, 1000, 0], jnp.int32)
    ctx = jnp.asarray([1536, 512, 1300, 0], jnp.int32)
    positions = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    real = np.asarray(positions < ctx[:, None])  # the chunk's own tokens
    # ... and at the long-document cells': Laguna-S-2.1's full (48/8) and
    # window (72/8, window 512) layers, Solar-Open2's GQA layers (64/8)
    for heads, kv_heads, window in (
            (32, 8, 0), (28, 4, 0), (16, 16, 0),
            (48, 8, 0), (64, 8, 0), (72, 8, 512)):
        q, tb = arr(4, t, heads, d, gain=3.0), tables(4)
        pool = arr(2, num_blocks, bs, kv_heads, d)
        if window:
            got = paged_window_prefill_attention(
                q, pool, tb, ctx, start, scale=scale, window=window)
        else:
            got = paged_prefill_attention(
                q, pool, tb, ctx, start, scale=scale)
        want = paged_attention_xla(
            q, pool, tb,
            causal_page_mask(positions, ctx, nb * bs, window=window),
            scale=scale,
        )
        got = np.asarray(got, np.float32)
        check = diff(got[real], np.asarray(want, np.float32)[real])
        check["ok"] = check["ok"] and bool(np.all(np.isfinite(got)))
        report["kernels"][
            f"prefill_{heads}_{kv_heads}" + (f"_w{window}" if window else "")
        ] = check

    # the latent prefill kernel at Kanana-2's and Ling's shapes (32 heads,
    # rank 512 + rope 64 stored 640 wide): the same four rows, both pools
    lat = arr(1, num_blocks, bs, 640).at[..., 576:].set(0)
    qa, tb = arr(4, t, 32, 640, gain=3.0).at[..., 576:].set(0), tables(4)
    mask = causal_page_mask(positions, ctx, nb * bs)
    for name, pool in (("mla_prefill", lat),
                       ("mla_prefill_fp8_pool", lat.astype(jnp.float8_e4m3fn))):
        got = np.asarray(mla_prefill_attention(
            qa, pool, tb, ctx, start, scale=192 ** -0.5, rank=512), np.float32)
        want = latent_attention_xla(qa, pool, tb, mask, scale=192 ** -0.5,
                                    rank=512)
        check = diff(got[real], np.asarray(want, np.float32)[real])
        check["ok"] = check["ok"] and bool(np.all(np.isfinite(got)))
        report["kernels"][name] = check

    # the chunk write kernel against the XLA form, the WHOLE pool bit for
    # bit: four rows packed on a 512-token axis (a continued row that starts
    # and ends mid-page, a one-token row, a first chunk, a padding row), at
    # the cells' kv head counts and an fp8 pool
    ids = np.zeros((4, (t - 1) // bs + 2), np.int32)
    hist, lens = np.array([1000, 95, 0, 0]), np.array([301, 1, 210, 0])
    pages = iter(rng.permutation(np.arange(1, num_blocks)).tolist())
    for i in range(3):
        span = (hist[i] + lens[i] - 1) // bs - hist[i] // bs + 1
        ids[i, :span] = [next(pages) for _ in range(span)]
    write = tuple(jnp.asarray(a, jnp.int32) for a in (
        ids, hist % bs, lens, np.cumsum(lens) - lens))
    for kv_heads, dtype in ((4, jnp.bfloat16), (8, jnp.bfloat16),
                            (16, jnp.bfloat16), (4, jnp.float8_e4m3fn)):
        pool = arr(2, num_blocks, bs, kv_heads, d).astype(dtype)
        k, v = arr(t, kv_heads, d), arr(t, kv_heads, d)
        got = np.asarray(paged_kv_write(pool, k, v, *write)).view(np.uint8)
        want = np.asarray(write_kv_pages_blockwise(
            pool, k, v, *write[:3], row_off=write[3])).view(np.uint8)
        report["kernels"][f"kv_write_{kv_heads}_{jnp.dtype(dtype).name}"] = {
            "bytes_that_differ": int(np.sum(got != want)),
            "bytes_written": int(np.sum(
                want != np.asarray(pool).view(np.uint8))),
            "ok": bool(np.array_equal(got, want)),
        }

    # the pass that makes a KDA layer's q, k, v (ops/kda_short_conv.py)
    # against the XLA form it stands in for, bf16 in and out: a continued
    # row, a fresh row whose slot held garbage and a padding row on a
    # 512-token axis, at Solar-Open2's and Ling's head counts — the new
    # tails bit for bit, q, k, v within one bf16 step
    from vllm_production_stack_tpu.models import llama

    lens = jnp.asarray([210, 290, 0], jnp.int32)
    pack = llama.pack_index(lens, lens, 512)
    for heads in (64, 32):
        width = 3 * heads * 128
        x, taps, tails = arr(512, width), arr(4, width), arr(8, 3, width)
        out = {}
        for interpret in (None, False):
            recur = llama._PackedRecurrence({"conv": tails}, {
                "read": jnp.asarray([1, 2, 3]), "write": jnp.asarray([4, 5, 6]),
                "row_off": pack["row_off"], "tok_row": pack["tok_row"],
                "chunk_lens": lens, "fresh": jnp.asarray([False, True, False]),
                "interpret": interpret, "tap": None}, None)
            qkv = recur.qkv(tuple(jnp.split(x, 3, axis=-1)), taps, heads, 128)
            out[interpret] = (np.concatenate([np.asarray(
                y[:500], np.float32).ravel() for y in qkv]),
                np.asarray(recur.pools["conv"], np.float32))
        (want, want_tails), (got, got_tails) = out[None], out[False]
        steps = float(np.max(np.abs(got - want) / 2.0 ** (np.floor(
            np.log2(np.maximum(np.abs(want), 1e-30))) - 7)))
        report["kernels"][f"kda_short_conv_{heads}"] = {
            "max_bf16_steps": steps,
            "share_that_differ": float(np.mean(got != want)),
            "tails_equal": bool(np.array_equal(got_tails, want_tails)),
            "ok": bool(steps <= 1.0
                       and np.array_equal(got_tails, want_tails)),
        }

    report["ok"] = all(k["ok"] for k in report["kernels"].values())
    emit(report)
    return 0 if report["ok"] else 1


def child_reference(args) -> int:
    """The engine against the plain float32 reference, in this process (it
    holds the chip). One JSON line; exit 0 only if the served arm is inside
    its family's limits (the largest |Δlogprob|, for laguna the mean too;
    where the model holds state, the served state; for falcon_h1 the served
    pages), its routing inside its bounds, and the fp8-pool arm — for laguna
    also each reference that is wrong in one way — outside them."""
    import contextlib
    import gc
    import importlib.util

    import jax
    import numpy as np

    sys.path.insert(0, REPO)
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.server import (
        build_parser, engine_config_from_args,
    )
    from vllm_production_stack_tpu.models import llama
    from vllm_production_stack_tpu.testing import reference_check
    from vllm_production_stack_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    with open(os.path.join(REPO, args.config)) as f:
        config = json.load(f)
    # the reference by architecture: the Llama lineage's, the DeepSeek-V3
    # family's (latent attention, sigmoid routing), laguna's (window and
    # full layers, a per-head gate, a share of the experts) or solar_open2's
    # (KDA layers over state slots between gated NoPE GQA layers)
    family = {"deepseek_v3": "mla", "laguna": "laguna",
              "solar_open2": "solar_open2", "sdar_moe": "sdar",
              "ling_flash": "ling", "falcon_h1": "falcon_h1",
              "glm_moe_dsa": "glm_dsa",
              "olmo_hybrid": "olmo_hybrid"}.get(
        config.get("model_type"), "decoder")
    # (the two families whose reference follows the served routing and
    # whose cell is the long-document one)
    mla = family != "decoder"
    # the benchmark's copy of the reference, not the package's: a later PR
    # to the program cannot move the yardstick
    spec = importlib.util.spec_from_file_location(
        "reference_decoder", os.path.join(
            REPO, "benchmarks", "chip", "reference", family + ".py"))
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)
    if family == "sdar":
        return _child_reference_sdar(args, config, reference)
    if family == "glm_dsa":
        return _child_reference_glm_dsa(args, config, reference)
    # (max |Δlogprob|, mean |Δlogprob| or None, routing disagreement share,
    # routing margin where differing): each family's limits, set above
    atol, mean_atol, disagreement_max, margin_max = {
        "decoder": (REFERENCE_ATOL, None, ROUTING_DISAGREEMENT_MAX,
                    ROUTING_MARGIN_MAX),
        "mla": (REFERENCE_ATOL_MLA, None, ROUTING_DISAGREEMENT_MAX,
                ROUTING_MARGIN_MAX_MLA),
        "laguna": (REFERENCE_ATOL_LAGUNA, REFERENCE_MEAN_ATOL_LAGUNA,
                   ROUTING_DISAGREEMENT_MAX_LAGUNA,
                   ROUTING_MARGIN_MAX_LAGUNA),
        "solar_open2": (REFERENCE_ATOL_SOLAR, REFERENCE_MEAN_ATOL_SOLAR,
                        ROUTING_DISAGREEMENT_MAX_SOLAR,
                        ROUTING_MARGIN_MAX_SOLAR),
        "ling": (REFERENCE_ATOL_LING, REFERENCE_MEAN_ATOL_LING,
                 ROUTING_DISAGREEMENT_MAX_LING, ROUTING_MARGIN_MAX_LING),
        "falcon_h1": (REFERENCE_ATOL_FALCON_H1, REFERENCE_MEAN_ATOL_FALCON_H1,
                      None, None),
        "olmo_hybrid": (REFERENCE_ATOL_OLMO_HYBRID,
                        REFERENCE_MEAN_ATOL_OLMO_HYBRID, None, None),
    }[family]
    flags = (config["rehearse"] if args.rehearse else config)["engine_flags"]
    dev = jax.devices()[0]
    report: dict = {"phase": "reference", "config": config["name"],
                    "platform": dev.platform, "kind": dev.device_kind,
                    "seed": args.seed, "atol": atol, "mean_atol": mean_atol,
                    "routing_disagreement_max": disagreement_max,
                    "routing_margin_max": margin_max}
    if dev.platform != "tpu" and not args.rehearse:
        report.update(ok=False, error=f"platform is {dev.platform!r}")
        emit(report)
        return 1
    configure_compile_cache()
    rng = np.random.RandomState(args.seed % (2 ** 31))
    lo, hi, n_out = (16, 64, 8) if args.rehearse else (128, 1024, 32)
    n_prompts = 8
    if mla and not args.rehearse:
        # the long-document cell's range: one prompt at its top, one past
        # 8k that ends mid-chunk, the rest log-uniform over it
        lo, hi, n_prompts = 2048, 16320, 6
    chunk = int(flags[flags.index("--max-num-batched-tokens") + 1])
    lengths = np.exp(
        rng.uniform(np.log(lo), np.log(hi), size=n_prompts)).astype(int)
    lengths[:2] = [hi, (chunk + hi) // 2]  # two prompts cross a chunk edge
    # (the families with layers that hold a recurrent state)
    holds_state = family in (
        "solar_open2", "ling", "falcon_h1", "olmo_hybrid")
    # (the families whose reference reads the served tree itself and takes
    # its fault arms as keywords; every seat in flight; the served pages of
    # the first layer that holds any have a limit of their own)
    own_arms = {"falcon_h1": FALCON_H1_ARMS,
                "olmo_hybrid": OLMO_HYBRID_ARMS}.get(family)
    # (whether an fp8 pool reaches most of the model: K/V or latent pages in
    # more than half of its layers)
    model = engine_config_from_args(build_parser().parse_args(flags)).model
    pages_in_most_layers = 2 * len(model.page_layers) > model.num_layers
    state_rtol = SSD_STATE_RTOL if family == "falcon_h1" else KDA_STATE_RTOL
    pages_rtol = (KV_PAGES_RTOL_OLMO_HYBRID if family == "olmo_hybrid"
                  else KV_PAGES_RTOL)
    if own_arms and not args.rehearse:
        # every seat in flight: fifteen rows of the cell's lengths (and the
        # short one below)
        lengths = np.concatenate([lengths, np.exp(rng.uniform(
            np.log(lo), np.log(hi), size=9)).astype(int)])
    if own_arms:
        # (every row in flight at once, or a page given back is written
        # again before `served_pages` reads it)
        lengths = _fit_pool(
            lengths, n_out, int(flags[flags.index("--num-blocks") + 1])
            * int(flags[flags.index("--block-size") + 1]), lo)
    if holds_state:
        # ... and one SHORT prompt, the one the faulty references are held
        # to: what a slot's previous holder left (a state, a convolution
        # history) is forgotten a few hundred tokens into a row, so only a
        # row's first positions can show that it was not zeroed
        lengths = np.append(lengths, 12 if args.rehearse else 48)
    report["prompt_lengths"] = [int(n) for n in lengths]

    def arm(extra_flags: list[str], faults: bool = False) -> dict:
        eargs = build_parser().parse_args(
            [*flags, *extra_flags, "--seed", str(args.seed % (2 ** 31))])
        ecfg = engine_config_from_args(eargs)
        vocab = ecfg.model.vocab_size
        prompts = [list(map(int, rng.randint(1, vocab, size=n)))
                   for n in lengths]
        t0 = time.monotonic()
        # (MLA: the served programs' own routing is tapped, and the
        # reference follows it — reference_check.compare says why; a model
        # with state slots: what its last such layer's recurrence was fed)
        tap = (reference_check.RoutingTap()
               if mla and not own_arms else None)
        linear = ecfg.model.state_layers
        state_tap = reference_check.StateTap(linear[-1]) if linear else None
        # (ling: and what its last expert layer's ROUTER was fed and chose)
        router_tap = (reference_check.RouterTap(
            ecfg.model.num_moe_layers - 1) if family == "ling" else None)
        with (tap or contextlib.nullcontext(),
              state_tap or contextlib.nullcontext(),
              router_tap or contextlib.nullcontext()):
            engine = LLMEngine(ecfg)
            device = engine.runner.device_report()
            out = {"attention": device.get("attention"),
                   "moe": device.get("moe"),
                   "kv_dtype": str(engine.runner._kv_dtype)}
            served = reference_check.served_logprobs(engine, prompts, n_out)
        if state_tap:
            state_pool = np.asarray(
                engine.runner.kv_caches[state_tap.layer]["s"])
            out["state"] = reference_check.served_state(
                reference, ecfg.model, state_tap, state_pool,
                params=engine.runner.params)
        if router_tap:
            from vllm_production_stack_tpu.models import loader

            served_view = loader.LingCheckpointView(
                ecfg.model, engine.runner.params)
            out["router"] = reference_check.served_router(
                reference, ecfg.model, router_tap, served_view)
        if own_arms:
            first_pages = np.asarray(llama.page_pool(
                engine.runner.kv_caches[ecfg.model.page_layers[0]]))
        out["engine_s"] = round(time.monotonic() - t0, 1)
        print(f"reference check: served in {out['engine_s']} s",
              file=sys.stderr, flush=True)
        # the reference needs the weights as served and room for one
        # layer in float32: let the pool and the programs go
        params = engine.runner.params
        engine.runner.shutdown()
        engine.runner.kv_caches = None
        del engine
        t0 = time.monotonic()
        # a long sequence's routing through the system's own model code
        # goes in the served chunks and through the served attention
        out.update(reference_check.compare(
            reference, ecfg.model, params, prompts, served,
            routing_chunk=chunk if mla else None,
            routing_backend=(
                out["attention"]["prefill"]["resolved"] if mla else "xla"),
            served_routing=tap))
        if own_arms:
            out["pages"] = reference_check.served_pages(
                reference, ecfg.model, params, first_pages, prompts, served)
        out["reference_s"] = round(time.monotonic() - t0, 1)
        print(f"reference check: compared in {out['reference_s']} s",
              file=sys.stderr, flush=True)
        if faults and own_arms:
            # (its faulty references are its own fault arms; the bfloat16
            # state is the state's limit's to tell, on every row)
            short = int(np.argmin(lengths))
            out["faults"] = {}
            for arms in own_arms:
                got = reference_check.compare(
                    reference, ecfg.model, params, [prompts[short]],
                    [served[short]], reference_arms=arms)
                out["faults"]["-".join(
                    f"{k}={v}" for k, v in arms.items())] = {
                    k: got[k] for k in (
                        "max_abs_dlogprob", "mean_abs_dlogprob")}
            got = reference_check.compare(
                reference, ecfg.model, params, [prompts[short]],
                [served[short]], reference_arms={"state_dtype": "bfloat16"})
            out["faults"]["state_bfloat16"] = {
                **{k: got[k] for k in (
                    "max_abs_dlogprob", "mean_abs_dlogprob")},
                "state": reference_check.served_state(
                    reference, ecfg.model, state_tap, state_pool,
                    params=params, state_dtype="bfloat16")}
        elif faults:
            # the shortest prompt's served logprobs against references that
            # are each wrong in ONE way: the tolerance has to catch each
            short = int(np.argmin(lengths))
            out["faults"] = {}
            for name, faulty in _faulty_references(
                    reference, family).items():
                got = reference_check.compare(
                    faulty, ecfg.model, params, [prompts[short]],
                    [served[short]], served_routing=tap)
                out["faults"][name] = {
                    k: got[k] for k in (
                        "max_abs_dlogprob", "mean_abs_dlogprob",
                        # (ling: a router in a lower precision shows in
                        # the routing, which the reference follows)
                        *(("routing_disagreement_share",
                           "routing_margin_max_where_differing")
                          if family == "ling" else ()))}
                if state_tap:
                    out["faults"][name]["state"] = (
                        reference_check.served_state(
                            faulty, ecfg.model, state_tap, state_pool))
                if router_tap:
                    out["faults"][name]["router"] = (
                        reference_check.served_router(
                            faulty, ecfg.model, router_tap, served_view))
        return out

    served = arm([], faults=holds_state or family == "laguna")
    report["served"] = served
    routing_ok = (
        "routing_disagreement_share" not in served
        or (served["routing_disagreement_share"] <= disagreement_max
            and served["routing_margin_max_where_differing"]
            <= margin_max))
    def inside(arm: dict) -> bool:
        """Within the limits: the largest |Δlogprob|, where the family has
        one the mean, where the model holds state the served state, and
        where the arm read them the served pages."""
        return (arm["max_abs_dlogprob"] <= atol
                and (mean_atol is None
                     or arm["mean_abs_dlogprob"] <= mean_atol)
                and (family != "ling" or (
                    arm["routing_disagreement_share"] <= disagreement_max
                    and arm["routing_margin_max_where_differing"]
                    <= margin_max))
                and ("state" not in arm
                     or arm["state"]["state_rel_err"] <= state_rtol)
                and ("pages" not in arm
                     or arm["pages"]["pages_rel_err"] <= pages_rtol)
                and ("router" not in arm
                     or arm["router"]["choice_disagreement_share"]
                     <= ROUTER_CHOICE_DISAGREEMENT_MAX_LING))

    if holds_state:
        report["state_rtol"] = state_rtol
    if own_arms:
        report["pages_rtol"] = pages_rtol
    if family == "ling":
        report["router_choice_disagreement_max"] = (
            ROUTER_CHOICE_DISAGREEMENT_MAX_LING)
    report["ok"] = bool(
        inside(served) and routing_ok
        and not any(inside(f) for f in served.get("faults", {}).values()))
    if args.fp8_arm and (pages_in_most_layers or family == "olmo_hybrid"):
        # (solar_open2, ling: the precision below the configuration's is a
        # bfloat16 STATE, one of its faulty references: most of its cache
        # is state, and an fp8 pool would touch the 2 GQA layers — ling:
        # the one latent layer — only. falcon_h1 has BOTH arms: every layer
        # holds K/V pages beside its state; olmo_hybrid as well: its 3 full
        # layers hold 45 KiB a token, most of the cache's bytes)
        # (a quantized pool resolves 'auto' to XLA attention, whose
        # gathered history and scores a long-document prompt cannot
        # afford: the latent kernels widen fp8 pages themselves)
        kernels = (["--attention-backend", "pallas",
                    "--prefill-attention-backend", "pallas"]
                   if mla and not args.rehearse else [])
        # (the served arm's reading first, should the second engine fail;
        # and its programs and the reference's — an eager op a sequence
        # length — unloaded: the init program of an 8B int8 model needs the
        # chip all but empty, model_runner._init_device_params)
        print("reference check: served arm " + json.dumps(served),
              file=sys.stderr, flush=True)
        gc.collect()
        jax.clear_caches()
        fp8 = report["fp8_pool"] = arm(
            ["--kv-cache-dtype", "fp8", *kernels])
        report["ok"] = report["ok"] and bool(args.rehearse or not inside(fp8))
    emit(report)
    return 0 if report["ok"] else 1


def _child_reference_glm_dsa(args, config: dict, reference) -> int:
    """`child_reference` for a model whose latent layers attend over
    SELECTED keys: prompts of the long-document cell's lengths (every one
    longer than the top-k) in flight together, the engine's logprobs against
    `reference.forward` FOLLOWING the served routing and selection, the
    selection's own two limits, the first layer's index keys, and the served
    numbers held against references that are wrong in one way each
    (GLM_DSA_ARMS, on ONE prompt of 2.5 x the top-k): exit 0 only with the
    served arm inside every limit and each faulty one outside at least one."""
    import jax
    import numpy as np

    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.server import (
        build_parser, engine_config_from_args,
    )
    from vllm_production_stack_tpu.testing import reference_check
    from vllm_production_stack_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    flags = (config["rehearse"] if args.rehearse else config)["engine_flags"]
    dev = jax.devices()[0]
    limits = {
        "atol": REFERENCE_ATOL_GLM_DSA,
        "mean_atol": REFERENCE_MEAN_ATOL_GLM_DSA,
        "routing_disagreement_max": ROUTING_DISAGREEMENT_MAX_GLM_DSA,
        "routing_margin_max": ROUTING_MARGIN_MAX_GLM_DSA,
        "selection_disagreement_max": SELECTION_DISAGREEMENT_MAX_GLM_DSA,
        "selection_margin_spreads_max": SELECTION_MARGIN_SPREADS_MAX_GLM_DSA,
        "index_pages_rtol": INDEX_PAGES_RTOL_GLM_DSA}
    report: dict = {"phase": "reference", "config": config["name"],
                    "platform": dev.platform, "kind": dev.device_kind,
                    "seed": args.seed, **limits}
    if dev.platform != "tpu" and not args.rehearse:
        report.update(ok=False, error=f"platform is {dev.platform!r}")
        emit(report)
        return 1
    configure_compile_cache()
    rng = np.random.RandomState(args.seed % (2 ** 31))
    ecfg = engine_config_from_args(build_parser().parse_args(
        [*flags, "--seed", str(args.seed % (2 ** 31))]))
    model = ecfg.model
    # the long-document cell's range (the tiny preset: its rehearse mix's),
    # every prompt longer than the top-k; one at the top, one past a chunk
    # edge, two log-uniform
    lo, hi, n_out, n_prompts = ((48, 320, 8, 5) if args.rehearse
                                else (2049, 16320, 32, 5))
    assert lo > model.index_topk
    chunk = ecfg.scheduler.max_num_batched_tokens
    lengths = np.exp(
        rng.uniform(np.log(lo), np.log(hi), size=n_prompts)).astype(int)
    # ... and one of 2.5 x the top-k, which the faulty references are held
    # to: three fifths of its queries select among more keys than they keep.
    # NOT the shortest, as the other families' arms: at 2.1-2.4k tokens
    # nearly every key is selected and no indexer fault can show (the first
    # two chip runs of PR 55 read 0.0005-0.019 of slots there)
    held = 2
    lengths[:3] = [hi, (chunk + hi) // 2, max(lo, int(2.5 * model.index_topk))]
    report["prompt_lengths"] = [int(n) for n in lengths]
    prompts = [list(map(int, rng.randint(1, model.vocab_size, size=n)))
               for n in lengths]
    t0 = time.monotonic()
    with (reference_check.RoutingTap() as routing,
          reference_check.SelectionTap() as selection):
        engine = LLMEngine(ecfg)
        device = engine.runner.device_report()
        served_rows = reference_check.served_logprobs(engine, prompts, n_out)
    index_pool = np.asarray(engine.runner.kv_caches[0]["idx"])
    served: dict = {"attention": device.get("attention"),
                    "moe": device.get("moe"), "mixers": device.get("mixers"),
                    "engine_s": round(time.monotonic() - t0, 1)}
    print(f"reference check: served in {served['engine_s']} s",
          file=sys.stderr, flush=True)
    # the reference needs the weights as served and room for one layer in
    # float32: let the pool and the programs go
    params = engine.runner.params
    engine.runner.shutdown()
    engine.runner.kv_caches = None
    del engine
    t0 = time.monotonic()

    def compare(rows_p, rows_s, arms=None):
        return reference_check.compare(
            reference, model, params, rows_p, rows_s, served_routing=routing,
            served_selection=selection, reference_arms=arms)

    served.update(compare(prompts, served_rows))
    served["index_pages"] = reference_check.served_index_pages(
        reference, model, params, index_pool, prompts, served_rows)
    served["reference_s"] = round(time.monotonic() - t0, 1)
    print(f"reference check: compared in {served['reference_s']} s",
          file=sys.stderr, flush=True)
    report["served"] = served

    def outside(arm: dict) -> list[str]:
        """The limits an arm's readings lie outside."""
        held = {
            "max_abs_dlogprob": limits["atol"],
            "mean_abs_dlogprob": limits["mean_atol"],
            "routing_disagreement_share": limits["routing_disagreement_max"],
            "routing_margin_max_where_differing": limits[
                "routing_margin_max"],
            "selection_disagreement_share": limits[
                "selection_disagreement_max"],
            "selection_margin_max_in_spreads": limits[
                "selection_margin_spreads_max"]}
        out = [k for k, lim in held.items() if arm[k] > lim]
        if "index_pages" in arm and (arm["index_pages"]["index_pages_rel_err"]
                                     > limits["index_pages_rtol"]):
            out.append("index_pages_rel_err")
        return out

    t0 = time.monotonic()
    report["faults_on_prompt"] = int(lengths[held])
    report["faults"] = {}
    for arms in GLM_DSA_ARMS:
        arms = dict(arms)
        if "topk_share" in arms:  # (top-1,024 at the published top-2,048)
            arms = {"topk": int(model.index_topk * arms["topk_share"])}
        got = compare([prompts[held]], [served_rows[held]], arms)
        keep = {k: got[k] for k in (
            "max_abs_dlogprob", "mean_abs_dlogprob",
            "routing_disagreement_share",
            "routing_margin_max_where_differing",
            "selection_disagreement_share", "selection_margin_max_in_spreads",
            "selection_first_layer_disagreement_share")}
        if "key_dtype" in arms:
            # (a cache of a lower precision: the served pages against the
            # keys such a cache would hold)
            keep["index_pages"] = reference_check.served_index_pages(
                reference, model, params, index_pool, [prompts[held]],
                [served_rows[held]], key_dtype=arms["key_dtype"])
        keep["outside"] = outside(keep)
        report["faults"]["-".join(f"{k}={v}" for k, v in arms.items())] = keep
    report["faults_s"] = round(time.monotonic() - t0, 1)
    report["served"]["outside"] = outside(served)
    # on the CPU the limits are the chip's bf16 ones: float32 lies inside
    # them by orders, and so may a fault (`borrow_lag` at tiny size)
    report["ok"] = bool(not report["served"]["outside"] and all(
        f["outside"] for f in report["faults"].values()))
    emit(report)
    return 0 if report["ok"] else 1


def child_batch_probe(args) -> int:
    """Batch invariance with a prompt LONGER than the top-k of a model that
    selects its keys (`--config`; benchmarks/chip/run.py's own probe is 600
    tokens: under GLM-5.2's top-2,048 every key is selected there, so a
    selection that moves with the batch would pass it). The configuration's
    engine as ONE OS process, prefix caching OFF so that the second pass
    computes every chunk again: a prompt of twice the top-k alone, then with
    fifteen long requests holding the other seats; the first token's
    top-8 logprobs are held as the four-chip comparison's are. This process
    imports no JAX."""
    with open(os.path.join(REPO, args.config)) as f:
        config = json.load(f)
    at = config["rehearse"] if args.rehearse else config
    # (tokens = characters under the presets' byte tokenizer; the tiny
    # preset's top-k is 16 and its contexts end at 512)
    n_probe, hold, step, n_out = ((128, 160, 8, 200) if args.rehearse
                                  else (4096, 6000, 400, 1024))
    port = free_port()
    url = f"http://127.0.0.1:{port}"
    fails = Failures()
    report: dict = {"phase": "batch_probe", "config": config["name"],
                    "seed": args.seed, "probe_tokens": n_probe,
                    "logprob_atol": LOGPROB_ATOL,
                    "min_shared_top8": MIN_SHARED_TOP8}
    engine = Child("engine_batch_probe", [
        "-m", "vllm_production_stack_tpu.engine.server", "--port", str(port),
        "--host", "127.0.0.1", *at["engine_flags"], "--seed",
        str(args.seed % (2 ** 31)), "--no-enable-prefix-caching"])
    try:
        engine.wait_healthy(url, 1500)
        report["platform"] = http_json(
            "GET", url + "/debug/programs")["device"]["platform"]
        probe = filler(n_probe, salt=7)

        def first_top8() -> dict:
            out = completion(url, at["model"], probe, 1, logprobs=8)
            return out["choices"][0]["logprobs"]["top_logprobs"][0]

        alone = first_top8()
        holders = [threading.Thread(
            target=completion, daemon=True, args=(
                url, at["model"], filler(hold + step * i, salt=100 + i),
                n_out)) for i in range(15)]
        for t in holders:
            t.start()

        def in_flight() -> float:
            seen = scrape(url)
            return sum(metric_sum(seen, "tpu:num_requests_" + state)
                       for state in ("running", "waiting"))

        # the probe arrives behind all fifteen and is prefilled and decoded
        # while they hold their seats (their answers outlast its prompt)
        deadline = time.monotonic() + 300
        while in_flight() < 15:
            if time.monotonic() > deadline:
                raise TimeoutError("fifteen requests never were in flight")
            time.sleep(0.02)
        crowded = first_top8()
        report["in_flight_after_probe"] = in_flight()
        for t in holders:
            t.join(timeout=600)
        common = set(alone) & set(crowded)
        worst = max((abs(alone[t] - crowded[t]) for t in common),
                    default=float("inf"))
        report.update(shared_top8=len(common), max_abs_diff=worst)
        fails.check(report["in_flight_after_probe"] >= 1,
                    "the other seats had emptied before the probe returned")
        fails.check(len(common) >= MIN_SHARED_TOP8 and worst <= LOGPROB_ATOL,
                    f"the first token moves with the batch: {len(common)}/8 "
                    f"shared, max |dlogprob| {worst}")
        fails.check(report["platform"] == "tpu" or args.rehearse,
                    f"platform is {report['platform']!r}")
    except Exception as e:
        fails.add(f"{type(e).__name__}: {e}"[:1500])
    finally:
        engine.stop(fails)
    report.update(ok=not fails.items, failures=fails.items)
    emit(report)
    return 0 if report["ok"] else 1


def _child_reference_sdar(args, config: dict, reference) -> int:
    """`child_reference` for a model that generates by diffusion over blocks:
    16 rows in flight, prompts of the cell's lengths, the engine's own
    tokens, order of unmasking and logprobs against `reference.forward`
    over the same block states (a row's first and last block: the prompt's
    tail beside masks, and the deepest context), then the same served
    numbers against references and procedures that are wrong in one way
    each. Exit 0 only if the served arm is inside both limits and every
    faulty one outside."""
    import jax
    import numpy as np

    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.server import (
        build_parser, engine_config_from_args,
    )
    from vllm_production_stack_tpu.testing import reference_check
    from vllm_production_stack_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    flags = (config["rehearse"] if args.rehearse else config)["engine_flags"]
    dev = jax.devices()[0]
    # (the rehearsal serves float32: what differs there is the order of
    # sums, and a bfloat16 statistic is told from it only by that limit)
    atol, mean_atol = (1e-4, 1e-5) if args.rehearse else (
        REFERENCE_ATOL_SDAR, REFERENCE_MEAN_ATOL_SDAR)
    report: dict = {"phase": "reference", "config": config["name"],
                    "platform": dev.platform, "kind": dev.device_kind,
                    "seed": args.seed, "atol": atol, "mean_atol": mean_atol}
    if dev.platform != "tpu" and not args.rehearse:
        report.update(ok=False, error=f"platform is {dev.platform!r}")
        emit(report)
        return 1
    configure_compile_cache()
    rng = np.random.RandomState(args.seed % (2 ** 31))
    lo, hi, n_out, pad = (
        (16, 64, 11, 32) if args.rehearse else (128, 1024, 18, 128))
    lengths = np.exp(rng.uniform(np.log(lo), np.log(hi), size=16)).astype(int)
    lengths[:2] = [hi, hi - 3]  # the longest, and one with a tail of 1
    report["prompt_lengths"] = [int(n) for n in lengths]
    eargs = build_parser().parse_args(
        [*flags, "--seed", str(args.seed % (2 ** 31))])
    ecfg = engine_config_from_args(eargs)
    model = ecfg.model
    # (never the mask token's id: the reference tells a mask by it)
    prompts = [list(map(int, rng.randint(
        1, min(model.vocab_size, model.block_diffusion.mask_token_id), n)))
        for n in lengths]
    t0 = time.monotonic()
    with (reference_check.BlockTap() as tap,
          reference_check.RoutingTap() as routing):
        engine = LLMEngine(ecfg)
        device = engine.runner.device_report()
        served_rows = reference_check.served_block_logprobs(
            engine, prompts, n_out)
        timing = engine.flightrec.timing()
    served = {"attention": device.get("attention"), "moe": device.get("moe"),
              "generation": device.get("generation"),
              "engine_s": round(time.monotonic() - t0, 1),
              **{k: v for k, v in timing.items() if k.startswith("bd_")}}
    # (why the passes touch the share of the experts they do: PERF.md §5)
    served["touched_split"] = tap.touched_split(
        model.block_diffusion.mask_token_id, model.num_experts)
    params = engine.runner.params
    engine.runner.shutdown()
    engine.runner.kv_caches = None
    del engine

    def compare(rows, **kw):
        return reference_check.compare_blocks(
            reference, model, params, [prompts[i] for i in rows],
            [served_rows[i] for i in rows], tap, routing, blocks="ends",
            query_block=64, pad_to=pad, **kw)

    t0 = time.monotonic()
    wide = range(0, 16, 2)
    served.update(compare(wide))
    served["reference_s"] = round(time.monotonic() - t0, 1)
    report["served"] = served

    def inside(arm: dict) -> bool:
        return (arm["max_abs_dlogprob"] <= atol
                and arm["mean_abs_dlogprob"] <= mean_atol)

    # the two shortest rows against what is wrong in ONE way each: a cache
    # that keeps a block's last denoising pass (no commit pass), Qwen3's own
    # causal mask, logits read one position to the left, no q/k norm. The
    # nearest precision below the float32 the configuration states for the
    # router, the softmax and the confidence shifts the mean by the least of
    # them and is told by the mean alone, so it is read over the served
    # arm's own rows: a mean over as many positions as the one it is told
    # from.
    keys = reference.published_keys(model)
    short = [int(i) for i in np.argsort(lengths)[:2]]
    t0 = time.monotonic()
    report["faults"] = {
        "no_commit_pass": compare(short, commit_pass=False),
        "shifted_logits": compare(short, shift=True),
        **{name: compare(short, ref_cfg={**keys, **wrong})
           for name, wrong in (
               ("causal_mask", {"causal_mask": True}),
               ("no_qk_norm", {"qk_norm": False}))},
        "bf16_statistics": compare(
            wide, ref_cfg={**keys, "float32_statistics": False})}
    report["faults_on_rows"] = {"bf16_statistics": list(wide), "others": short}
    report["served_on_those_rows"] = compare(short)
    report["faults_s"] = round(time.monotonic() - t0, 1)
    report["ok"] = bool(
        inside(served) and inside(report["served_on_those_rows"])
        and not any(inside(f) for f in report["faults"].values()))
    emit(report)
    return 0 if report["ok"] else 1


def _fit_pool(lengths, n_out: int, pool_tokens: int, lo: int):
    """The rows' lengths, the longest of the drawn ones (not the two that
    cross a chunk edge) halved until all rows, their answers and a page of
    slack each fit a pool of `pool_tokens` at once."""
    lengths = lengths.copy()
    while int(lengths.sum()) + len(lengths) * (n_out + 64) > pool_tokens:
        longest = 2 + int(lengths[2:].argmax())
        if lengths[longest] // 2 < lo:
            break
        lengths[longest] //= 2
    return lengths


def _faulty_references(reference, family: str = "laguna") -> dict:
    """reference/laguna.py or reference/solar_open2.py, wrong in one way
    each: what the comparison's tolerance has to tell from the served
    program."""
    import types

    def variant(edit):
        def published_keys(model_cfg):
            cfg = reference.published_keys(model_cfg)
            edit(cfg)
            return cfg

        return types.SimpleNamespace(
            forward=reference.forward, published_keys=published_keys,
            **{name: getattr(reference, name, None) for name in (
                "delta_rule", "route", "check_config", "f32")})

    if family == "ling":
        # the per-head gate of the latent layer left out, the group limit
        # left out of the routing weights' choice, the unbounded decay
        # gate, beta with a factor 2, a slot's state or convolution tail
        # trusted from the row before, a state kept in bfloat16 and a
        # router in bfloat16 — the nearest precisions below the float32
        # the configuration states for both
        edits = {
            "mla_gate_left_out": {
                "gated_attention_proj_granularity_type": None},
            "unbounded_gate": {"kda_safe_gate": False},
            "beta_with_factor_2": {"kda_beta_scale": 2.0},
            "state_not_zeroed": {"kda_state_inherited": True},
            "conv_across_a_seam": {"kda_conv_inherited": True},
            "bf16_state": {"kda_state_dtype": "bfloat16"},
            "bf16_router": {"router_dtype": "bfloat16"}}
        return {name: variant(lambda cfg, e=e: cfg.update(e))
                for name, e in edits.items()}
    if family == "solar_open2":
        # a gate left out, beta without its factor 2, a slot's state or
        # convolution tail trusted from the row before (here: an identical
        # one) instead of zeroed, a state kept in bfloat16 — the nearest
        # precision below the float32 the configuration states
        edits = {"gate_left_out": {"use_gqa_gate": False},
                 "beta_without_factor_2": {"kda_allow_neg_eigval": False},
                 "state_not_zeroed": {"kda_state_inherited": True},
                 "conv_across_a_seam": {"kda_conv_inherited": True},
                 "bf16_state": {"kda_state_dtype": "bfloat16"}}
        return {name: variant(lambda cfg, e=e: cfg.update(e))
                for name, e in edits.items()}

    def window_a_page_longer(cfg):
        cfg["sliding_window"] += BLOCK_SIZE

    def no_yarn(cfg):
        cfg["rope_parameters"] = {
            kind: {"rope_theta": rp["rope_theta"], "rope_type": "default",
                   "partial_rotary_factor": rp["partial_rotary_factor"]}
            for kind, rp in cfg["rope_parameters"].items()}

    def no_gate(cfg):
        cfg["gating"] = None

    return {"window_a_page_longer": variant(window_a_page_longer),
            "yarn_left_out": variant(no_yarn),
            "gate_left_out": variant(no_gate)}


# -- entry -------------------------------------------------------------------

def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, default=1, choices=[1, 4],
                   help="4: run only the tp=1 vs tp=4 comparison")
    p.add_argument("--model", default="qwen3-8b",
                   help="registry preset (tests pass tiny-llama)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--child", default=None,
                   choices=["kernels", "reference", "batch_probe"],
                   help=argparse.SUPPRESS)
    p.add_argument("--config", default=REFERENCE_CONFIG,
                   help="--child reference, batch_probe: the benchmark "
                        "configuration")
    p.add_argument("--rehearse", action="store_true",
                   help="--child reference, batch_probe: tiny size, any "
                        "platform")
    p.add_argument("--fp8-arm", type=int, default=1, choices=[0, 1],
                   help="--child reference: 0 skips the fp8-pool arm (more "
                        "seeds of the served arm alone)")
    args = p.parse_args()
    if args.child == "kernels":
        return child_kernels(args.seed)
    if args.child == "reference":
        return child_reference(args)
    if args.child == "batch_probe":
        return child_batch_probe(args)

    fails = Failures()
    device = None
    try:
        if args.chips == 4:
            device = phase_four_chips(args, fails)
        else:
            phase_kernels(args, fails)
            device = phase_serve(args, fails)
    except Exception as e:  # recorded, reported, and the exit code says so
        fails.add(f"{type(e).__name__}: {e}"[:1500])
        emit({"phase": "smoke", "ok": False,
              "trace": traceback.format_exc()[-1500:]})
    summary = device_summary(device)
    if summary is None or summary["platform"] != "tpu":
        fails.add(f"no TPU reported by the engine: {summary}")
    if fails.items:
        emit({"phase": "failures", "failures": fails.items,
              "elapsed_s": round(time.monotonic() - _T0, 1)})
    else:
        emit({"phase": "done", "elapsed_s": round(time.monotonic() - _T0, 1)})
    emit({"ok": not fails.items, "device": summary})
    return 0 if not fails.items else 1


if __name__ == "__main__":
    sys.exit(main())
