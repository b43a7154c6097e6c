#!/usr/bin/env python3
"""A whole model through the paged serving path against the plain float32
reference, at published width on this device: LOGITS, not tokens.

    python tools/model_parity.py --model olmoe-1b-7b-int8         # the chip
    python tools/model_parity.py --model olmoe-1b-7b-int8 --kv-dtype int8
    python tools/model_parity.py --model olmoe-1b-7b-int8 --bf16-router
    JAX_PLATFORMS=cpu python tools/model_parity.py --model olmoe-test-int8 \\
        --prompt 48 --chunk 16 --steps 8 --page 8     # debug the tool itself

Seeded weights (quantized as the model's name says), ``--batch`` seeded
prompts of ``--prompt`` tokens prefilled in ``--chunk``-token chunks
straight into a page pool (the program's paged forward seam: the prefill
kernel on a TPU), then ``--steps`` greedy decode steps through the pages
(the decode kernel).  The reference is the benchmark's
(``benchmark/reference.py`` + ``benchmark/families/<family>.py``: float32
under ``jax.default_matmul_precision("highest")``, the whole sequence at
once, no cache, no kernel, the experts as their definition), run on the
same parameters over prompt + the tokens the served path chose.  Compared:
the log-softmax over the WHOLE vocabulary at the prompt's last position
and at every decode step.

With random weights the largest logit changes on rounding, so sampled
tokens say little; the log-probabilities say how far the arithmetic is.
A position's number is the largest |served - reference| over the
vocabulary.  The run passes if the MEAN of that over the positions is <=
``TOL_MEAN`` (the statistic that is steady from seed to seed) and the
largest over the positions is <= ``TOL_MAX`` (a gross fault at one
position).  ``mean_abs`` (over positions and vocabulary) and the error at
the served path's own token are printed beside them.

Readings behind the limits (``READINGS``: olmoe-1b-7b-int8, 4 x 512 + 32,
seeds 0, 1, 2, TPU v5e; my chip runs, PR 28).  The served configuration
reads 0.0554-0.0580; int8 KV pages 0.0737-0.0739, which ``TOL_MEAN``
refuses with room on both sides.  A router whose matmul runs on the bf16
rows at the default precision reads 0.0578-0.0608: 2-5 % above the sound
reading OF THE SAME SEED every time, and inside the sound readings' own
spread from seed to seed, so no fixed limit refuses it and this tool does
not claim to.  Why: on seeded weights the router's probabilities are near
1/64 each, a third of the (position, layer) pairs have a reference margin
p8 - p9 under 1e-3 (``router_margin_under_1e-3``: 685-728 of 2,112), the
served path's bf16 activations flip those whatever the router's own
precision, and a flip between two experts of near-equal weight moves a
log-probability by less than the bf16 activations already do.

A latent-attention model (family ``deepseek_v3``: ``--model
kanana-2-30b-a3b-bf16``, a benchmark configuration's name) is read twice,
short (``--batch 2 --prompt 512 --steps 16``) and long (``--batch 1
--prompt 8192 --steps 16``: chunked prefill, then decode over 65 pages a
row; the reference's attention runs in query blocks of 512), and held
against its own lower precision, ``--bf16-softmax-state`` (the kernels'
online-softmax state between fold iterations in bf16; an iteration takes
a group of four pages since PR 55, and the control then reads 0.094-0.105
where it read 0.140-0.151, on both sides of the limit):
``READINGS_LATENT``.  There
``--bf16-router`` reads what the served path reads to the last digit: the
rows and the router's matrix are bf16 as stored, so the float32 matmul
and the bf16 one differ only in a rounding of the result that the
compiler elides before the widening.

A period model (family ``laguna``: ``--model laguna-s-2.1-bf16-ep4``) is
read the same two ways, through one pool and one table a kind of block;
the window kind's pages are a ring (``period_tables``), so the long
reading runs sixteen windows deep over pages that came back from behind
the window.  It is held against two controls that must be refused:
``--bf16-softmax-state`` (the paged kernels' state rounded to bf16
between pages) and ``--window-ignored`` (its window blocks served as full
ones): ``READINGS_PERIOD``.

A model with a summarised cache (family ``evabyte``: ``--model
evabyte-6.5b-bf16``) is read short (``--batch 2 --prompt 512 --steps
16``: inside the first window, a plain causal decoder) and long
(``--batch 1 --prompt 6136 --steps 16``: 24 chunks, two windows close in
prefill, the third at decode step 8, then 8 steps over 384 summaries;
the last chunk is padded to ``--chunk`` as the engine's slab pads it).
The tables are a row's leases, summary pages then window pages, and the
program builds the attended table from them (``ops.eva_attention``).  Two
controls must be refused there: ``--bf16-softmax-state`` and
``--summaries-withheld`` (the REFERENCE told that no query sees a
summary: if the served path's summaries moved nothing, the long reading
would prove nothing about them): ``READINGS_EVA``.

A model with a recurrent state (family ``solar_open2``: ``--model
solar-open2-250b-bf16-ep8``) is read short (``--batch 2 --prompt 512
--steps 16``) and long (``--batch 1 --prompt 1000 --steps 16``: four
prefill chunks, the last partial and padded as the engine's slab pads it,
the state and the convolution's tail carried from each to the next, then
16 steps of the recurrence), and at a chunk's EDGE (``--batch 1 --prompt
770 --steps 16``: the prompt ends two tokens into its fourth chunk, so
the positions read stand on the tail the third one left).  A row's table
is its pages and, last, its row of the state pool.  Three controls must
be refused: ``--state-not-carried`` (every chunk starts from a zero
state; on the long reading or the edge one), ``--conv-tail-dropped``
(every chunk's convolution starts from zeros; on the EDGE reading: on the
long one it reads 1.2-1.4 x its seed's sound reading, inside what the
seeds leave) and ``--bf16-state`` (the state rounded to bfloat16 after
every op that writes it; on any reading): ``READINGS_STATE``.  The first
two are refused by the log-probabilities; **the last is not**: on seeded
bf16 weights a state rounded to bfloat16 reads what the float32 one reads
(every activation around it is bfloat16 already).  What refuses it is the state's own
numbers: a float32 state lies ~1.6e-3 of its norm from its rounding to
bfloat16 and a rounded one 0 (``state_f32_residue``, held to the
family's ``STATE_F32_RESIDUE_MIN``), here over the rows the served path
left, and in every benchmark run of the cell over the sample of the
SERVED state that the reply with log-probabilities carries
(``benchmark/families/solar_open2.py``, its ``replay``).  ``--state-ops``
reads the two ops alone at the model's widths against the recurrence
token by token (``state_ops_reading``): the served float32 state 7e-5-9e-5,
``--bf16-state`` 4e-3 (outputs) and 1e-2 (state).

The second state kind (family ``granite_moe_hybrid``: ``--model
granite-4.0-h-small-bf16-ep2``; Mamba-2 blocks, ``ops.ssd``) is read the
same ways (long, and the edge reading for the tail's control) and against
the same three controls, which reach ``ops.ssd``'s two ops and the one
convolution both kinds share, plus ``--ssd-skip-dropped`` (``D`` = 0 in
the served parameters alone): ``READINGS_SSD``.  Its readings are small
(the tied head's / 16 leaves logits of std ~0.08), so its limits are its
own (``FAMILY_TOL``), and every reading also prints the served state
against the reference's after the same ids (``state_rel_err``, the
family's ``STATE_REL_TOL``, for solar's kind too).  ``--state-ops`` holds
the chunk form's OUTPUTS to ``STATE_OPS_OUT_TOL_SSD`` (bfloat16 operands
on the matrix unit) and the state to the limit above.

A model of several residual streams (family ``xing4_0``: ``--model
xing4.0-29b-a4b-bf16``) is read short (``--batch 2 --prompt 512 --steps
16``) and long (``--batch 1 --prompt 8192 --steps 16``: 32 chunks through
the stream's two kernels at 256 rows, positions past YaRN's original
4,096 and a second group of pages, then 16 steps at one row) against two
controls that must be refused: ``--hc-sinkhorn-iters 1`` (the served maps
after ONE Sinkhorn step) and ``--bf16-coef-maps`` (the coefficient maps
computed in bfloat16): ``READINGS_STREAMS``.  The first is refused by the
log-probabilities (1.3-1.55 x its seed's sound reading); **the second is
not** (1.06 x: the maps' rounding is small beside bf16 activations), and
neither would be by a margin worth the name without the maps' own number:
``hc_sinkhorn_residual``, the largest ``|row or column sum - 1|`` of the
served first block's map over 128 prompt tokens (the function behind the
engine's ``/stats.hc.sinkhorn_residual_max``, which every benchmark run of
the cell holds to the same limit through the family's ``replay``), 1.2e-6
sound, 4.9e-3 with bfloat16 maps, 0.13-0.17 after one step, held to
``HC_RESIDUAL_MAX``.

A model with a block-sparse kind (family ``minicpm_sala``: ``--model
minicpm-sala-9b-bf16``) is read long (``--batch 1 --prompt 24600 --steps
16``: every query read is past ``dense_len`` three times over and keeps 97
of 385 blocks) against three controls that must exit 1, each PLANTED IN THE
SERVED PROGRAM by swapping a function its dispatch calls
(``selection_control``): ``--selection forced-only``, ``one-head`` and
``edge-dropped``.  Two numbers hold them: the log-probabilities
(``FAMILY_TOL``, between the sound readings and the forced blocks alone),
and ``kept_differ_max``, the blocks of a query's kept set that differ
between what the served program's selection handed its fold (``keep_tap``:
the scores' Pallas call on bfloat16 q, the bisection, for the prompt's last
queries in a tile of 32 and the last decode steps' in a tile of one; read
in a second pass of the served path, since the tap's host callback changes
what the compiler fuses around it and the log-probabilities are the
untapped program's) and the family's ``kept_blocks`` in float32:
``READINGS_SPARSE``.

One ``MODEL_PARITY {json}`` line, exit code 1 if a limit is passed.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "benchmark"))
sys.path.insert(0, str(ROOT / "tools"))

READINGS = {   # max_over_vocab_mean, max_over_vocab_max, mean_abs a seed
    "served (bf16 pages, float32 router)": [
        (0.0566, 0.089, None), (0.0554, 0.097, 0.01009),
        (0.0580, 0.108, 0.01057)],
    "--kv-dtype int8": [
        (0.0739, 0.129, None), (0.0739, 0.122, 0.01332),
        (0.0737, 0.126, 0.01334)],
    "--bf16-router": [
        (0.0578, 0.099, None), (0.0579, 0.101, 0.01055),
        (0.0608, 0.112, 0.01098)],
}
TOL_MEAN = 0.066    # between 0.0580 (sound) and 0.0737 (int8 pages)
TOL_MAX = 0.25      # 2.3 x the largest sound maximum: a gross fault

READINGS_LATENT = {  # kanana-2-30b-a3b-bf16, TPU v5e; my chip runs, PR 44
    "served, 2 x 512 + 16, seeds 0, 1": [
        (0.0684, 0.090, 0.01209), (0.0650, 0.083, 0.01116)],
    "served, 1 x 8192 + 16, seeds 0, 1, 2": [
        (0.0727, 0.091, 0.01270), (0.0694, 0.080, 0.01234),
        (0.0763, 0.105, 0.01351)],
    "--bf16-softmax-state, 1 x 8192 + 16, seeds 0, 1, 2": [
        (0.1473, 0.176, 0.02590), (0.1403, 0.166, 0.02495),
        (0.1509, 0.185, 0.02617)],
    # since PR 55 a fold iteration takes a group of four pages (my chip
    # runs, PR 55: the parent read 0.0684, 0.0650 and 0.0727, 0.0694,
    # 0.0763 in the same call).  The state is rounded a quarter as often,
    # so its bf16 control stands 1.30-1.38 x above the sound reading of its
    # seed, as visible as before, and only seed 2 is still over the 0.10
    # that PR 44 set between 0.076 and 0.140; the limit was left there
    "served, 2 x 512 + 16, seeds 0, 1 (PR 55)": [
        (0.0697, 0.083, 0.01215), (0.0647, 0.096, 0.01129)],
    "served, 1 x 8192 + 16, seeds 0, 1, 2 (PR 55)": [
        (0.0732, 0.089, 0.01271), (0.0721, 0.086, 0.01254),
        (0.0764, 0.119, 0.01326)],
    "--bf16-softmax-state, 1 x 8192 + 16, seeds 0, 1, 2 (PR 55)": [
        (0.0983, 0.137, 0.01740), (0.0941, 0.110, 0.01638),
        (0.1052, 0.146, 0.01814)],
    # an earlier tree (routed down projections seeded at twice the scale),
    # seed 0: four pages cannot show the state's precision, and the bf16
    # router reads what the served path reads, digit for digit
    "served / --bf16-softmax-state / --bf16-router, 2 x 512 + 16": [
        (0.0815, 0.173, 0.01424), (0.0889, 0.195, 0.01543),
        (0.0815, 0.173, 0.01424)],
}
# (max_over_vocab_mean, max_over_vocab_max, mean_abs); my chip runs, PR 46,
# TPU v5 lite, laguna-s-2.1-bf16-ep4 at published widths (1 leading block
# + one period, 64 of 256 experts held), seeds 1 and 2
READINGS_PERIOD = {
    "served, 2 x 512 + 16": [(0.1023, 0.134, 0.0177), (0.1026, 0.138, 0.0178)],
    "served, 1 x 8192 + 16": [(0.0906, 0.106, 0.0162), (0.0936, 0.109, 0.0167)],
    "--bf16-softmax-state, 1 x 8192 + 16, seed 1": [(0.1628, 0.231, 0.0288)],
    "--window-ignored, 1 x 8192 + 16, seed 1": [(0.7447, 0.858, 0.1314)],
}
# limits a family's own readings set: (mean, max).  deepseek_v3: the mean
# between 0.0763 (sound, long) and 0.1403 (bf16 state, long), 1.3-1.4 x
# room on both sides; the maximum is every family's, 2.4 x the largest
# sound one here: a gross fault (the lower precision is refused by the
# mean, not by each limit: its maxima read 0.166-0.185)
# laguna: the mean between 0.1026 (the largest sound reading, short) and
# 0.1628 (bf16 state, long), 1.25 x room on both sides; a window ignored
# reads 0.74 (refused by both limits)
# (max_over_vocab_mean, max_over_vocab_max, mean_abs); my chip runs, PR 53,
# TPU v5 lite, evabyte-6.5b-bf16 at published widths (16 of 32 layers)
READINGS_EVA = {
    "served, 2 x 512 + 16, seeds 0, 1, 3 (the mean alone)": [
        (0.0322, 0.0438, 0.0081), (0.0312, 0.0428, 0.0078),
        (0.0293, None, None)],
    "served, 1 x 6136 + 16, seeds 0, 1, 2, 3 (the mean alone)": [
        (0.0258, 0.0325, 0.0068), (0.0269, 0.0341, 0.0068),
        (0.0251, 0.0329, 0.0067), (0.0280, None, None)],
    "--bf16-softmax-state, 1 x 6136 + 16, seeds 0, 1, 3 (the mean alone)": [
        (0.0378, 0.0465, 0.0094), (0.0358, 0.0454, 0.0092),
        (0.0351, None, None)],
    "--summaries-withheld, 1 x 6136 + 16, seeds 0, 3 (the mean alone)": [
        (4.3909, 6.7581, 0.9089), (3.1623, None, None)],
}
# evabyte, a reading PAST the first window (the long one, which alone
# sees a summary and enough pages for the state's precision to show): the
# mean between 0.0280 (the largest sound reading of four seeds) and
# 0.0351 (the smallest of three with the state in bf16), 10-13 % of room
# on both sides; the
# state's precision is refused by the mean, not by each limit (its
# maxima read 0.045-0.047 beside a sound 0.044 in the short reading);
# summaries withheld read 3.2-4.4 (refused by both).  A reading inside the
# first window (the short one: a plain causal decoder over four pages,
# which cannot show the state's precision, as READINGS_LATENT's last row
# found) reads 0.031-0.032 and is held to 0.04.  The maximum: 2.3 x the
# largest sound one, a gross fault, as for every family
EVA_LONG_TOL = (0.031, 0.10)
# solar_open2 (READINGS_STATE below): the mean between 0.0974, the largest
# of 17 sound readings over three shapes (they average 0.0918 and stand
# 0.0034 apart), and 0.258, a state not carried on the long reading, the
# nearest control that must be refused: 1.23 x and 2.15 x of room.  The
# convolution's tail dropped is refused on the EDGE reading (1 x 770 + 16: the
# prompt ends two tokens past a chunk's edge, so the positions read stand on
# the tail): 1.63-1.73 against a sound 0.086-0.097.  On the long reading the
# same fault reads 0.108-0.124, 1.21-1.37 x its seed's sound reading but
# inside what the seeds' own levels leave (the first session's limit, 0.105,
# stood 2.5 % under its smallest seed and 8 % over the largest sound one):
# it is listed and not relied on.  A bfloat16 state is refused by no
# log-probability: ``state_f32_residue`` holds it (the family's limit)
# xing4_0 (READINGS_STREAMS below): the mean between 0.0805, the largest
# of six sound readings, and 0.1027, the smaller of two readings after
# ONE Sinkhorn step: 1.14 x and 1.12 x of room.  bfloat16 coefficient maps
# are refused by no log-probability: ``hc_sinkhorn_residual`` holds them
# granite_moe_hybrid (READINGS_SSD below): the tied head's / 16 over seeded
# embeddings leaves logits of std ~0.08, so every reading is small: the mean
# between 0.00505, the largest of five sound readings (0.00467-0.00505 over
# two shapes), and 0.0577, a state not carried, the nearest control: 3.0 x
# and 3.8 x of room; the maximum between 0.0065 and 0.064.  A bfloat16 state
# is refused by no log-probability (0.00468): the state's two numbers hold it
# nemotron_h (READINGS_ONE_SUBLAYER below): an untied head of unit variance,
# so the readings are kanana's size; the mean between 0.0550, the largest of
# five sound readings (0.0525-0.0550), and 0.0868, the smaller of two
# readings with the LAST block's 64 held experts writing nothing (the one
# control that lies behind the last state plane AND under the in-run check's
# sixteen tokens: PERF.md section 7): 1.27 x and 1.24 x of room.  A bfloat16
# state is refused by no log-probability: ``state_f32_residue`` holds it
# minicpm_sala (READINGS_SPARSE below): the head's / 16 leaves small
# logits, as granite's; the mean between 0.00439, the larger of two sound
# readings (0.00436, 0.00439; behind the tap's other compilation 0.0051),
# and 0.0165, the selection cut to its forced blocks (planted in the served
# program): 1.8 x and 2.1 x of room; the maximum between 0.0050 (0.0061)
# and 0.0159 (one head's scores), 2.2 x and 1.4 x.  A kernel's keys dropped
# at a chunk's edge reads 0.0056 / 0.0081 and is refused by the kept sets
# (``KEPT_DIFFER_MAX``), not here
FAMILY_TOL = {"deepseek_v3": (0.10, TOL_MAX), "laguna": (0.13, TOL_MAX),
              "evabyte": (0.04, 0.10), "solar_open2": (0.12, TOL_MAX),
              "xing4_0": (0.092, TOL_MAX),
              "granite_moe_hybrid": (0.015, 0.03),
              "nemotron_h": (0.070, TOL_MAX),
              "minicpm_sala": (0.008, 0.011)}
# (max_over_vocab_mean, max_over_vocab_max, own_token_mean, own_token_max,
# state_rel_err, state_f32_residue); my chip runs, PR 66, TPU v5 lite,
# nemotron-3-nano-30b-a3b-bf16-ep2 at published widths (nine blocks, 64 of
# 128 experts, half the vocabulary), the routed experts' down projections
# seeded at 1/16 of the fan-in scale; every path Pallas (pallas_prefill /
# pallas_decode / pallas_ssd); 4 x 512 + 32 unless it says otherwise
READINGS_ONE_SUBLAYER = {
    "served, seeds 0, 1, 2, 3": [
        (0.0546, 0.167, 0.0123, 0.0375, 0.0205, 1.46e-3),
        (0.0549, 0.169, 0.0108, 0.0440, 0.0326, 1.55e-3),
        (0.0529, 0.143, 0.0118, 0.0439, 0.0180, 1.40e-3),
        (0.0550, 0.170, 0.0131, 0.0426, 0.0224, 1.49e-3)],
    "served, 1 x 770 + 16 (the edge reading), seed 0": [
        (0.0525, 0.071, 0.0076, 0.0233, 0.0163, 1.59e-3)],
    "--last-experts-dropped 64, seeds 0, 1": [
        (0.0928, 0.167, 0.0201, 0.0654, 0.0255, 1.43e-3),
        (0.0868, 0.146, 0.0154, 0.0554, 0.0232, 1.48e-3)],
    "--last-mlp-dropped, seed 0": [
        (1.747, 2.103, 0.414, 1.201, 0.0235, 1.48e-3)],
    "--state-not-carried, seed 0": [
        (0.2411, 0.319, 0.0398, 0.152, 0.278, 1.55e-3)],
    "--conv-tail-dropped, 1 x 770 + 16, seed 0": [
        (1.203, 4.784, 0.361, 1.683, 0.157, 1.57e-3)],
    # the same experts seeded at 1/8: a swapped expert moved single tokens
    # past the in-run check's 0.1, so the seeding went to 1/16
    "served at 1/8, seeds 0, 1, 2": [
        (0.0642, 0.278, 0.0128, 0.0589, 0.0211, 1.50e-3),
        (0.0648, 0.261, 0.0141, 0.1188, 0.0261, 1.54e-3),
        (0.0720, 0.300, 0.0151, 0.0978, 0.0227, 1.46e-3)],
    "--last-experts-dropped 64 at 1/8, seed 0": [
        (0.1698, 0.357, 0.0330, 0.1202, 0.0380, 1.46e-3)],
    # the log-probabilities cannot see it; the residue does: 0.0 exactly
    "--bf16-state at 1/8, seed 0": [
        (0.0641, 0.290, 0.0112, 0.0626, 0.0294, 0.0)],
    # --state-ops (out_rel_err, decode_out_rel_err, state_rel_err), 512 + 32
    # in segments of 256 (two scan chunks of 128), both ops the Pallas calls
    "--state-ops": [(3.47e-3, 5.3e-4, 9.2e-4)],
    # read again with the step's tile loop (PR 67: another order of the
    # read-out's float32 sums): seed 0 as above to the digits kept (3.47e-3,
    # 5.35e-4, 9.16e-4: the chunk form's bfloat16 operands set all three),
    # and seed 1, on the parent's loop the same
    "--state-ops, seed 1": [(4.88e-3, 6.3e-4, 2.8e-4)],
    # THE REVIEW'S ROUND (the selection bias reseeded at N(0, 0.02); the
    # rows above were read at 0.1): --replay on requests of the canary's
    # shape, 8 x 96 + 15: the family's routed_share a block as (least,
    # largest, mean, deviation, readings): the E blocks at places 1, 3 and 6
    # by the state plane behind them (the MEDIAN of the four sampled heads'
    # shares; seeds 20-29), the last by the sixteen log-probabilities (seeds
    # 0, 1, 4, 5, 7-14, 20-27)
    "--replay, sound": [
        (0.928, 1.092, 0.998, 0.027, 144), (0.780, 1.129, 0.991, 0.058, 128),
        (0.716, 1.197, 0.979, 0.095, 64), (0.228, 1.640, 0.987, 0.252, 152)],
    "--replay --routed-dropped <that block>": [
        (-0.051, 0.061, -0.006, 0.026, 16), (-0.092, 0.057, -0.008, 0.041, 16),
        (-0.248, 0.278, -0.004, 0.091, 64), (-0.257, 0.438, 0.075, 0.193, 24)],
    # ... and POOLED over the heads, as the round first read it (seeds 0, 1,
    # 4, 5, 7-14 sound; 8 readings a block dropped): one fast head can be
    # 0.99 of a plane's sum of squares, and a sound run of the cell read
    # 0.40 at place 6 (seed 3100000013): why the median is the plane's
    "--replay, sound, pooled over the heads": [
        (0.851, 1.209, 1.002, 0.059, 128), (0.722, 1.435, 1.010, 0.108, 120),
        (0.654, 1.475, 0.995, 0.136, 112)],
    "--replay --routed-dropped <that block>, pooled": [
        (-0.021, 0.127, 0.025, 0.042, 8), (-0.048, 0.282, 0.070, 0.096, 8),
        (-0.298, 0.347, 0.005, 0.228, 8)],
    # what the limits of the first round said of the same controls (mean
    # |err| of the sixteen, largest state plane's rel_err), 8 requests each:
    # place 1 dropped 0.036-0.063 / 0.052-0.097 (refused by the mean), place
    # 3 dropped 0.027-0.042 / 0.030-0.057 (two of eight passed both), place
    # 6 dropped seven of eight passed both (0.021-0.040 the state), the last
    # dropped passed all (0.011-0.020 / the sound state), all four dropped
    # 0.040-0.068 / 0.054-0.160 (refused); sound 0.0057-0.0221 (mean 0.0127,
    # deviation 0.0029) / 0.011-0.049 over 150 canaries, the largest of a
    # canary's sixteen 0.076 at most (eight canaries over 0.05): the mean's
    # limit went 0.025 -> 0.04 with the bias (the family's file says why)
}
# (max_over_vocab_mean, max_over_vocab_max, mean_abs, state_rel_err,
# state_f32_residue); my chip runs, PR 62, TPU v5 lite,
# granite-4.0-h-small-bf16-ep2 at published widths (one period of ten
# blocks, 36 of 72 experts, half the vocabulary); every path Pallas
# (pallas_prefill / pallas_decode / pallas_ssd)
READINGS_SSD = {
    "served, 1 x 1000 + 16, seeds 0, 1, 2": [
        (0.00467, 0.00566, 0.00085, 0.0170, 1.41e-3),
        (0.00470, 0.00546, 0.00087, 0.0148, 1.44e-3),
        (0.00505, 0.00560, 0.00091, 0.0201, 1.41e-3)],
    "served, 1 x 770 + 16 (the edge reading), seed 0": [
        (0.00491, 0.00649, 0.00086, 0.0166, 1.36e-3)],
    "--state-not-carried, 1 x 1000 + 16, seed 0": [
        (0.0577, 0.0639, 0.0106, 0.313, 1.43e-3)],
    "--conv-tail-dropped, 1 x 770 + 16, seed 0": [
        (0.1381, 0.4178, 0.0250, 0.619, 1.34e-3)],
    "--ssd-skip-dropped (D = 0), 1 x 1000 + 16, seeds 0, 1": [
        (0.3815, 0.4251, 0.0705, 1.676, 1.32e-3),
        (0.3271, 0.3973, 0.0598, 2.715, 1.20e-3)],
    # the logits cannot see it (0.00468 against a sound 0.00467), nor can
    # the state's distance to the reference (0.0212 against 0.0148-0.0201);
    # the residue does: 0.0 exactly
    "--bf16-state, 1 x 1000 + 16, seed 0": [
        (0.00468, 0.00533, 0.00086, 0.0212, 0.0)],
    # --state-ops (out_rel_err, decode_out_rel_err, state_rel_err), 1000 + 16
    # in segments of 256, both ops the Pallas calls: bfloat16 operands in
    # the chunk form's products (STATE_OPS_OUT_TOL_SSD)
    "--state-ops, seed 0": [(3.3e-3, 1.3e-3, 4.0e-4)],
    "--state-ops --bf16-state, seed 1": [(4.7e-3, None, 3.3e-3)],
    # read again with the step's tile loop (PR 67): seed 0 as above (3.32e-3,
    # 1.31e-3, 4.04e-4), and seed 1
    "--state-ops, seed 1 (PR 67)": [(4.70e-3, 1.38e-3, 5.0e-4)],
}
# (own_token_mean, own_token_max, state_rel_err): the emitted tokens' own
# log-probabilities, which are all a benchmark run's check sees, and which
# the family holds to LOGPROB_MEAN_TOL (0.0065, the mean); my chip runs, PR
# 62, the same shapes.  What lies behind the last state plane shows here or
# nowhere: the last block's second sublayer does, its routed experts alone
# do not (the seeded down-projections leave their sum under bfloat16's
# noise)
READINGS_SSD_OWN = {
    "served, 1 x 1000 + 16, seeds 0, 1, 3": [
        (0.00153, 0.00470, 0.0170), (0.00242, 0.00502, 0.0148),
        (0.00222, 0.00700, 0.0210)],
    "--last-experts-dropped 36, seeds 0, 1 (rc 0: not seen)": [
        (0.00153, 0.00470, 0.0170), (0.00242, 0.00502, 0.0148)],
    "--last-experts-dropped 7, seed 0 (rc 0: not seen)": [
        (0.00153, 0.00470, 0.0170)],
    "--last-mlp-dropped, seeds 0, 1 (rc 1, the state sound)": [
        (0.0127, 0.0267, 0.0170), (0.0418, 0.0555, 0.0148)],
    "--state-not-carried, seed 0": [(0.0124, 0.0224, 0.313)],
    "--conv-tail-dropped, 1 x 770 + 16, seed 0": [(0.0378, 0.1212, 0.619)],
    "--ssd-skip-dropped, seed 0": [(0.1386, 0.1925, 1.676)],
    "--logits-scaling-dropped, seed 0": [(9.31, 9.35, 0.0170)],
}
# (max_over_vocab_mean, max_over_vocab_max, mean_abs, hc_sinkhorn_residual);
# my chip runs, PR 60, TPU v5 lite, xing4.0-29b-a4b-bf16 at published
# widths (2 leading + 5 expert blocks, 64 of 64 experts, whole vocabulary,
# a bf16 stream); every path Pallas (pallas_prefill / pallas_decode, the
# stream's two calls)
READINGS_STREAMS = {
    "served, 1 x 8192 + 16, seeds 0, 1, 2, 3 (the last on the committed "
    "files alone)": [
        (0.0670, 0.0824, 0.01167, 1.2e-6), (0.0785, 0.0954, 0.01399, 1.1e-6),
        (0.0805, 0.0888, 0.01415, 1.2e-6), (0.0797, 0.0932, 0.01377, 1.1e-6)],
    "served, 2 x 512 + 16, seeds 0, 1": [
        (0.0604, 0.0748, 0.01037, 1.2e-6), (0.0723, 0.0962, 0.01257, 1.1e-6)],
    "--hc-sinkhorn-iters 1, 1 x 8192 + 16, seeds 0, 1": [
        (0.1039, 0.1255, 0.01812, 0.128), (0.1027, 0.1436, 0.01829, 0.172)],
    "--bf16-coef-maps, 1 x 8192 + 16, seed 0": [
        (0.0711, 0.0895, 0.01264, 4.9e-3)],
    # an earlier tree of this PR, seed 0: the query's second matrix seeded
    # at the fan-in scale, so that the seeded scores spread twice as wide
    # under the softmax scale's factor 2.005 (``init_layer_params`` says
    # what was done about it).  The stream's dtype is not where the 0.127
    # came from (float32 stream 0.121); the factor is (0.069 without it)
    "fan-in wq: served 1 x 8192 / 2 x 512 / float32 stream / attn_scale "
    "1 on both sides / one Sinkhorn step / bf16 maps": [
        (0.1266, 0.1635, 0.02222, 1.2e-6), (0.1576, 0.1842, 0.02748, None),
        (0.1209, 0.1401, 0.02062, 1.1e-6), (0.0686, 0.0928, 0.01182, 1.2e-6),
        (0.1444, 0.1733, 0.02480, None), (0.1278, 0.1735, 0.02191, None)],
}
# (max_over_vocab_mean, max_over_vocab_max, mean_abs); my chip runs, PR 56,
# TPU v5 lite, solar-open2-250b-bf16-ep8 at published widths (one period,
# 40 of 320 experts held, an eighth of the vocabulary); every path Pallas
# (pallas_prefill / pallas_decode / pallas_kda)
READINGS_STATE = {
    "served, 1 x 1000 + 16, seeds 0-7": [
        (0.0923, 0.1125, 0.01766), (0.0922, 0.1035, 0.01712),
        (0.0907, 0.1029, 0.01708), (0.0928, 0.1043, 0.01762),
        (0.0898, 0.1006, 0.01727), (0.0884, 0.1002, 0.01712),
        (0.0853, 0.0941, 0.01619), (0.0893, 0.1003, 0.01702)],
    "served, 2 x 512 + 16, seeds 1, 0, 2, 3": [
        (0.0966, 0.1124, 0.01780), (0.0936, 0.1073, 0.01757),
        (0.0939, 0.1136, 0.01791), (0.0974, 0.1155, 0.01809)],
    "--state-not-carried, 1 x 1000 + 16, seed 0": [(0.2583, 0.3078, 0.05044)],
    "--conv-tail-dropped, 1 x 1000 + 16, seeds 0-6": [
        (0.1193, 0.1356, 0.02301), (0.1133, 0.1415, 0.02158),
        (0.1241, 0.1384, 0.02345), (0.1119, 0.1269, 0.02121),
        (0.1189, 0.1378, 0.02279), (0.1126, 0.1335, 0.02126),
        (0.1077, 0.1252, 0.02050)],
    "served, 1 x 770 + 16 (the edge reading), seeds 0-4": [
        (0.0920, 0.1031, 0.01769), (0.0932, 0.1048, 0.01753),
        (0.0860, 0.1032, 0.01661), (0.0911, 0.1074, 0.01734),
        (0.0966, 0.1203, 0.01771)],
    "--conv-tail-dropped, 1 x 770 + 16, seeds 0-4": [
        (1.7150, 3.4597, 0.32905), (1.7328, 3.6501, 0.31498),
        (1.6352, 3.3224, 0.31291), (1.6253, 3.1095, 0.31228),
        (1.7296, 3.9444, 0.32126)],
    "--state-not-carried, 1 x 770 + 16, seed 0": [(3.0097, 3.6596, 0.55752)],
    # the log-probabilities do NOT refuse it (inside the sound readings' own
    # spread, seed by seed); state_f32_residue does: 0.0 against a sound
    # 1.54e-3-1.67e-3 in the eleven readings of the review round that print it
    "--bf16-state, 1 x 1000 + 16, seeds 0, 1, 2": [
        (0.0937, 0.1061, 0.01805), (0.0904, 0.1029, 0.01731),
        (0.0900, 0.1067, 0.01722)],
    # --state-ops, 1000 + 16 in segments of 256, both ops the Pallas calls:
    # (outputs, decode outputs, final state), relative to the largest
    "--state-ops, seed 1": [(7.2e-5, 6.9e-5, 8.8e-5)],
    "--state-ops --bf16-state, seeds 0, 1": [
        (4.4e-3, 4.7e-3, 1.15e-2), (4.5e-3, 5.5e-3, 1.49e-2)],
}
# ``--state-ops``: the ops alone against the recurrence, the larger of the
# outputs' and the final state's relative error: between 8.8e-5 (float32,
# served) and 4.4e-3 (bfloat16), 11 x and 4 x of room
STATE_OPS_TOL = 1e-3
# an ssd kind's chunk form multiplies bfloat16 operands (dt x, the masked
# C B^T, the state rounded for its read: ``ops.ssd``), so its OUTPUTS
# stand ~3e-3 from the float32 recurrence (READINGS_SSD) where kda's
# float32 products stand 7e-5; the STATE keeps the limit above, which is
# what tells a rounded state (4e-4 sound)
STATE_OPS_OUT_TOL_SSD = 8e-3
# a model of several residual streams: the served maps' largest |row or
# column sum - 1| (READINGS_STREAMS): between float32's floor after 20
# Sinkhorn steps and what bfloat16 maps or one step leave
HC_RESIDUAL_MAX = 1e-4


def seeded_ids(seed: int, n: int, vocab: int):
    import numpy as np
    return np.random.default_rng(seed).integers(1, vocab, size=n,
                                                dtype=np.int32)


def bf16_router():
    """A router as a careless port would write it: the matmul on the
    activations as they come (bf16) at the default precision, the scores
    (softmax or sigmoid) over its bf16 result.  Swapped in for
    ``decoder._router_logits`` by ``--bf16-router`` (what it reads is
    under ``READINGS``)."""
    import jax.numpy as jnp

    from distributed_inference_demo_tpu.models import decoder

    def logits(h, w):
        return jnp.einsum("th,he->te", h, w.astype(h.dtype)).astype(
            jnp.float32)

    decoder._router_logits = logits


def hc_sinkhorn_residual(cfg, params, ids) -> float:
    """Largest ``|row or column sum - 1|`` of the first block's attention
    map over the first 128 tokens of ``ids``, through the served
    ``hc_pre`` (the kernel on the chip) at ``cfg``'s iteration count: what
    the engine's ``/stats.hc.sinkhorn_residual_max`` reads."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_inference_demo_tpu.models.decoder import (
        hc_sinkhorn_probe)

    probe = jax.jit(lambda p, i: hc_sinkhorn_probe(p, cfg, i))
    return float(probe(params, jnp.asarray(np.resize(ids, 128))))


def bf16_coef_maps():
    """``--bf16-coef-maps``: the residual streams' coefficient maps (the
    raw coefficients, the sigmoids, the exponential and every Sinkhorn
    step) in bfloat16, on the plain path (``ops.hyper_connection``'s
    ``COEF_DTYPE``; the kernels hold them in float32 and are not taken)."""
    import jax.numpy as jnp

    from distributed_inference_demo_tpu.ops import hyper_connection

    hyper_connection.COEF_DTYPE = jnp.bfloat16


def bf16_softmax_state():
    """The latent kernels' online-softmax state (running maximum, sum and
    output between pages) kept in bf16, where the program keeps float32:
    ``--bf16-softmax-state``, the lower precision a latent-attention
    model's long-context reading is held against."""
    import jax.numpy as jnp

    from distributed_inference_demo_tpu.ops import (latent_attention,
                                                    paged_attention)

    latent_attention._STATE_DTYPE = jnp.bfloat16
    paged_attention._STATE_DTYPE = jnp.bfloat16   # rounded between pages


def window_ignored(cfg):
    """``cfg`` with every window kind's window wider than any context:
    its blocks are then served as full ones (``--window-ignored``, the
    control a wrong window must not pass; the reference keeps the
    window)."""
    wide = lambda k: dataclasses.replace(k, window=1 << 30) if k.window else k
    return cfg.replace(period=tuple(wide(k) for k in cfg.period))


def summaries_withheld():
    """The evabyte REFERENCE told that no query sees a summary
    (``--summaries-withheld``): the fault control of the long reading,
    which must then read far outside the limits."""
    import jax.numpy as jnp

    from families import evabyte

    evabyte.summaries_seen = lambda n, lo, hi, window, chunk: jnp.zeros(
        (hi - lo, n), bool)


def state_controls(bf16_state=False, not_carried=False, tail_dropped=False,
                   kind="kda"):
    """The three faults a recurrent state's long reading must show
    (``--bf16-state``, ``--state-not-carried``, ``--conv-tail-dropped``),
    swapped in for the two ops of the state KIND's module (``ops.kda`` or
    ``ops.ssd``) and for the one convolution both kinds share
    (``ops.kda.causal_conv``), which the decoder calls by name."""
    import jax
    import jax.numpy as jnp

    from distributed_inference_demo_tpu.ops import kda, ssd

    # (the linear-attention kind rides the ssd kind's two ops)
    mod = {"kda": kda, "ssd": ssd, "lightning": ssd}[kind]
    base = "ssd" if kind == "lightning" else kind
    names = (f"{base}_step", f"{base}_chunk")
    step, chunk = (getattr(mod, n) for n in names)
    conv = kda.causal_conv
    # (an op of its own: the compiler elides a convert to bfloat16 and
    # back, and the first reading of this control read the sound one)
    rounded = lambda st: jax.lax.reduce_precision(st, exponent_bits=8,
                                                  mantissa_bits=7)
    if bf16_state:
        def rounding(op):
            def wrapped(state, *a, **k):
                o, state = op(state, *a, **k)
                return o, rounded(state)
            return wrapped

        setattr(mod, names[0], rounding(step))
        setattr(mod, names[1], rounding(chunk))
    if not_carried:
        inner = getattr(mod, names[1])
        setattr(mod, names[1],
                lambda state, plane, row, fresh, *a, **k: inner(
                    state, plane, row, jnp.bool_(True), *a, **k))
    if tail_dropped:
        kda.causal_conv = lambda u, tail, w, ntok, bias=None: conv(
            u, jnp.zeros_like(tail) if u.shape[1] > 1 else tail, w, ntok,
            bias)


def ssd_ops_reading(cfg, args) -> dict:
    """``--state-ops`` for an ssd kind: :func:`state_ops_reading`'s reading
    of ``ops.ssd``'s two ops at the model's widths (one row of a pool;
    ``--prompt`` tokens in segments of ``--chunk`` through ``ssd_chunk`` at
    the kind's scan chunk, the last padded with tokens that are not there,
    then ``--steps`` tokens through ``ssd_step``; the Pallas calls on a
    TPU) against the recurrence token by token in float32.  Vectors as an
    ssd block makes them: x, B and C in the model's dtype, ``A`` and ``dt``
    as seeded."""
    import jax
    import jax.numpy as jnp

    from distributed_inference_demo_tpu.ops import ssd

    kind = cfg.state_kind
    H, P, N, G = (kind.state_heads, kind.state_head_dim, kind.state_size,
                  kind.groups)
    C, n = args.chunk, args.prompt + args.steps
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 6)
    act = lambda k, shape: jax.nn.silu(jax.random.normal(k, shape)).astype(
        cfg.dtype)
    x, B, Cm = act(ks[0], (n, H, P)), act(ks[1], (n, G, N)), act(
        ks[2], (n, G, N))
    A = -jax.random.uniform(ks[3], (H,), jnp.float32, 1.0, 16.0)
    step = jnp.exp(jax.random.uniform(ks[4], (H,), jnp.float32,
                                      jnp.log(1e-3), jnp.log(0.1)))
    dt = jax.nn.softplus(jax.random.normal(ks[5], (n, H))
                         + step + jnp.log(-jnp.expm1(-step)))
    state = jnp.zeros((1, 2, H, P, N), jnp.float32)
    kernel, why = ssd.on_kernel(state.shape, G, min(C, kind.chunk))
    zero = jnp.int32(0)

    @jax.jit
    def segment(state, fresh, *v):
        return ssd.ssd_chunk(state, zero, zero, fresh, *v, A,
                             chunk=kind.chunk, kernel=kernel)

    @jax.jit
    def token(state, *v):
        return ssd.ssd_step(state, zero, jnp.zeros((1,), jnp.int32), *v, A,
                            jnp.ones((1,), bool), kernel=kernel)

    outs = []
    for lo in range(0, args.prompt, C):
        hi = min(args.prompt, lo + C)
        pad = lambda a, fill=0.0: jnp.pad(
            a[lo:hi], ((0, C - (hi - lo)),) + ((0, 0),) * (a.ndim - 1),
            constant_values=fill)
        o, state = segment(state, jnp.bool_(lo == 0), pad(x, 9.0),
                           pad(B, 9.0), pad(Cm, 9.0), pad(dt))
        outs.append(o[:hi - lo].astype(jnp.float32))
    for t in range(args.prompt, n):
        o, state = token(state, x[t:t + 1], B[t:t + 1], Cm[t:t + 1],
                         dt[t:t + 1])
        outs.append(o)
    o = jnp.concatenate(outs)
    with jax.default_matmul_precision("highest"):
        want_o, want_S = jax.jit(ssd.ssd_recurrence)(
            jnp.zeros((H, P, N), jnp.float32), x, B, Cm, dt, A)
    rel = lambda a, b: float(jnp.abs(a - b).max() / jnp.abs(b).max())
    return {"kernel": kernel, "why_not": why,
            "out_rel_err": rel(o, want_o),
            "decode_out_rel_err": rel(o[args.prompt:], want_o[args.prompt:]),
            "state_rel_err": rel(state[0, 0], want_S),
            "mean_log_decay": float((dt * A).mean())}


def state_ops_reading(cfg, args) -> dict:
    """``--state-ops``: the two recurrent-state ops ALONE at the model's
    widths, as the served path calls them (one row of a pool; ``--prompt``
    tokens in segments of ``--chunk`` through ``kda_chunk``, the last
    padded with tokens that are not there, then ``--steps`` tokens through
    ``kda_step``; the Pallas calls on a TPU), against the recurrence token
    by token in float32 (``ops.kda.kda_recurrence``).  The logits cannot
    see the state's precision under seeded bf16 weights (``READINGS_STATE``),
    so it is held here: the largest error of the outputs and of the final
    state, each over its reference's largest magnitude.  Vectors as a kda
    block makes them: q and k of unit length, the decay from ``A_log`` and
    ``dt_bias`` as seeded and a gate input of unit variance."""
    import jax
    import jax.numpy as jnp

    from distributed_inference_demo_tpu.ops import kda

    if cfg.state_kind.attn == "ssd":
        return ssd_ops_reading(cfg, args)
    kind = cfg.state_kind
    H, d, C = kind.num_heads, cfg.head_dim, args.chunk
    n = args.prompt + args.steps
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 7)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (n, H, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (n, H, d)))
    v = jax.random.normal(ks[2], (n, H, d))
    A = jax.random.uniform(ks[3], (H, 1), jnp.float32, 1.0, 16.0)
    step = jnp.exp(jax.random.uniform(ks[4], (H, d), jnp.float32,
                                      jnp.log(1e-3), jnp.log(0.1)))
    bias = step + jnp.log(-jnp.expm1(-step))
    g = -A * jax.nn.softplus(jax.random.normal(ks[5], (n, H, d)) + bias)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[6], (n, H)))
    state = jnp.zeros((1, 2, H, d, d), jnp.float32)
    kernel, why = kda.on_kernel(state.shape, C)
    zero = jnp.int32(0)

    @jax.jit
    def segment(state, fresh, *x):
        return kda.kda_chunk(state, zero, zero, fresh, *x, kernel=kernel)

    @jax.jit
    def token(state, *x):
        return kda.kda_step(state, zero, jnp.zeros((1,), jnp.int32), *x,
                            jnp.ones((1,), bool), kernel=kernel)

    outs = []
    for lo in range(0, args.prompt, C):
        hi = min(args.prompt, lo + C)
        pad = lambda a, fill=0.0: jnp.pad(
            a[lo:hi], ((0, C - (hi - lo)),) + ((0, 0),) * (a.ndim - 1),
            constant_values=fill)
        o, state = segment(state, jnp.bool_(lo == 0), pad(q, 9.0),
                           pad(k, 9.0), pad(v, 9.0), pad(g), pad(beta))
        outs.append(o[:hi - lo])
    for t in range(args.prompt, n):
        o, state = token(state, q[t:t + 1], k[t:t + 1], v[t:t + 1],
                         g[t:t + 1], beta[t:t + 1])
        outs.append(o)
    o = jnp.concatenate(outs)
    with jax.default_matmul_precision("highest"):
        want_o, want_S = jax.jit(kda.kda_recurrence)(
            jnp.zeros((H, d, d), jnp.float32), q, k, v, g, beta)
    rel = lambda a, b: float(jnp.abs(a - b).max() / jnp.abs(b).max())
    return {"kernel": kernel, "why_not": why,
            "out_rel_err": rel(o, want_o),
            "decode_out_rel_err": rel(o[args.prompt:], want_o[args.prompt:]),
            "state_rel_err": rel(state[0, 0], want_S),
            "mean_log_alpha": float(g.mean())}


def period_tables(cfg, b: int, W: int, bt: int, lo: int, hi: int,
                  span: int):
    """A period model's tables for a call that writes tokens ``[lo, hi)``
    of every row, ``[b, pools x W]`` (one table a pool side by side, the
    full kind's first), and the pages of each pool.  The window kind's
    pages are a RING of as many as its window and one call's tokens meet:
    a page that fell behind the window is the page a later block is
    written to, as in the engine, where it went back to the pool and came
    out again; table entries behind the window are sentinel."""
    import numpy as np
    if len(cfg.cache_kinds) == 1:
        # one pool of pages and, for a model with a recurrent state, a
        # last column: row r's row of the state pool
        full = np.arange(b * W, dtype=np.int32).reshape(b, W)
        rows = np.arange(b, dtype=np.int32)[:, None]
        return (np.concatenate([full, rows], 1) if cfg.state_planes
                else full), (b * W,)
    window = cfg.cache_kinds[1][0]
    ring = -(-(min(window, W * bt) + span) // bt) + 1
    sentinel = b * W
    full = np.arange(b * W, dtype=np.int32).reshape(b, W)
    win = np.full((b, W), sentinel, np.int32)
    first = max(0, lo - window + 1) // bt
    for j in range(first, min(W, -(-hi // bt))):
        win[:, j] = np.arange(b) * ring + j % ring
    return np.concatenate([full, win], 1), (b * W, b * ring)


def served_logprobs(cfg, params, prompts, args):
    """``(tokens [b, steps], logprobs [b, steps + 1, V], paths)``: the
    served path's log-softmax at the prompt's last position and after
    each decode step, and the greedy tokens it chose."""
    return served(cfg, params, prompts, args)[:3]


def served(cfg, params, prompts, args):
    """``served_logprobs`` and, last, what a model with a recurrent state
    left in its requests' rows of the state pool (a sample as the engine's
    reply takes it: every plane, four heads, every eighth key), else
    None."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_inference_demo_tpu.models import KVCache, StageSpec
    from distributed_inference_demo_tpu.ops.paged_attention import (
        AttnPathRecord)
    from distributed_inference_demo_tpu.ops.quant import alloc_kv_pool
    from distributed_inference_demo_tpu.parallel.tensor import (
        make_paged_forward_seam)

    b, plen = prompts.shape
    bt, C = args.page, args.chunk
    W = -(-(plen + args.steps + 1) // bt)
    if cfg.summary_kv:
        # a row's leases: a summary page a window, then one window's pages
        W = (-(-(plen + args.steps + 1) // cfg.eva_window)
             + cfg.eva_window // bt)
    record = AttnPathRecord()
    fwd, bind, _ = make_paged_forward_seam(
        cfg, StageSpec(0, 1, 0, cfg.num_layers), None, params, bt,
        record=record)
    heads, width = cfg.kv_page_shape
    if cfg.mixed_kinds:     # one pool and one table a kind of block
        _, pages = period_tables(cfg, b, W, bt, 0, C, C)
        pools = [alloc_kv_pool((planes, n, heads, bt, width), args.kv_dtype,
                               cfg.dtype)
                 for (_, planes), n in zip(cfg.cache_kinds, pages)]
        pk, pv = tuple(p[0] for p in pools), tuple(p[1] for p in pools)
        if cfg.sparse_kind is not None:     # the index plane beside pool 0
            pk += (jnp.zeros(cfg.index_shape(pages[0], bt), cfg.dtype),)
            pv += (jnp.zeros((1,), cfg.dtype),)
        if cfg.state_planes:    # b rows and one that is nobody's
            s_shape, c_shape = cfg.state_shapes
            pk += (jnp.zeros((cfg.state_planes, b + 1) + s_shape,
                             jnp.float32),)
            pv += (jnp.zeros((cfg.state_planes, b + 1) + c_shape,
                             cfg.dtype),)

        def tables_for(lo, hi):
            return jnp.asarray(period_tables(cfg, b, W, bt, lo, hi, C)[0])
    else:
        pk, pv = alloc_kv_pool((cfg.kv_planes, b * W, heads, bt, width),
                               args.kv_dtype, cfg.dtype,
                               streams=cfg.kv_streams)
        whole = jnp.arange(b * W, dtype=jnp.int32).reshape(b, W)
        tables_for = lambda lo, hi: whole

    @jax.jit
    def chunk(params, pk, pv, ids, start, tables, last):
        bind(tables, "prefill")
        pos = start + jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
        # a recurrent state must be told which positions hold a token
        held = ({"valid": jnp.broadcast_to(
            jnp.arange(ids.shape[1]) <= last, ids.shape)}
            if cfg.state_planes else {})
        logits, cache = fwd(params, ids, KVCache(pk, pv, jnp.int32(0)),
                            pos, last, **held)
        return (jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), -1),
                cache.keys, cache.values)

    @jax.jit
    def step(params, pk, pv, tok, length, tables):
        bind(tables, "decode")
        logits, cache = fwd(params, tok[:, None],
                            KVCache(pk, pv, jnp.int32(0)), length[:, None],
                            0)
        return (jax.nn.log_softmax(logits[:, 0].astype(jnp.float32), -1),
                cache.keys, cache.values)

    for start in range(0, plen, C):
        ids = prompts[:, start:start + C]
        last = ids.shape[1] - 1
        if (cfg.summary_kv or cfg.state_planes) and ids.shape[1] < C:
            # a chunk lies in one window and holds whole pooling chunks:
            # the last one is padded as the engine's slab pads it (what
            # the pad tokens write lies behind the length, and the decode
            # steps write it again before any query sees it)
            ids = np.pad(ids, ((0, 0), (0, C - ids.shape[1])))
        lp, pk, pv = chunk(params, pk, pv, jnp.asarray(ids),
                           jnp.int32(start), tables_for(start, start + C),
                           jnp.int32(last))
    lps, toks = [np.asarray(lp)], []
    length = jnp.full((b,), plen, jnp.int32)
    for t in range(args.steps):
        tok = jnp.argmax(lp, -1).astype(jnp.int32)
        toks.append(np.asarray(tok))
        lp, pk, pv = step(params, pk, pv, tok, length,
                          tables_for(plen + t, plen + t + 1))
        lps.append(np.asarray(lp))
        length = length + 1
    # a recurrent state as the served path left it: the requests' rows,
    # every plane (the limit on it is the family's, as in a benchmark run)
    state = (np.asarray(pk[-1][:, :b, ::max(1, pk[-1].shape[2] // 4), ::8])
             if cfg.state_planes else None)
    return np.stack(toks, 1), np.stack(lps, 1), record.snapshot(), state


# blocks of a query's kept set (97 at the published sizes) that may differ
# between the SERVED selection and the equations': between the sound
# readings' largest, 2, and the nearest control's 11 (READINGS_SPARSE)
KEPT_DIFFER_MAX = 4
# (max_over_vocab_mean, max_over_vocab_max, state_rel_err, kept_differ_max,
# kept_differ_mean over 8 positions x 2 kv heads of the first sparse
# block: the prompt's last four queries, which the PREFILL calls select and
# fold in a tile of 32, and the last four decode steps', a tile of one); my
# chip runs, PR 69, TPU v5 lite, minicpm-sala-9b-bf16 at published widths
# (eight layers, the whole vocabulary), every path Pallas
# (pallas_sparse_prefill / pallas_sparse_decode / pallas_la); 1 x 24,600 +
# 16 unless it says otherwise: every query read is past dense_len three
# times over and keeps 97 of 385 blocks.  The kept sets are what the served
# PROGRAM chose (``keep_tap``: the mask its selection handed its fold), and
# the three controls are planted in that program (``selection_control``),
# so each is refused through the path a request takes; FAMILY_TOL holds the
# log-probabilities between the sound readings and the forced blocks alone
READINGS_SPARSE = {
    "served, seed 0": (0.00436, 0.00473, 0.0202, 1, 0.3125),
    "served, seed 1, 1 x 30,000 + 16": (0.00439, 0.00497, 0.0198, 2, 0.3125),
    "--selection forced-only (exit 1; 33 kept of 97)":
        (0.01653, 0.02003, 0.0697, 64, 64.0),
    "--selection one-head (exit 1)": (0.01160, 0.01589, 0.0506, 57, 50.25),
    "--selection edge-dropped (exit 1, by the kept sets alone)":
        (0.00563, 0.00808, 0.0260, 11, 5.4375),
    # the log-probabilities read BEHIND the tap, one pass (before the tool
    # took a second pass for it): another compilation of the same program
    "behind the tap, seeds 0 / 1 / 2 (24,600 / 30,000 / 27,000)":
        ((0.00508, 0.00494, 0.00495), (0.00600, 0.00574, 0.00609),
         (0.0222, 0.0220, 0.0223), (1, 2, 1), 0.3125)}


def selection_control(which: str) -> None:
    """``--selection``: one fault planted in the served selection, by
    swapping a function of ``ops.sparse_attention`` that the dispatch calls
    by name (as :func:`state_controls` does; the op carries no switch):
    ``forced-only`` chooses no block by score (``_choose`` told top-0),
    ``one-head`` scores with the first query head of each kv group alone
    (``select_blocks`` handed that head's q), ``edge-dropped`` reads zeros
    for the keys before a chunk's first token, so the kernels that straddle
    it pool half of nothing (``_keys_before``)."""
    import jax.numpy as jnp

    from distributed_inference_demo_tpu.ops import sparse_attention as sa

    if which == "forced-only":
        choose = sa._choose
        sa._choose = lambda sj, t, sizes: choose(
            sj, t, sizes[:3] + (0,) + sizes[4:])
    elif which == "one-head":
        select = sa.select_blocks

        def one_head(q, index, tables, positions, sizes, nkv, *a, **k):
            b, s, nh, hd = q.shape
            return select(q.reshape(b, s, nkv, nh // nkv, hd)[:, :, :, 0],
                          index, tables, positions, sizes, nkv, *a, **k)

        sa.select_blocks = one_head
    elif which == "edge-dropped":
        before = sa._keys_before
        sa._keys_before = lambda *a: jnp.zeros_like(before(*a))
    else:
        raise ValueError(f"no control of the selection named {which!r}")


def read_positions(n_prompt: int, n: int, last: int = 4) -> list:
    """The positions whose kept sets a reading of ``n`` tokens compares: the
    prompt's ``last`` (the prefill calls' queries) and the ``last`` of all
    (the decode calls')."""
    return sorted({t for t in (*range(n_prompt - last, n_prompt),
                               *range(n - last, n)) if t >= 0})


def keep_tap(want) -> dict:
    """The kept blocks as the served PROGRAM chose them: the two folds of
    ``ops.sparse_attention`` (the Pallas call's wrapper and the gather)
    swapped for themselves behind a host callback that files the mask
    ``keep`` its selection handed the call, for the queries at the
    positions ``want``.  Returns the dict the callbacks fill, ``{(plane,
    row, position): keep [kv heads, blocks] bool}``; read it after
    ``jax.effects_barrier()``."""
    import jax
    import numpy as np

    from distributed_inference_demo_tpu.ops import sparse_attention as sa

    store, want = {}, set(want)

    def file(plane, positions, keep):
        for row, col in zip(*np.nonzero(np.isin(positions, list(want)))):
            store[int(plane), int(row), int(positions[row, col])] = (
                np.asarray(keep[row, col]))

    def tapped(fold):
        def call(q, k_pages, v_pages, tables, positions, keep, **kw):
            jax.debug.callback(file, k_pages.layer, positions, keep)
            return fold(q, k_pages, v_pages, tables, positions, keep, **kw)
        return call

    sa.sparse_fold = tapped(sa.sparse_fold)
    sa.sparse_gather_attention = tapped(sa.sparse_gather_attention)
    return store


def selection_reading(cfg, params, ids, n_prompt: int, kept: dict) -> dict:
    """How many blocks of a query's kept set differ between the SERVED
    selection and the equations', at :func:`read_positions` (the prompt's
    last queries, selected and folded by the prefill calls, and the last of
    ``ids``, by the decode calls), each kv head of the FIRST sparse block (the
    period's first place: its input is the embedding, so its float32 q and k
    are one norm and two products from the parameters).  The served side is
    ``kept`` (:func:`keep_tap`: what the program's selection handed its
    fold, the first request's), the other the family's ``kept_blocks`` over
    float32 keys.  Log-probabilities see a selection over seeded weights
    faintly (every block weighs alike); this sees a block."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import families
    from distributed_inference_demo_tpu.ops.sparse_attention import (
        blocks_kept)

    kind = cfg.period[0]
    assert kind.attn == "sparse", "the period's first block is the sparse one"
    name = next(n for n, k, _ in cfg.kinds if k == kind)
    leaf = lambda key: np.asarray(  # noqa: E731
        params.layers[f"{key}.{name}"][0, 0].astype(jnp.float32))
    hd, nkv = cfg.head_dim, cfg.num_kv_heads
    g = kind.num_heads // nkv
    norm = lambda x, w: x / np.sqrt(  # noqa: E731
        (x * x).mean(-1, keepdims=True) + cfg.norm_eps) * w
    at = read_positions(n_prompt, len(ids))
    with jax.default_matmul_precision("highest"):
        x = cfg.embedding_multiplier * np.asarray(
            params.embed["tokens"][jnp.asarray(ids)].astype(jnp.float32))
        a = norm(x, leaf("attn_norm_w"))
        k = np.asarray(jnp.asarray(a) @ jnp.asarray(leaf("wk"))).reshape(
            len(ids), nkv, hd)
        q = np.asarray(jnp.asarray(a[at]) @ jnp.asarray(
            leaf("wq"))).reshape(len(at), nkv, g, hd)
    if kind.qk_norm:
        q, k = norm(q, leaf("q_norm_w")), norm(k, leaf("k_norm_w"))
    fam = families.load(cfg.family)
    sizes = kind.sparse_sizes
    differ, sizes_kept = [], []
    for i, t in enumerate(at):
        served = kept[0, 0, t]
        for h in range(nkv):
            want = set(fam.kept_blocks(q[i, h], k[:t + 1, h], t, sizes))
            got = set(np.flatnonzero(served[h]).tolist())
            differ.append(max(len(want - got), len(got - want)))
            sizes_kept.append(len(got))
    return {"kept_differ_max": max(differ),
            "kept_differ_mean": sum(differ) / len(differ),
            "kept_served": sorted(set(sizes_kept)),
            "kept_of": int(blocks_kept(len(ids) - 1, sizes)[1]),
            "kept_differ_max_tol": KEPT_DIFFER_MAX}


def reference_logprobs(cfg, params, ids, n_prompt: int):
    """``(logprobs, margins)``: the reference's log-softmax
    ``[len(ids) - n_prompt + 1, V]`` at the positions that predict token
    ``n_prompt`` onward and the one after the last (the whole sequence at
    once, float32, highest precision); and, for a model with experts, the
    router's margin ``p_k - p_(k+1)`` at those positions in every layer
    (``[layers, positions]``, else None): where it is tiny, rounding
    picks another expert and the error does not shrink with precision.
    The rows that enter a layer's router are the family's own layer with
    the experts' down projections zeroed (x + attention), normed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import families
    import reference

    mc = dataclasses.asdict(cfg)
    embed, layer_eq, final_norm = families.load(cfg.family).equations(mc)
    # (a period model's leaves are named by kind, and the router of a
    # model of several residual streams reads their weighted sum: no
    # margins are read)
    E, k = (0 if cfg.mixed_kinds or cfg.hc_streams else cfg.num_experts,
            cfg.experts_per_token)

    @jax.jit
    def margin(x, layers, i):
        p = {key: reference._f32(jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            v)) for key, v in layers.items()}
        zeroed = {key: jnp.zeros_like(p[key])
                  for key in ("w_down", "ws_down") if key in p}
        mid = layer_eq(dict(p, **zeroed), x)
        h = reference._rms_norm(mid[n_prompt - 1:], p["mlp_norm_w"],
                                cfg.norm_eps)
        if cfg.router_scoring == "sigmoid":   # chosen by score + bias
            s = jax.nn.sigmoid(h @ p["router"]) + p.get("router_bias", 0.0)
        else:
            s = jax.nn.softmax(h @ p["router"], -1)
        s = jnp.sort(s, -1)
        return s[:, E - k] - s[:, E - k - 1]

    margins = []
    with jax.default_matmul_precision("highest"):
        x = embed(params, jnp.asarray(ids, jnp.int32))
        layer = reference._make_layer_fn(layer_eq)
        for i in range(cfg.num_layers):
            if E:
                margins.append(np.asarray(
                    margin(x, params.layers, jnp.int32(i))))
            x = layer(x, params.layers, jnp.int32(i))
        x = final_norm(params, x[n_prompt - 1:])
        head = (params.embed["tokens"].astype(jnp.float32).T
                if cfg.tie_embeddings
                else reference._f32(params.lm_head["w"])
                [:, :cfg.vocab_size])   # the next token's head, the first
        return (np.asarray(jax.nn.log_softmax(x @ head, -1)),
                np.stack(margins) if margins else None)


def reference_states(cfg, params, ids):
    """The reference's recurrent states after ``ids``, one a state plane
    (the family's ``blocks``: its period layer that also returns them),
    sampled as the engine's reply samples a row: ``[planes, 4 heads, every
    eighth row, all]``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import families
    import reference

    fam, mc = families.load(cfg.family), dataclasses.asdict(cfg)
    embed, _, _ = fam.equations(mc)
    layer = reference._make_layer_fn(fam.blocks(mc)[1])
    states = []
    with jax.default_matmul_precision("highest"):
        x = embed(params, jnp.asarray(ids, jnp.int32))
        for i in range(cfg.num_layers):
            x, planes = layer(x, params.layers, jnp.int32(i))
            states += [np.asarray(S) for S in planes]
    hs = max(1, states[0].shape[0] // 4)
    return np.stack([S[::hs, ::8] for S in states])


def replay_readings(cfg, params, prompts, toks, served_lp, state) -> list:
    """``--replay``: each request through the family's own ``replay``, as
    ``benchmark/reference.py`` hands it a canary: the ids (prompt, the
    tokens emitted and the one the last position chose: the served state
    has absorbed all but that one), the state's sample as the engine's
    reply records it and the emitted tokens' served log-probabilities.
    One entry a request: the replay's error, or the largest |served -
    reference| of its tokens (what the harness holds to its 0.1)."""
    import base64

    import numpy as np

    import families

    mc = dataclasses.asdict(cfg)
    score = families.load(cfg.family).replay(mc)
    heads = list(range(0, cfg.state_shapes[0][0],
                       max(1, cfg.state_shapes[0][0] // 4)))
    keys = list(range(0, cfg.state_shapes[0][1], 8))
    out = []
    for r in range(len(prompts)):
        chosen = list(toks[r]) + [int(served_lp[r, -1].argmax())]
        own = [float(served_lp[r, i, t]) for i, t in enumerate(chosen)]
        got = np.asarray(state[:, r], "<f4")
        said = score(params, [int(t) for t in prompts[r]] + [
            int(t) for t in chosen], len(prompts[r]), {
            "logprobs": own, "ssd_state": {
                "pool_dtype": str(state.dtype), "heads": heads,
                "keys": keys,
                "shape": list(got.shape),
                "float32_b64": base64.b64encode(got.tobytes()).decode(
                    "ascii")}})
        errs = ([] if "error" in said else
                [abs(a - b) for a, b in zip(own, said["logprobs"])])
        out.append({k: said[k] for k in ("error", "routed_share",
                                         "state_rel_err") if k in said}
                   | ({"max_abs_err": max(errs),
                       "mean_abs_err": sum(errs) / len(errs)}
                      if errs else {}))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=512)
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--page", type=int, default=128)
    ap.add_argument("--kv-dtype", default="bf16",
                    choices=("bf16", "int8"))
    ap.add_argument("--bf16-router", action="store_true",
                    help="swap in a bf16 router (see READINGS)")
    ap.add_argument("--bf16-softmax-state", action="store_true",
                    help="attention kernels: online-softmax state in bf16")
    ap.add_argument("--window-ignored", action="store_true",
                    help="serve a period model's window blocks as full "
                         "ones (a control: must be refused)")
    ap.add_argument("--summaries-withheld", action="store_true",
                    help="tell an evabyte REFERENCE that no query sees a "
                         "summary (a control: must be refused)")
    ap.add_argument("--state-ops", action="store_true",
                    help="read the two recurrent-state ops alone against "
                         "the recurrence (see state_ops_reading)")
    ap.add_argument("--bf16-state", action="store_true",
                    help="a recurrent state rounded to bfloat16 after "
                         "every write (a control: must be refused)")
    ap.add_argument("--state-not-carried", action="store_true",
                    help="every prefill chunk starts from a zero state (a "
                         "control: must be refused)")
    ap.add_argument("--conv-tail-dropped", action="store_true",
                    help="every prefill chunk's convolution starts from "
                         "zeros (a control: must be refused)")
    ap.add_argument("--ssd-skip-dropped", action="store_true",
                    help="serve an ssd model with D = 0, the skip dropped "
                         "(a control: its states must be refused)")
    ap.add_argument("--last-experts-dropped", type=int, default=0,
                    help="serve a period model with the first N held "
                         "experts of its LAST block writing nothing (their "
                         "down-projections zero): a control behind the "
                         "last state plane, which only log-probabilities "
                         "reach")
    ap.add_argument("--routed-dropped", default="",
                    help="serve a model of one-sublayer blocks with the "
                         "held routed experts of these E blocks writing "
                         "nothing (comma-separated places among the "
                         "period's E blocks, 0 the first; 'all'): controls "
                         "the family's replay must refuse by its paired "
                         "readings (--replay)")
    ap.add_argument("--selection", default="",
                    choices=("", "forced-only", "one-head", "edge-dropped"),
                    help="a control of a block-sparse kind's selection "
                         "(must exit 1 on a reading past dense_len): the "
                         "forced blocks alone, one head's scores in place "
                         "of the group's sum, or the keys before a chunk's "
                         "first token left out of the kernels that "
                         "straddle it")
    ap.add_argument("--replay", action="store_true",
                    help="hand each request to the family's OWN replay as "
                         "a benchmark run's canary is (its state's sample "
                         "and its emitted tokens' log-probabilities as the "
                         "engine's reply carries them) and print what it "
                         "said; exit 1 if it refused one")
    ap.add_argument("--last-mlp-dropped", action="store_true",
                    help="... and its shared MLP too: the last block's "
                         "whole second sublayer writes nothing")
    ap.add_argument("--logits-scaling-dropped", action="store_true",
                    help="serve with logits_scaling 1 (a control behind "
                         "the last state plane: must be refused)")
    ap.add_argument("--hc-sinkhorn-iters", type=int, default=None,
                    help="serve a model of several residual streams with "
                         "this many Sinkhorn steps (a control at 1: must be "
                         "refused)")
    ap.add_argument("--bf16-coef-maps", action="store_true",
                    help="the streams' coefficient maps computed in "
                         "bfloat16 (a control: must be refused)")
    args = ap.parse_args(argv)
    from distributed_inference_demo_tpu.cli import configure_compile_cache
    configure_compile_cache()
    import jax
    import numpy as np

    from bench_config import model_config_for
    from distributed_inference_demo_tpu.models.decoder import (
        init_full_params)

    if args.bf16_router:
        bf16_router()
    if args.bf16_softmax_state:
        bf16_softmax_state()
    if args.summaries_withheld:
        summaries_withheld()
    if args.bf16_coef_maps:
        bf16_coef_maps()
    if args.selection:
        selection_control(args.selection)
    dev = jax.devices()[0]
    cfg = model_config_for(args.model)
    if args.bf16_state or args.state_not_carried or args.conv_tail_dropped:
        state_controls(args.bf16_state, args.state_not_carried,
                       args.conv_tail_dropped, cfg.state_kind.attn)
    t0 = time.monotonic()
    if args.state_ops:
        row = dict(state_ops_reading(cfg, args), model=args.model,
                   platform=dev.platform, device_kind=dev.device_kind,
                   bf16_state=args.bf16_state, prompt=args.prompt,
                   steps=args.steps, chunk=args.chunk, tol=STATE_OPS_TOL)
        tol_out = (STATE_OPS_OUT_TOL_SSD if cfg.state_kind.attn == "ssd"
                   else STATE_OPS_TOL)
        row["tol_out"] = tol_out
        row["ok"] = (row["out_rel_err"] <= tol_out
                     and row["state_rel_err"] <= STATE_OPS_TOL)
        row["total_s"] = round(time.monotonic() - t0, 1)
        print("STATE_OPS " + json.dumps(row), flush=True)
        return 0 if row["ok"] else 1
    params = init_full_params(jax.random.PRNGKey(args.seed), cfg,
                              quantize=cfg.quantization != "none")
    prompts = np.stack([seeded_ids(args.seed * 1000 + 17 + i, args.prompt,
                                   cfg.vocab_size)
                        for i in range(args.batch)])
    served_cfg = window_ignored(cfg) if args.window_ignored else cfg
    if args.hc_sinkhorn_iters is not None:
        served_cfg = served_cfg.replace(
            hc_sinkhorn_iters=args.hc_sinkhorn_iters)
    served_params = params
    if args.ssd_skip_dropped:
        served_params = dataclasses.replace(params, layers={
            k: (jax.numpy.zeros_like(v) if k.split(".")[0] == "D" else v)
            for k, v in params.layers.items()})
    if args.logits_scaling_dropped:
        served_cfg = served_cfg.replace(logits_scaling=1.0)
    if args.last_mlp_dropped:
        args.last_experts_dropped = cfg.experts_held[0]
    if args.last_experts_dropped:
        # the stacks of the period's last place, its last block
        last = cfg.period[-1].name
        of = lambda leaf: next(                                 # noqa: E731
            k for k in served_params.layers if k.split(".")[0] == leaf
            and k.split(".")[1].startswith(last))
        layers = dict(served_params.layers)
        layers[of("w_down")] = layers[of("w_down")].at[
            -1, -1, :args.last_experts_dropped].set(0)
        if args.last_mlp_dropped:
            layers[of("ws_down")] = layers[of("ws_down")].at[-1, -1].set(0)
        served_params = dataclasses.replace(served_params, layers=layers)
    if args.routed_dropped:
        name = next(k for k in served_params.layers
                    if k.split(".")[0] == "w_down"
                    and k.split(".")[1].startswith("mlp"))
        stack = served_params.layers[name]      # [repeats, E blocks, ...]
        places = (range(stack.shape[1]) if args.routed_dropped == "all"
                  else [int(p) for p in args.routed_dropped.split(",")])
        for place in places:
            stack = stack.at[:, place].set(0)
        served_params = dataclasses.replace(
            served_params, layers=dict(served_params.layers, **{name: stack}))
    toks, served_lp, paths, state = served(served_cfg, served_params,
                                           prompts, args)
    t_served = time.monotonic() - t0
    worst, own, margins, means = [], [], [], []
    for r in range(args.batch):
        ids = np.concatenate([prompts[r], toks[r]])
        ref, margin = reference_logprobs(cfg, params, ids, args.prompt)
        if margin is not None:
            margins.extend(float(m) for m in margin.ravel())
        err = np.abs(served_lp[r] - ref)              # [steps + 1, V]
        worst.extend(float(e) for e in err.max(-1))
        means.append(float(err.mean()))
        # the served path's own choice at each position (the last
        # position's is never fed back: take its argmax)
        chosen = list(toks[r]) + [int(served_lp[r, -1].argmax())]
        own.extend(float(err[i, t]) for i, t in enumerate(chosen))
    tol_mean, tol_max = FAMILY_TOL.get(cfg.family, (TOL_MEAN, TOL_MAX))
    if cfg.summary_kv and args.prompt + args.steps > cfg.eva_window:
        tol_mean, tol_max = EVA_LONG_TOL
    row = {"model": args.model, "platform": dev.platform,
           "device_kind": dev.device_kind, "kv_dtype": args.kv_dtype,
           "bf16_router": args.bf16_router,
           "bf16_softmax_state": args.bf16_softmax_state,
           "window_ignored": args.window_ignored,
           "summaries_withheld": args.summaries_withheld,
           "bf16_state": args.bf16_state,
           "state_not_carried": args.state_not_carried,
           "conv_tail_dropped": args.conv_tail_dropped,
           "ssd_skip_dropped": args.ssd_skip_dropped,
           "last_experts_dropped": args.last_experts_dropped,
           "routed_dropped": args.routed_dropped,
           "last_mlp_dropped": args.last_mlp_dropped,
           "logits_scaling_dropped": args.logits_scaling_dropped,
           "hc_sinkhorn_iters": args.hc_sinkhorn_iters,
           "bf16_coef_maps": args.bf16_coef_maps,
           "selection": args.selection,
           "batch": args.batch,
           "prompt": args.prompt, "steps": args.steps,
           "positions": len(worst), "paths": paths,
           "max_over_vocab_max": max(worst),
           "max_over_vocab_mean": sum(worst) / len(worst),
           "mean_abs": sum(means) / len(means),
           "own_token_max": max(own), "own_token_mean": sum(own) / len(own),
           "tol_mean": tol_mean, "tol_max": tol_max,
           "router_pairs": len(margins),
           "router_margin_under_1e-3": sum(m < 1e-3 for m in margins),
           "router_margin_under_1e-4": sum(m < 1e-4 for m in margins),
           "served_s": round(t_served, 1),
           "total_s": round(time.monotonic() - t0, 1)}
    row["ok"] = bool(row["max_over_vocab_mean"] <= tol_mean
                     and row["max_over_vocab_max"] <= tol_max)
    if state is not None:
        # what log-probabilities cannot see: a float32 state does not
        # survive a rounding to bfloat16 (``--bf16-state`` reads 0)
        # ... and the served state against the reference's after the same
        # ids, the largest plane (the family's two limits, as its replay
        # holds them in a benchmark run)
        import families
        fam = families.load(cfg.family)
        readings = [fam.state_readings(
            state[:, r], reference_states(
                cfg, params, np.concatenate([prompts[r], toks[r]])))
            for r in range(args.batch)]
        residue = min(min(r["f32_residue"]) for r in readings)
        row["state_f32_residue"] = residue
        row["state_f32_residue_min"] = fam.STATE_F32_RESIDUE_MIN
        row["state_rel_err"] = max(max(r["rel_err"]) for r in readings)
        row["state_rel_tol"] = fam.STATE_REL_TOL
        row["ok"] = (row["ok"] and residue >= row["state_f32_residue_min"]
                     and row["state_rel_err"] <= row["state_rel_tol"])
        if hasattr(fam, "LOGPROB_MEAN_TOL"):
            # ... and its own limit on the emitted tokens' log-probabilities
            # (a batch of one is the benchmark's canary: one mean a request)
            row["own_token_mean_tol"] = fam.LOGPROB_MEAN_TOL
            row["ok"] = (row["ok"] and row["own_token_mean"]
                         <= fam.LOGPROB_MEAN_TOL)
    if cfg.sparse_kind is not None:
        # the blocks the served program kept against the equations' (the
        # first request's; its last token was emitted and never fed), from
        # a SECOND pass behind the tap and over that pass's own tokens: the
        # host callback changes what the compiler fuses around it (the same
        # seed reads 0.0051 behind it and 0.0044 without: my chip runs, PR
        # 69), so the log-probabilities above are the program's as served
        kept = keep_tap(read_positions(args.prompt,
                                       args.prompt + args.steps - 1))
        tapped = served(served_cfg, served_params, prompts, args)[0]
        jax.effects_barrier()
        row.update(selection_reading(
            cfg, params, np.concatenate([prompts[0], tapped[0][:-1]]),
            prompts.shape[1], kept))
        row["ok"] = row["ok"] and (row["kept_differ_max"]
                                   <= row["kept_differ_max_tol"])
    if args.replay:
        row["replay"] = replay_readings(cfg, params, prompts, toks,
                                        served_lp, state)
        row["ok"] = row["ok"] and not any("error" in r
                                          for r in row["replay"])
    if cfg.hc_streams:
        # what log-probabilities see least: how far the served doubly-
        # stochastic maps stand from 1 (``hc_sinkhorn_residual``)
        row["hc_sinkhorn_residual"] = hc_sinkhorn_residual(
            served_cfg, params, prompts[0])
        row["hc_sinkhorn_residual_max"] = HC_RESIDUAL_MAX
        row["ok"] = row["ok"] and (row["hc_sinkhorn_residual"]
                                   <= HC_RESIDUAL_MAX)
    print("MODEL_PARITY " + json.dumps(row), flush=True)
    return 0 if row["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
